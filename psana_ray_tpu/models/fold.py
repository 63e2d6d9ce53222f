"""Train→serve continuity: fold BatchNorm statistics into FrozenAffine.

The served models (``norm='frozen'`` under ``jax.jit``, and the fused
ResNet path in models/pallas_resnet.py) consume the ``norm='frozen'``
parameter form — per-channel affine
constants that fuse into conv epilogues. This module supplies the
supported route from a TRAINED checkpoint to that form, closing the gap
the reference's mission statement implies (streaming *to inference*,
reference ``project.toml:4``) but its 260 lines never build.

Train with ``norm='batch'`` (``_norm`` in models/resnet.py —
``nn.BatchNorm`` with running statistics in the ``batch_stats``
collection), then::

    serving = fold_batchnorm(variables)            # {'params': ...}
    logits  = resnet_fused_infer(serving, x)       # or model(norm='frozen')

The fold is EXACT: eval-mode BatchNorm computes
``(x - mean)/sqrt(var + eps) * gamma + beta``, which is the affine
``x * scale + bias`` with ``scale = gamma/sqrt(var + eps)`` and
``bias = beta - mean * scale`` — precisely ``FrozenAffine``. The module
renames each ``BatchNorm_i`` subtree to ``FrozenAffine_i`` (explicitly
named norms — ``stem_norm``, ``proj_norm`` — keep their names, which are
kind-independent), so the folded tree is bit-compatible with
``ResNetClassifier(norm='frozen')`` / ``PeakNetUNetTPU(norm='frozen')``
and with the fused kernels' ``_block_params`` extractors.
"""

from __future__ import annotations

import re
from typing import Any, Dict

import numpy as np

_BN_EPS = 1e-5  # must match _norm(kind='batch') epsilon in models/resnet.py


def _fold_leaf(gamma, beta, mean, var, eps: float):
    # host numpy, deliberately: the fold is a one-off over a few dozen
    # tiny per-channel vectors — not worth a device dispatch each
    inv = 1.0 / np.sqrt(np.asarray(var, np.float32) + np.float32(eps))
    scale = np.asarray(gamma, np.float32) * inv
    bias = np.asarray(beta, np.float32) - np.asarray(mean, np.float32) * scale
    return {"scale": scale, "bias": bias}


def fold_batchnorm(variables: Any, eps: float = _BN_EPS) -> Dict[str, Any]:
    """``{'params', 'batch_stats'}`` (norm='batch') → ``{'params'}`` (norm='frozen').

    Walks the two collections in parallel: any module path present in
    ``batch_stats`` with ``mean``/``var`` leaves is a BatchNorm; its
    params-side ``scale``/``bias`` fold with the statistics into a
    FrozenAffine ``{scale, bias}`` and the subtree key is renamed
    ``BatchNorm_i`` → ``FrozenAffine_i``. Everything else passes through
    unchanged. Accepts boxed (LogicallyPartitioned) or plain trees;
    returns a plain (unboxed) tree ready for ``model.apply`` and the
    fused-inference entry points.
    """
    from flax.core import meta

    unboxed = meta.unbox(variables)
    params = unboxed.get("params", unboxed)
    stats = unboxed.get("batch_stats")
    if stats is None:
        raise ValueError(
            "fold_batchnorm needs a 'batch_stats' collection — train the "
            "model with norm='batch' (models/resnet.py _norm) and pass the "
            "full variables dict {'params': ..., 'batch_stats': ...}"
        )

    def walk(p_node, s_node):
        out = {}
        for key, p_child in p_node.items():
            s_child = s_node.get(key) if isinstance(s_node, dict) else None
            if isinstance(s_child, dict) and "mean" in s_child and "var" in s_child:
                new_key = re.sub(r"^BatchNorm_(\d+)$", r"FrozenAffine_\1", key)
                out[new_key] = _fold_leaf(
                    p_child["scale"], p_child["bias"],
                    s_child["mean"], s_child["var"], eps,
                )
            elif isinstance(p_child, dict):
                out[key] = walk(p_child, s_child if isinstance(s_child, dict) else {})
            else:
                out[key] = p_child
        return out

    return {"params": walk(params, stats)}


def export_serving_params(variables: Any, path: str, eps: float = _BN_EPS):
    """Fold and save serving params in one step (orbax via checkpoint.py).

    Returns the folded ``{'params': ...}`` tree (also written to ``path``,
    loadable with :func:`psana_ray_tpu.checkpoint.load_params`).
    """
    from psana_ray_tpu.checkpoint import save_params

    serving = fold_batchnorm(variables, eps=eps)
    # persist as host numpy: serving checkpoints are small (f32 params) and
    # this keeps the export path device-free
    host = _to_host(serving)
    save_params(path, host)
    return serving


def _to_host(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x), tree)
