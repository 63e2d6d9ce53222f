"""A config-driven decoder block and trunk, and the frame reader built on it.

Nothing else in ``models/`` has an RMS norm, a rotary embedding, a gated
MLP or a block assembled from a configuration (``vit.py`` hard-codes
LayerNorm, GELU and a position table). This module builds the text
decoder of a vision-language model from the keys of its published
``config.json``:

    x  -> x + Wo . attention(rms(x))          pre-norm, per-head q/k RMS norm,
    x  -> x + mlp(rms(x))                     multimodal rotary on q and k

with grouped-query heads; attention either plain causal or restricted, per
query, to the ``topk`` keys a learned indexer ranks highest
(``parallel/sparse_attention.py``); the MLP either a dense gated-SiLU one
or top-k of ``num_experts`` experts without dropped tokens
(``parallel/moe.dropless_moe``), of which this holder may hold a share
(``experts_held``).

:func:`frame_step` is the serving step of a FRAME READER: one detector
frame, calibrated on the device, cut into patches, embedded by a linear
patch embedding (standing in for the model's vision tower), followed by a
text prompt, read through the trunk; the logits of the next token come
back with a small statistics vector (:data:`STEP_STATS`) that
:func:`fold_step_stats` adds to a pipeline's counters. Weights are an
ARGUMENT of the step: one step keys alike in the compile cache from every
entry point.

Not imported by ``psana_ray_tpu`` nor ``psana_ray_tpu.models`` at package
import: the serving CLIs that never read a frame with it do not pay for it.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from psana_ray_tpu.parallel import sparse_attention as sa
from psana_ray_tpu.parallel.moe import dropless_moe

# what frame_step's statistics vector holds, summed over the layers
STEP_STATS = (
    "expert_tokens_max_total",   # the busiest held expert's token slots
    "expert_tokens_mean_total",  # token slots per expert, were the load even: S * k / E
    "attn_tiles_live_total",     # 512 x 512 tiles at or below the diagonal with a selected pair
    "attn_tiles_causal_total",   # all such tiles
)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    rms_eps: float
    rope_theta: float
    mrope_section: Tuple[int, int, int]
    # learned sparse attention (None: plain causal attention)
    indexer_heads: Optional[int] = None
    indexer_head_dim: int = 0
    topk: int = 0
    # the computation's tiles; no effect on the mathematics. Measured on the
    # v5e at 34,304 tokens: selection 36 ms a layer at 128 queries against 67
    # at 256 (512 needs 70 MB of VMEM for a tile's score row), attention 124
    # ms at 256 against 146 at 128 and 128 at 512
    q_tile: int = 128  # of the selection kernel (a query tile's whole score row sits in VMEM)
    kv_tile: int = 512  # sa_config's kv_chunk_size
    attn_q_tile: int = 256  # of the attention kernel, a multiple of q_tile
    # experts (num_experts 0: a dense gated MLP of intermediate_size)
    num_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    norm_topk_prob: bool = True
    intermediate_size: int = 0
    patch: int = 8

    @property
    def patch_dim(self) -> int:
        return self.patch * self.patch

    @classmethod
    def from_mapping(cls, m: Mapping) -> "DecoderConfig":
        """From the keys of a Hugging Face ``config.json`` (as the
        benchmark's configuration file repeats them), plus ``patch`` and
        ``experts_held``."""
        sa_cfg = m.get("sa_config")
        n_exp = int(m.get("num_experts", 0))
        return cls(
            hidden_size=int(m["hidden_size"]), num_layers=int(m["num_hidden_layers"]),
            num_heads=int(m["num_attention_heads"]), num_kv_heads=int(m["num_key_value_heads"]),
            head_dim=int(m["head_dim"]), vocab_size=int(m["vocab_size"]),
            rms_eps=float(m["rms_norm_eps"]), rope_theta=float(m["rope_theta"]),
            mrope_section=tuple(int(v) for v in m["rope_scaling"]["mrope_section"]),
            indexer_heads=int(sa_cfg["indexer_num_heads"]) if sa_cfg else None,
            indexer_head_dim=int(sa_cfg["indexer_head_dim"]) if sa_cfg else 0,
            topk=int(sa_cfg["topk"]) if sa_cfg else 0,
            kv_tile=int(sa_cfg["kv_chunk_size"]) if sa_cfg else 512,
            num_experts=n_exp, experts_per_token=int(m.get("num_experts_per_tok", 0)),
            expert_width=int(m.get("moe_intermediate_size", 0)),
            experts_held=tuple(int(v) for v in m.get("experts_held", (0, n_exp))),
            norm_topk_prob=bool(m.get("norm_topk_prob", True)),
            intermediate_size=int(m.get("intermediate_size", 0)),
            patch=int(m.get("patch", 8)),
        )


# ---------------------------------------------------------------------------
# parameters: bf16, made on the device from a key
# ---------------------------------------------------------------------------

def init_params(cfg: DecoderConfig, key, dtype=jnp.bfloat16) -> dict:
    """normal(0, 0.02) matrices and unit gains, as one tree:
    ``{"patch", "embed", "layers": [..], "norm", "head"}``. Call under
    ``jax.jit`` to make the weights on the device."""
    d, hd = cfg.hidden_size, cfg.head_dim
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 8))

    def w(*shape):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(dtype)

    def gain(n):
        return jnp.ones((n,), dtype)

    layers = []
    for _ in range(cfg.num_layers):
        p = {
            "norm1": gain(d), "wq": w(d, cfg.num_heads * hd), "wk": w(d, cfg.num_kv_heads * hd),
            "wv": w(d, cfg.num_kv_heads * hd), "q_norm": gain(hd), "k_norm": gain(hd),
            "wo": w(cfg.num_heads * hd, d), "norm2": gain(d),
        }
        if cfg.indexer_heads:
            di = cfg.indexer_head_dim
            p.update(idx_wq=w(d, cfg.indexer_heads * di), idx_wk=w(d, di),
                     idx_k_norm=gain(di), idx_ww=w(d, cfg.indexer_heads))
        if cfg.num_experts:
            held = cfg.experts_held[1]
            p.update(router=w(d, cfg.num_experts), w_gate=w(held, d, cfg.expert_width),
                     w_up=w(held, d, cfg.expert_width), w_down=w(held, cfg.expert_width, d))
        else:
            p.update(w_gate=w(d, cfg.intermediate_size), w_up=w(d, cfg.intermediate_size),
                     w_down=w(cfg.intermediate_size, d))
        layers.append(p)
    return {"patch": w(cfg.patch_dim, d), "embed": w(cfg.vocab_size, d), "layers": layers,
            "norm": gain(d), "head": w(d, cfg.vocab_size)}


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def rms_norm(u, g, eps: float):
    """``u / sqrt(mean(u^2) + eps) * g`` over the last axis, in float32."""
    u = u.astype(jnp.float32)
    return u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def frame_positions(panels: int, rows: int, cols: int, prompt_len: int) -> np.ndarray:
    """``[S, 3]`` positions ``(t, h, w)``: the patch of panel ``p``, row
    ``r``, column ``c`` sits at ``(p, r, c)`` (a panel is to the detector
    what a frame is to a video: a disjoint sensor), panel-major as
    ``patchify_panels`` orders them; prompt token ``i`` has all three equal
    to ``max(panels, rows, cols) + i``."""
    p, r, c = np.meshgrid(np.arange(panels), np.arange(rows), np.arange(cols), indexing="ij")
    patches = np.stack([p.ravel(), r.ravel(), c.ravel()], axis=1)
    text = max(panels, rows, cols) + np.arange(prompt_len)
    return np.concatenate([patches, np.stack([text] * 3, axis=1)]).astype(np.int32)


def rotary_angles(pos, theta: float, pairs: int, sections=None):
    """``[S, pairs]`` angles: pair ``i`` turns by ``pos * theta**(-i/pairs)``;
    with ``sections`` (multimodal rotary) ``pos`` is ``[S, 3]`` and pair
    ``i`` reads the position component of the section it falls in."""
    inv_freq = theta ** (-np.arange(pairs, dtype=np.float64) / pairs)
    if sections is None:
        return jnp.asarray(pos, jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    which = np.repeat(np.arange(len(sections)), sections)  # [pairs] -> component
    return jnp.asarray(pos, jnp.float32)[:, which] * jnp.asarray(inv_freq, jnp.float32)


def rotate(x, angles):
    """``x [S, heads, 2*pairs]`` turned by ``angles [S, pairs]``; pair
    ``i`` is components ``(i, i + pairs)`` (the rotate-half convention)."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _projections(p, x, angles, cfg: DecoderConfig):
    s = x.shape[0]
    dt = x.dtype
    a = rms_norm(x, p["norm1"], cfg.rms_eps).astype(dt)
    q = rms_norm(_mm(a, p["wq"]).reshape(s, cfg.num_heads, cfg.head_dim), p["q_norm"], cfg.rms_eps)
    k = rms_norm(_mm(a, p["wk"]).reshape(s, cfg.num_kv_heads, cfg.head_dim), p["k_norm"], cfg.rms_eps)
    q = rotate(q, angles) * cfg.head_dim ** -0.5  # the softmax scale rides on q
    k = rotate(k, angles)
    v = _mm(a, p["wv"])
    return a, q.reshape(s, -1).astype(dt), k.reshape(s, -1).astype(dt), v.astype(dt)


def _indexer(p, a, idx_angles, cfg: DecoderConfig):
    s = a.shape[0]
    h, d = cfg.indexer_heads, cfg.indexer_head_dim
    q = rotate(_mm(a, p["idx_wq"]).reshape(s, h, d), idx_angles)
    k = rotate(rms_norm(_mm(a, p["idx_wk"]), p["idx_k_norm"], cfg.rms_eps)[:, None, :], idx_angles)
    w = _mm(a, p["idx_ww"]) * h ** -0.5
    # a call of its own: the kernel is `select_keys` in a device trace
    return jax.jit(sa.select_keys, static_argnames=("topk", "block_q", "block_k"))(
        jnp.transpose(q, (1, 0, 2)).astype(a.dtype), k[:, 0].astype(a.dtype), w,
        topk=cfg.topk, block_q=cfg.q_tile, block_k=cfg.kv_tile)


def _dense_mlp(p, b):
    h = (jax.nn.silu(_mm(b, p["w_gate"])) * _mm(b, p["w_up"])).astype(b.dtype)
    return _mm(h, p["w_down"]).astype(b.dtype)


def decoder_layer(p, x, angles, idx_angles, cfg: DecoderConfig):
    """One block: ``x [S, D]`` -> ``(x, stats [4] float32)``. Each part is
    a call of its own under its scope (``proj``, ``indexer``,
    ``sparse_attn``, ``moe``): a scope reaches the chip's profile only on
    ops inlined from a call."""
    s = x.shape[0]
    with jax.named_scope("proj"):
        a, q, k, v = jax.jit(_projections, static_argnums=3)(p, x, angles, cfg)
    with jax.named_scope("indexer"):
        if cfg.indexer_heads:
            mask, flags = jax.jit(_indexer, static_argnums=3)(p, a, idx_angles, cfg)
        else:
            mask, flags = sa.causal_tiles(s, cfg.q_tile, cfg.kv_tile)
        live, causal = sa.live_tiles(flags, mask.shape[2], mask.shape[3])
    with jax.named_scope("sparse_attn"):
        o = jax.jit(sa.masked_gqa_attention, static_argnames=("num_kv_heads", "block_q"))(
            q, k, v, mask, num_kv_heads=cfg.num_kv_heads,
            block_q=max(cfg.attn_q_tile, mask.shape[2]))
    with jax.named_scope("proj"):
        x = jax.jit(lambda x, o, wo: x + _mm(o, wo).astype(x.dtype))(x, o, p["wo"])
    with jax.named_scope("moe"):
        def mlp(p, x):
            b = rms_norm(x, p["norm2"], cfg.rms_eps).astype(x.dtype)
            if not cfg.num_experts:
                return x + _dense_mlp(p, b), jnp.zeros((), jnp.int32)
            y, tokens = dropless_moe(
                b, p["router"], p["w_gate"], p["w_up"], p["w_down"], k=cfg.experts_per_token,
                num_experts=cfg.num_experts, experts_held=cfg.experts_held,
                renormalise=cfg.norm_topk_prob)
            return x + y, jnp.max(tokens)

        x, busiest = jax.jit(mlp)(p, x)
    even = s * cfg.experts_per_token / cfg.num_experts if cfg.num_experts else 0.0
    stats = jnp.stack([busiest.astype(jnp.float32), jnp.float32(even),
                       live.astype(jnp.float32), jnp.float32(causal)])
    return x, stats


def trunk(params, x, pos, cfg: DecoderConfig):
    """``x [S, D]`` embedded tokens at ``pos [S, 3]`` (static) through
    every layer -> ``(x [S, D], stats [4])``."""
    pairs = cfg.head_dim // 2
    angles = rotary_angles(pos, cfg.rope_theta, pairs, cfg.mrope_section)
    idx_angles = None
    if cfg.indexer_heads:
        # the indexer's vectors turn with the sequence index alone
        idx_angles = rotary_angles(np.arange(x.shape[0]), cfg.rope_theta, cfg.indexer_head_dim // 2)
    stats = jnp.zeros((len(STEP_STATS),), jnp.float32)
    for p in params["layers"]:
        x, layer_stats = decoder_layer(p, x, angles, idx_angles, cfg)
        stats = stats + layer_stats
    return x, stats


def embed(params, patches, prompt_ids):
    """``patches [N, patch_dim]`` through the linear patch embedding, then
    the prompt's rows of the embedding table: ``[N + T, D]``."""
    dt = params["patch"].dtype
    return jnp.concatenate([
        _mm(patches.astype(dt), params["patch"]).astype(dt),
        jnp.take(params["embed"], prompt_ids, axis=0),
    ])


def logits_of(params, x, cfg: DecoderConfig):
    """Final norm and output head on rows ``x [N, D]`` -> ``[N, V]`` float32."""
    return _mm(rms_norm(x, params["norm"], cfg.rms_eps).astype(x.dtype), params["head"])


def frame_hidden(params, calib, frames, prompt_ids, *, cfg: DecoderConfig, threshold: float):
    """``frames [1, P, H, W]`` raw (batch ONE: a frame is a sequence),
    calibrated, cut into patches, embedded and followed by the prompt,
    through the trunk -> ``(x [S, D] at every token, stats [4] float32 in
    :data:`STEP_STATS`' order)``."""
    from psana_ray_tpu.models.vit import patchify_panels
    from psana_ray_tpu.ops import fused_calibrate

    if frames.shape[0] != 1:
        raise ValueError(f"a frame is one sequence: batch {frames.shape[0]} is not 1")
    _, panels, height, width = frames.shape
    pos = frame_positions(panels, height // cfg.patch, width // cfg.patch, prompt_ids.shape[0])
    with jax.named_scope("calib"):
        x = fused_calibrate(frames, *calib, threshold=threshold, out_dtype=jnp.bfloat16)
    with jax.named_scope("embed"):
        x = jax.jit(lambda p, x, ids: embed(p, patchify_panels(x, cfg.patch)[0], ids))(
            {"patch": params["patch"], "embed": params["embed"]}, x, prompt_ids)
    return trunk(params, x, pos, cfg)


def frame_step(params, calib, frames, prompt_ids, *, cfg: DecoderConfig, threshold: float):
    """The serving step: :func:`frame_hidden`, then the logits of the
    next token -> ``(logits [1, V] float32, stats [4] float32)``."""
    x, stats = frame_hidden(params, calib, frames, prompt_ids, cfg=cfg, threshold=threshold)
    with jax.named_scope("head"):
        logits = jax.jit(lambda p, x: logits_of(p, x, cfg))(
            {"norm": params["norm"], "head": params["head"]}, x[-1:])
    return logits, stats


def fold_step_stats(metrics, stats) -> None:
    """Add one step's statistics vector (on the host or the device) to the
    pipeline's counters of the same names (``PipelineMetrics.counters``:
    in ``snapshot()`` and so under ``/metrics``)."""
    for name, value in zip(STEP_STATS, np.asarray(stats, np.float64)):
        metrics.add_counter(name, float(value))
