"""Plain float32 forward pass of LFM2-8B-A1B's trunk as the frame reader
runs it: the reference for ``lfm2_8b_a1b_prefill_epix10k2m``.

Sizes from the model's public ``config.json`` (``lfm2_moe``); what it does
not fix is listed in the configuration file's ``assumed``. One sequence of
``T`` tokens, ``x [T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) *
g``, every layer ``l`` is

    h  = x + Op_l(rms(x; g1))            x' = h + FF_l(rms(h; g2))

    Op, "conv":            [B | C | z] = a W_in        u = B * z
                           c[t] = sum_{j<3} w[:, j] * u[t - 2 + j]     u[<0] = 0
                           Op = (C * c) W_out
    Op, "full_attention":  q, k = rope(rms_head(a Wq; gq)), rope(rms_head(a Wk; gk))   v = a Wv
                           o[t,h] = sum_{s<=t} softmax_s(q[t,h] . k[s,h//(H/G)] / sqrt(d_h)) v[s,h//(H/G)]
                           Op = concat_h(o) Wo
    FF, l < num_dense_layers:  (silu(b W1) * (b W3)) W2
    FF, else:              s = sigmoid(b Wr)      T(t) = the k_e largest of s[t] + bias
                           gate_e = s_e / (sum_{T(t)} s + 1e-6) * routed_scaling_factor
                           FF = sum_{e in T(t), e held} gate_e (silu(b W1_e) * (b W3_e)) W2_e

``rope`` turns pair ``(i, i + d_h/2)`` by ``t * theta**(-2i/d_h)``, ``t``
the index within the sequence; the output is ``rms(x_L; g) E^T`` with
``E`` the embedding table (tied). No kernel, no grouped product, no
batch: attention as a softmax over a block of queries' whole rows, the
convolution as three shifted copies, the experts as a loop over all held
ones with a 0/1 membership in the gate (equal scores: the lower index).
Float32 at ``Precision.HIGHEST``; ``compute=jnp.bfloat16`` gives the
precision yardstick as ``keye_decoder`` describes it, whose ``_mm`` (the
rounding behind ``lax.optimization_barrier``), ``rms``, ``rotate`` and
``angles_1d`` are used here: the same few lines, and no code of the
package under test.

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls that show that ``correct`` can tell one (``tests/lfm2_controls.py``):
``taps_used`` (which taps of the convolution count), ``scoring``
(``"softmax"``: a softmax router without bias or epsilon), ``select_bias``
(False: the bias left out of the selection)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, angles_1d, dense_mlp, rms, rotate


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping (LFM2's
    Hugging Face keys), apart from the program's."""
    heads = int(cfg["num_attention_heads"])
    n_exp = int(cfg["num_experts"])
    m = {
        "H": heads, "G": int(cfg["num_key_value_heads"]),
        "dh": int(cfg["hidden_size"]) // heads, "eps": float(cfg["norm_eps"]),
        "theta": float(cfg["rope_theta"]), "layer_types": tuple(cfg["layer_types"]),
        "n_dense": int(cfg["num_dense_layers"]), "taps": int(cfg["conv_L_cache"]),
        "E": n_exp, "k_e": int(cfg["num_experts_per_tok"]),
        "experts_held": tuple(cfg.get("experts_held", (0, n_exp))),
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "select_bias": bool(cfg["use_expert_bias"]), "scoring": "sigmoid",
    }
    m["taps_used"] = tuple(range(m["taps"]))
    m.update(fault)
    return m


def conv_op(p, a, m, compute):
    """The gated short convolution from the normed input ``a [T, d]``."""
    t, d = a.shape
    b, c, z = jnp.split(_mm(a, p["w_in"], compute), 3, axis=-1)
    u = jnp.concatenate([jnp.zeros((m["taps"] - 1, d), jnp.float32), b * z])
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(w[:, j] * u[j:j + t] for j in m["taps_used"])  # u[j + t'] is u[t' - 2 + j]
    return _mm(c * conv, p["w_out"], compute)


def attention_op(p, a, m, compute, block):
    """Causal grouped-query attention from the normed input ``a [T, d]``,
    a block of queries at a time."""
    t = a.shape[0]
    H, G, dh, eps = m["H"], m["G"], m["dh"], m["eps"]
    ang = angles_1d(np.arange(t), m["theta"], dh // 2)
    q = rotate(rms(_mm(a, p["wq"], compute).reshape(t, H, dh), p["q_norm"], eps), ang)
    k = rotate(rms(_mm(a, p["wk"], compute).reshape(t, G, dh), p["k_norm"], eps), ang)
    v = _mm(a, p["wv"], compute).reshape(t, G, dh)

    def block_out(t0):
        open_ = jnp.arange(t)[None, :] <= (t0 + jnp.arange(block))[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block)
        out = []
        for h in range(H):
            g = h // (H // G)  # query head h reads key-value head h // (H/G)
            logit = _mm(qb[:, h], k[:, g].T, compute) / np.sqrt(dh)
            prob = jax.nn.softmax(jnp.where(open_, logit, -jnp.inf), axis=-1)
            out.append(_mm(prob, v[:, g], compute))
        return jnp.concatenate(out, axis=-1)

    o = jax.lax.map(block_out, jnp.arange(0, t, block))
    return _mm(o.reshape(t, H * dh), p["wo"], compute)


def experts(p, b, m, compute):
    """The expert feed-forward from the normed input ``b [T, d]``: the
    held experts' part of the layer's result, and each token's expert
    set ``[T, E]``."""
    first, count = m["experts_held"]
    logits = _mm(b, p["router"], compute)
    if m["scoring"] == "softmax":  # the fault: another router altogether
        s, by, gate_eps = jax.nn.softmax(logits, axis=-1), 0.0, 0.0
    else:
        s = jax.nn.sigmoid(logits)
        by, gate_eps = (p["router_bias"].astype(jnp.float32) if m["select_bias"] else 0.0), 1e-6
    rank = jnp.argsort(jnp.argsort(-(s + by), axis=-1, stable=True), axis=-1)
    chosen = rank < m["k_e"]  # equal scores: the lower index first
    gate = s * chosen
    if m["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + gate_eps)
    gate = gate * m["scale"]

    def one(e, y):
        h = jax.nn.silu(_mm(b, p["w_gate"][e], compute)) * _mm(b, p["w_up"][e], compute)
        g = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1, keepdims=True)
        return y + g * _mm(h, p["w_down"][e], compute)

    return jax.lax.fori_loop(0, count, one, jnp.zeros(b.shape, jnp.float32)), chosen


def kinds(m) -> list:
    """Each layer's ``(operator, its feed-forward is dense)``."""
    return [(op, i < m["n_dense"]) for i, op in enumerate(m["layer_types"])]


def layer(p, x, kind, m, compute=jnp.float32, block=128):
    """One layer of ``kind`` (an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    op, dense = kind
    a = rms(x, p["norm1"], m["eps"])
    if op == "conv":
        x = x + conv_op(p, a, m, compute)
    else:
        x = x + attention_op(p, a, m, compute, block)
    b = rms(x, p["norm2"], m["eps"])
    if dense:
        return x + dense_mlp(p, b, compute)
    return x + experts(p, b, m, compute)[0]


def patches_of(frame, patch: int):
    """One calibrated frame ``[P, H, W]`` as ``[P * H/patch * W/patch,
    patch^2]``: panel after panel, patch row after patch row."""
    panels, height, width = frame.shape
    x = frame.reshape(panels, height // patch, patch, width // patch, patch)
    return jnp.transpose(x, (0, 1, 3, 2, 4)).reshape(-1, patch * patch)


def embed(params, patches, prompt_ids, compute=jnp.float32):
    return jnp.concatenate([
        _mm(patches.astype(jnp.float32), params["patch"], compute),
        params["embed"][prompt_ids].astype(jnp.float32),
    ])


def logits_of(params, x, m, compute=jnp.float32):
    """The tied head: the final norm, then the embedding table's rows."""
    return _mm(rms(x, params["norm"], m["eps"]), params["embed"].T, compute)


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``."""
    x = embed(params, patches, prompt_ids, compute)
    for p, kind in zip(params["layers"], kinds(m)):
        x = layer(p, x, kind, m, compute, block)
    return x
