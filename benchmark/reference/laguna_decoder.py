"""Plain float32 forward pass of Laguna-S-2.1's block (``laguna``) as the
frame reader runs it: the reference for ``laguna_s21_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is
listed in the configuration file's ``assumed``. One sequence of ``T``
tokens, ``x [T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g``
every layer ``l`` is ``h = x + Attn_l(rms(x; g1))``, ``x' = h + FF_l(rms(h;
g2))``:

    Attn_l(a): q = a W_q -> [T, H_l, 128]     H_l = num_attention_heads_per_layer[l]  (48 full, 72 sliding)
               k, v = a W_k, a W_v -> [T, 8, 128]       query head h reads key head h // (H_l / 8)
               q, k turned by the LAYER TYPE's rotary (rope_parameters[layer_types[l]]), the leading
                 partial_rotary_factor * 128 components of a head as pairs (i, i + half), the rest passed
                 as they are, unscaled:
                   sliding_attention: all 64 pairs, theta 10,000, plain
                   full_attention:    32 pairs (64 of 128), theta 500,000, YaRN, the cosines and sines
                                      times attention_factor
               score[t, j, h] = q[t, h] . k[j, h // (H_l/8)] 128^(-1/2)
               open(t, j):  j <= t  (full)      t - sliding_window < j <= t  (sliding: 512 keys, t's own among them)
               o[t, h] = sum_j softmax_j(score | open) v[j, .] * sigmoid(a W_g)[t, h]      (gating: per-head)
               Attn = concat_h(o) W_o
    FF_l, l in mlp_only_layers:  (silu(b W1) * (b W3)) W2           (12,288 wide)
    FF_l, else:  s = sigmoid(b W_r) over 256       T(t) = the 10 largest of s[t]  (equal: the lower index)
                 gate_e = s_e / (sum_{T(t)} s + 1e-20) * moe_routed_scaling_factor
                 FF = sum_{e in T(t), e held} gate_e E_e(b) + Shared(b),   E(b) = (silu(b W1) * (b W3)) W2

YaRN, written out from the six numbers of ``rope_parameters.full_attention``
(``rope_theta``, ``factor``, ``original_max_position_embeddings``,
``beta_fast``, ``beta_slow``, ``attention_factor``) as the public
``transformers`` code computes it over the ROTARY width ``dim = 64``: pair
``i`` of 32 has the frequency ``theta^(-2i/dim)`` where the linear ramp
between the two correction dimensions (``dim ln(original / (2 pi beta)) /
(2 ln theta)`` for ``beta_fast`` and ``beta_slow``, floor and ceiling, kept
inside ``[0, dim - 1]``; equal: the upper + 0.001) reads 0, that over
``factor`` where it reads 1, their blend between.

No kernel, no tile, no batch: attention as a softmax under a dense ``[block,
T]`` mask (the band written as its two compares) over a block of queries'
whole rows, a key head's query heads stacked; the experts as a loop over
the held ones with a 0/1 membership in the gate (``kimi_k2_decoder``'s few
lines, whose router this one is read as: no selection bias); the shared
expert as one more gated MLP, ungated. The holder's SHARE is the
reference's too: the 64 held experts' weights and the vocabulary slice.
Float32 at ``Precision.HIGHEST``; ``compute=jnp.bfloat16`` gives the
precision yardstick as ``keye_decoder`` describes it. No code of the
package under test.

Departures from the published code, each the program's own too: the
softmax scale multiplies the score here and the query there; rotary pairs
are ``(i, i + half)`` of the turned part (rotate-half, as the public code).

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/laguna_controls.py``): ``window`` (0: no window in the
sliding layers; 1,024: another one), ``rotary_of`` (which layer type's
rotary a layer type turns by: the full layers' in a sliding layer, or the
reverse), ``attention_factor`` (False: the cosines and sines as they are),
``attn_gate`` (False), ``k_e`` (8 of the 10 experts a token), ``scoring``
(``"softmax"``), ``shared`` (False)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, dense_mlp, rms, rotate
from benchmark.reference.kimi_k2_decoder import (  # noqa: F401 — the adapter reads them here
    embed, experts, logits_of, patches_of, shared_expert)

FULL, SLIDING = "full_attention", "sliding_attention"  # layer_types, as the file spells them


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping (Laguna's
    Hugging Face keys, and the file's ``router_scoring``), apart from the
    program's."""
    held = int(cfg["num_experts"])
    m = {
        "G": int(cfg["num_key_value_heads"]), "dh": int(cfg["head_dim"]),
        "heads": tuple(int(h) for h in cfg["num_attention_heads_per_layer"]),
        "layer_types": tuple(cfg["layer_types"]), "window": int(cfg["sliding_window"]),
        "rope": cfg["rope_parameters"], "rotary_of": {FULL: FULL, SLIDING: SLIDING},
        "attention_factor": True, "attn_gate": cfg["gating"] == "per-head",
        "eps": float(cfg["rms_norm_eps"]), "dense": tuple(int(i) for i in cfg["mlp_only_layers"]),
        "E": int(cfg.get("router_experts", held)), "k_e": int(cfg["num_experts_per_tok"]),
        "experts_held": tuple(cfg.get("experts_held", (0, held))),
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "scale": float(cfg["moe_routed_scaling_factor"]), "scoring": str(cfg["router_scoring"]),
        "select_bias": False, "n_shared": int(bool(cfg["shared_expert_intermediate_size"])),
        "shared": True,
    }
    layers = int(cfg["num_hidden_layers"])
    if (len(m["layer_types"]) != layers or len(m["heads"]) != layers
            or set(m["layer_types"]) - {FULL, SLIDING} or cfg.get("moe_router_logit_softcapping")):
        raise ValueError("only Laguna's two operators, a layer's own head count and a router "
                         "without a soft cap are written here")
    m.update(fault)
    return m


def rotary(t: int, rope: dict, head_dim: int, with_factor: bool = True):
    """A layer type's rotary from its ``rope_parameters`` entry: ``(angles
    [t, pairs], the width turned, what the cosines and sines are multiplied
    by)``."""
    width = int(round(head_dim * float(rope.get("partial_rotary_factor", 1))))
    theta = float(rope["rope_theta"])
    inv_freq = 1.0 / theta ** (np.arange(0, width, 2, dtype=np.float64) / width)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
        def correction_dim(turns):  # the dimension that makes `turns` turns over the original positions
            return width * np.log(float(rope["original_max_position_embeddings"])
                                  / (turns * 2 * np.pi)) / (2 * np.log(theta))

        low = max(int(np.floor(correction_dim(float(rope["beta_fast"])))), 0)
        high = min(int(np.ceil(correction_dim(float(rope["beta_slow"])))), width - 1)
        top = high + 0.001 if low == high else high
        ramp = np.clip((np.arange(width // 2, dtype=np.float64) - low) / (top - low), 0.0, 1.0)
        inv_freq = inv_freq / float(rope["factor"]) * ramp + inv_freq * (1.0 - ramp)
        if with_factor:
            factor = float(rope["attention_factor"])
    elif rope.get("rope_type", "default") != "default":
        raise ValueError(f"rotary type {rope['rope_type']!r} is not written here")
    angles = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * inv_freq, jnp.float32)
    return angles, width, factor


def attention(p, a, op: str, heads: int, m, compute, block):
    """Grouped-query attention, full or under the band, with the per-head
    output gate, from the normed input ``a [T, d]``, a block of queries at
    a time."""
    t = a.shape[0]
    G, dh = m["G"], m["dh"]
    rep = heads // G
    ang, width, factor = rotary(t, m["rope"][m["rotary_of"][op]], dh, m["attention_factor"])

    def turn(u):  # the leading `width` of a head turn, the rest passes as it is, unscaled
        return jnp.concatenate([rotate(u[..., :width], ang) * factor, u[..., width:]], axis=-1)

    q = turn(_mm(a, p["wq"], compute).reshape(t, heads, dh))
    k = turn(_mm(a, p["wk"], compute).reshape(t, G, dh))
    v = _mm(a, p["wv"], compute).reshape(t, G, dh)
    # key head g's query heads stacked: a block's scores are one batched product a key head
    q = jnp.transpose(q.reshape(t, G, rep, dh), (1, 2, 0, 3))
    k, v = jnp.transpose(k, (1, 2, 0)), jnp.transpose(v, (1, 0, 2))
    window = m["window"] if op == SLIDING else 0

    def block_out(t0):
        row, col = (t0 + jnp.arange(block))[:, None], jnp.arange(t)[None, :]
        open_ = col <= row
        if window:
            open_ = open_ & (col > row - window)
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block, axis=2).reshape(G, rep * block, dh)
        logit = (_mm(qb, k, compute) * dh ** -0.5).reshape(G, rep, block, t)
        prob = jax.nn.softmax(jnp.where(open_, logit, -jnp.inf), axis=-1)
        out = _mm(prob.reshape(G, rep * block, t), v, compute).reshape(G, rep, block, dh)
        return jnp.transpose(out, (2, 0, 1, 3)).reshape(block, heads, dh)

    o = jax.lax.map(block_out, jnp.arange(0, t, block)).reshape(t, heads, dh)
    if m["attn_gate"]:
        o = o * jax.nn.sigmoid(_mm(a, p["w_attn_gate"], compute))[:, :, None]
    return _mm(o.reshape(t, heads * dh), p["wo"], compute)


def kinds(m) -> list:
    """Each layer's kind: ``(its operator, its query heads, is its feed-forward dense?)``."""
    return [(op, m["heads"][i], i in m["dense"]) for i, op in enumerate(m["layer_types"])]


def layer(p, x, kind, m, compute=jnp.float32, block=128):
    """One layer (``kind``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    op, heads, dense = kind
    x = x + attention(p, rms(x, p["norm1"], m["eps"]), op, heads, m, compute, block)
    b = rms(x, p["norm2"], m["eps"])
    if dense:
        return x + dense_mlp(p, b, compute)
    y = experts(p, b, m, compute)[0]
    if m["n_shared"] and m["shared"]:
        y = y + shared_expert(p, b, compute)
    return x + y


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``."""
    x = embed(params, patches, prompt_ids, compute)
    for p, kind in zip(params["layers"], kinds(m)):
        x = layer(p, x, kind, m, compute, block)
    return x
