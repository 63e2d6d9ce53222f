"""Plain float32 forward pass of Ling-3.0-flash's hybrid trunk
(``bailing_hybrid``) as the frame reader runs it: the reference for
``ling3_flash_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is
listed in the configuration file's ``assumed``. One sequence of ``T``
tokens, ``x [T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g``
every layer is ``h = x + Op(rms(x; g1))``, ``x' = h + FF(rms(h; g2))``, and
``Op`` is one of two (``layer_types``):

    KDA(a):   [qh | kh | vh] = a W_qkv, each through conv4 + SiLU:
                  c[t] = sum_j w[:, j] u[t - 3 + j]   (zeros before the sequence)    silu(c)
              per head h of H, d = 128 wide:
                  q_t = l2(q'_t) d^(-1/2)    k_t = l2(k'_t)    l2(u) = u / sqrt(sum(u^2) + 1e-6)
                  g_t = lower * sigmoid(exp(A[h]) (a W_f + b)[h])   in (lower, 0), per CHANNEL
                  beta_t = sigmoid(a W_beta)[h]
                  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T     S_0 = 0
                  o_t = S_t^T q_t
              KDA = concat_h( rms(o_t; gain) * sigmoid(a W_z)[h] ) W_o
    MLA(a):   q = a W_q -> [T, H, dn + dr] = [q_n | q_r]          (full rank: no query rank, no norm)
              [c_kv | k_r] = a W_dkv      c_kv <- rms(c_kv; g_kv)      [k_n | v] = c_kv W_ukv
              q_r (each head) and k_r (ONE for all heads) turn by theta^(-2i/dr) at the token's index
              score[t, s, h] = (q_n[t,h] . k_n[s,h] + q_r[t,h] . k_r[s]) (dn + dr)^(-1/2),   s <= t
              o[t, h] = sum_s softmax_s(score) v[s, h] * sigmoid(a W_G)[t, h]      MLA = concat_h(o) W_o
    FF:       DeepSeek-V3's, as ``deepseek_v32_decoder`` writes it out: a dense gated MLP in the
              leading layers; else sigmoid affinities, the selection bias, the group limit (8
              groups, 4 kept), 8 a token weighted by the affinity over their sum + 1e-20, times
              2.5, over the experts HELD, plus the shared expert.

No kernel, no chunk, no batch: the recurrence TOKEN BY TOKEN (``lax.scan``
over ``t`` with the ``[H, d, d]`` float32 state, the two lines above as
they stand), the convolution as four shifted sums, attention as a masked
softmax over a block of queries' whole rows. Float32 at
``Precision.HIGHEST``; ``compute=jnp.bfloat16`` gives the precision
yardstick as ``keye_decoder`` describes it: the operands of every product
rounded to ``compute``, the recurrence's three (``k^T S``, ``k u^T``,
``S^T q``) among them, sums and the state itself float32. No code of the
package under test.

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/ling3_controls.py``): ``state`` (``"bfloat16"``: the state
rounded after every token), ``decay`` (``"none"``: alpha = 1; ``"head"``: a
head's mean log-decay in all its channels), ``beta`` (False: 1), ``carry``
(``n``: the state dropped every ``n`` tokens), ``taps_used`` (the taps that
stay), ``l2`` (False), ``o_norm`` (False), ``o_gate`` (False),
``attn_gate`` (False: the latent layer's), ``scoring`` (``"softmax"``),
``shared`` (False), ``group_limit`` (False), ``select_bias`` (False)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v32_decoder import chosen_experts
from benchmark.reference.keye_decoder import _mm, dense_mlp, rms, rotate
from benchmark.reference.kimi_k2_decoder import (  # noqa: F401 — the adapter reads them here
    embed, logits_of, patches_of, shared_expert)

KDA, MLA = "linear_attention", "full_attention"  # layer_types, as the file spells them
L2_EPS = 1e-6


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping
    (Ling-3.0's Hugging Face keys), apart from the program's."""
    held = int(cfg["num_experts"])
    m = {
        "H": int(cfg["num_attention_heads"]), "d": int(cfg["head_dim"]),
        "taps": int(cfg["short_conv_kernel_size"]), "lower": float(cfg["kda_lower_bound"]),
        "rkv": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
        "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "layer_types": tuple(cfg["layer_types"]), "n_dense": int(cfg["first_k_dense_replace"]),
        "E": int(cfg.get("router_experts", held)), "k_e": int(cfg["num_experts_per_tok"]),
        "n_group": int(cfg["n_group"]), "topk_group": int(cfg["topk_group"]),
        "experts_held": tuple(cfg.get("experts_held", (0, held))),
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "scale": float(cfg["routed_scaling_factor"]), "n_shared": int(cfg["num_shared_experts"]),
        "scoring": str(cfg["score_function"]),
        "select_bias": bool(cfg["moe_router_enable_expert_bias"]),
        "attn_gate": cfg["gated_attention_proj_granularity_type"] == "head_wise",
        "state": "float32", "decay": "channel", "beta": True, "carry": 0,
        "taps_used": tuple(range(int(cfg["short_conv_kernel_size"]))), "l2": True,
        "o_norm": True, "o_gate": True, "shared": True, "group_limit": True,
    }
    if (len(m["layer_types"]) != int(cfg["num_hidden_layers"])
            or set(m["layer_types"]) - {KDA, MLA} or cfg.get("q_lora_rank")
            or cfg.get("rope_scaling")):
        raise ValueError("only Ling-3.0's two operators, a full-rank latent query and the plain "
                         "rotary are written here")
    m.update(fault)
    return m


def _rounded(x, compute):
    """An operand of a product outside ``_mm``: rounded to ``compute``, widened again."""
    if compute == jnp.float32:
        return x
    return jax.lax.optimization_barrier(x.astype(compute)).astype(jnp.float32)


def conv_silu(u, w, m):
    """``silu(c)``, ``c[t] = sum_j w[:, j] u[t - (taps - 1) + j]`` over the taps in use."""
    t = u.shape[0]
    c = jnp.zeros(u.shape, jnp.float32)
    for j in m["taps_used"]:
        back = m["taps"] - 1 - j  # tap j meets the row `back` before
        c = c + w[:, j].astype(jnp.float32) * jnp.pad(u, ((back, 0), (0, 0)))[:t]
    return jax.nn.silu(c)


def delta_rule(q, k, v, g, beta, m, compute):
    """The recurrence, token by token: ``q, k, v, g [T, H, d]``, ``beta
    [T, H]`` -> ``o [T, H, d]``."""
    t, H, d = q.shape
    hi = jax.lax.Precision.HIGHEST

    def step(S, x):
        q, k, v, g, b, i = x
        if m["carry"]:  # the fault: nothing crosses a boundary of `carry` tokens
            S = jnp.where(i % m["carry"] == 0, 0.0, S)
        S = S * jnp.exp(g)[:, :, None]
        k_r = _rounded(k, compute)
        u = b[:, None] * (v - jnp.einsum("hk,hkv->hv", k_r, _rounded(S, compute), precision=hi))
        S = S + jnp.einsum("hk,hv->hkv", k_r, _rounded(u, compute), precision=hi)
        if m["state"] != "float32":  # the fault: the state kept in a narrower type
            S = jax.lax.optimization_barrier(S.astype(m["state"])).astype(jnp.float32)
        return S, jnp.einsum("hkv,hk->hv", _rounded(S, compute), _rounded(q, compute), precision=hi)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v, g, beta, jnp.arange(t)))
    return o


def kda(p, a, m, compute):
    """Kimi Delta Attention from the normed input ``a [T, d]``."""
    t, H, d = a.shape[0], m["H"], m["d"]
    q, k, v = (u.reshape(t, H, d) for u in jnp.split(
        conv_silu(_mm(a, p["w_qkv"], compute), p["conv_w"], m), 3, axis=1))
    if m["l2"]:
        q, k = (u / jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS) for u in (q, k))
    q = q * d ** -0.5
    f = _mm(a, p["w_f"], compute).reshape(t, H, d)
    g = m["lower"] * jax.nn.sigmoid(jnp.exp(p["decay_a"].astype(jnp.float32))[None, :, None]
                                    * (f + p["decay_b"].astype(jnp.float32).reshape(H, d)))
    if m["decay"] == "none":
        g = jnp.zeros_like(g)
    elif m["decay"] == "head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_mm(a, p["w_beta"], compute)) if m["beta"] else jnp.ones((t, H))
    o = delta_rule(q, k, v, g, beta, m, compute)
    if m["o_norm"]:
        o = rms(o, p["o_norm"], m["eps"])
    if m["o_gate"]:
        o = o * jax.nn.sigmoid(_mm(a, p["w_z"], compute)).reshape(t, H, d)
    return _mm(o.reshape(t, H * d), p["wo"], compute)


def latent_attention(p, a, m, compute, block):
    """MLA with a full-rank query and the head-wise output gate, from the
    normed input ``a [T, d]``, a block of queries at a time."""
    t = a.shape[0]
    H, dn, dr, dv, eps = m["H"], m["dn"], m["dr"], m["dv"], m["eps"]
    inv_freq = 1.0 / m["theta"] ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * inv_freq, jnp.float32)
    q = _mm(a, p["wq"], compute).reshape(t, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], ang)], axis=-1)
    down = _mm(a, p["wkv_a"], compute)
    c_kv = rms(down[:, :m["rkv"]], p["kv_a_norm"], eps)
    k_r = rotate(down[:, None, m["rkv"]:], ang)
    kv = _mm(c_kv, p["wkv_b"], compute).reshape(t, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (t, H, dr))], axis=-1)
    q, k, v = jnp.transpose(q, (1, 0, 2)), jnp.transpose(k, (1, 2, 0)), jnp.transpose(
        kv[..., dn:], (1, 0, 2))  # head-major: a block's scores are one batched product

    def block_out(t0):
        open_ = jnp.arange(t)[None, :] <= (t0 + jnp.arange(block))[:, None]
        logit = _mm(jax.lax.dynamic_slice_in_dim(q, t0, block, axis=1), k, compute) \
            * (dn + dr) ** -0.5
        prob = jax.nn.softmax(jnp.where(open_[None], logit, -jnp.inf), axis=-1)  # [H, block, T]
        return jnp.transpose(_mm(prob, v, compute), (1, 0, 2))

    o = jax.lax.map(block_out, jnp.arange(0, t, block)).reshape(t, H, dv)
    if m["attn_gate"]:
        o = o * jax.nn.sigmoid(_mm(a, p["w_attn_gate"], compute))[:, :, None]
    return _mm(o.reshape(t, H * dv), p["wo"], compute)


def experts(p, b, m, compute):
    """The routed experts from the normed input ``b [T, d]``: the held
    experts' part of their sum, and each token's expert set ``[T, E]``."""
    first, count = m["experts_held"]
    logits = _mm(b, p["router"], compute)
    if m["scoring"] == "softmax":  # the fault: another router altogether
        s, by = jax.nn.softmax(logits, axis=-1), 0.0
    else:
        s = jax.nn.sigmoid(logits)
        by = p["router_bias"].astype(jnp.float32) if m["select_bias"] else 0.0
    chosen = chosen_experts(s, by, m)
    gate = s * chosen
    if m["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * m["scale"]

    def one(e, y):
        h = jax.nn.silu(_mm(b, p["w_gate"][e], compute)) * _mm(b, p["w_up"][e], compute)
        g = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1, keepdims=True)
        return y + g * _mm(h, p["w_down"][e], compute)

    return jax.lax.fori_loop(0, count, one, jnp.zeros(b.shape, jnp.float32)), chosen


def kinds(m) -> list:
    """Each layer's kind: ``(its operator, is its feed-forward dense?)``."""
    return [(op, i < m["n_dense"]) for i, op in enumerate(m["layer_types"])]


def layer(p, x, kind, m, compute=jnp.float32, block=128):
    """One layer (``kind``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    op, dense = kind
    a = rms(x, p["norm1"], m["eps"])
    x = x + (kda(p, a, m, compute) if op == KDA else latent_attention(p, a, m, compute, block))
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
