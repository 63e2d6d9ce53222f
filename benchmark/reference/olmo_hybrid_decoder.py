"""Plain float32 forward pass of Olmo-Hybrid-7B's trunk (``olmo_hybrid``) as
the frame reader runs it: the reference for
``olmo_hybrid_7b_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is listed
in the configuration file's ``assumed``. One sequence of ``T`` tokens, ``x [T,
d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g`` every layer is OLMo
2's REORDERED block (arXiv:2501.00656 section 3: a branch is normed AFTER it is
computed and nothing norms its input)

    h = x + rms(Op(x); g1)        x' = h + rms(MLP(h); g2)        MLP(u) = (silu(u W_g) * u W_u) W_d

and ``Op`` is one of two (``layer_types``):

    GDN(x):   q' = x W_q [T, H, dk]   k' = x W_k [T, H, dk]   v' = x W_v [T, H, dv], each through conv4 + SiLU:
                  c[t] = sum_j w[:, j] u[t - 3 + j]   (zeros before the sequence, no bias)    silu(c)
              per head h of H:
                  q_t = l2(q'_t) dk^(-1/2)    k_t = l2(k'_t)    l2(u) = u / sqrt(sum(u^2) + 1e-6)
                  g_t = -exp(A_log[h]) softplus((x W_a)[h] + dt_bias[h])        ONE a head, unbounded below
                  beta_t = 2 sigmoid(x W_b)[h]                                  (linear_allow_neg_eigval)
                  S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T     S_0 = 0, [dk, dv]
                  o_t = S_t^T q_t
              GDN = concat_h( rms(o_t; gain [dv]) * silu(x W_g)[h] ) W_o       (the norm BEFORE the gate)
    MHA(x):   q = rms(x W_q; g_q)   k = rms(x W_k; g_k)   over ALL H * dh columns, THEN H heads of dh;  v = x W_v
              o[t, h] = sum_{s<=t} softmax_s(q[t,h] . k[s,h] dh^(-1/2)) v[s,h]          NO rotary
              MHA = concat_h(o) W_o

(Gated DeltaNet as arXiv:2412.06464 and ``flash-linear-attention``'s
``GatedDeltaNet`` give it, whose argument names the config's ``linear_*`` keys
are.) No kernel, no chunk, no batch: the recurrence TOKEN BY TOKEN
(``lax.scan`` over ``t`` with the ``[H, dk, dv]`` float32 state, the two lines
above as they stand: rank-one updates, no inverse), the convolution as four
shifted sums, attention as a masked softmax over a block of queries' whole
rows. Float32 at ``Precision.HIGHEST``; ``compute=jnp.bfloat16`` gives the
precision yardstick as ``keye_decoder`` describes it: the operands of every
product rounded to ``compute``, the recurrence's three (``k^T S``, ``k u^T``,
``S^T q``) among them, sums and the state itself float32. No code of the
package under test. Departures from the published description: none in the
mathematics.

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/olmo_hybrid_controls.py``) and for the OTHER reading of an
assumption: ``state`` (``"bfloat16"``: the state rounded after every token),
``carry`` (``n``: the state dropped every ``n`` tokens), ``beta_scale`` (1:
beta without its 2), ``decay`` (``"none"``: alpha = 1; ``"first"``: the first
head's decay in every head), ``gate`` (``"sigmoid"``), ``gate_first`` (True:
the gate BEFORE the norm), ``l2`` (False), ``taps_used`` (the taps that stay),
``norm_place`` (``"before"``: ``x + Op(rms(x; g))``, the norm BEFORE the
branch in place of after it, as FLA's own blocks have it), ``qk_norm``
(``"head"``: each head's 128 columns normed on their own; ``"none"``),
``rotary`` (True: a plain rotary at ``rope_theta`` 500,000, OLMo 3's, in the
full layers)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, angles_1d, dense_mlp, rms, rotate
from benchmark.reference.kimi_k2_decoder import (  # noqa: F401 — the adapter reads them here
    embed, logits_of, patches_of)
from benchmark.reference.ling3_decoder import _rounded

GDN, MHA = "linear_attention", "full_attention"  # layer_types, as the file spells them
L2_EPS = 1e-6
OTHER_THETA = 500000.0  # OLMo 3's rope_theta: the other reading of a null rope_parameters.rope_theta


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping
    (Olmo-Hybrid's Hugging Face keys), apart from the program's."""
    heads = int(cfg["num_attention_heads"])
    m = {
        "H": heads, "dh": int(cfg.get("head_dim") or int(cfg["hidden_size"]) // heads),
        "Hl": int(cfg["linear_num_key_heads"]), "dk": int(cfg["linear_key_head_dim"]),
        "dv": int(cfg["linear_value_head_dim"]), "taps": int(cfg["linear_conv_kernel_dim"]),
        "beta_scale": 2.0 if cfg["linear_allow_neg_eigval"] else 1.0,
        "eps": float(cfg["rms_norm_eps"]), "layer_types": tuple(cfg["layer_types"]),
        "state": "float32", "carry": 0, "decay": "head", "gate": "silu", "gate_first": False,
        "l2": True, "taps_used": tuple(range(int(cfg["linear_conv_kernel_dim"]))),
        "norm_place": "after", "qk_norm": "projection", "rotary": False, "theta": OTHER_THETA,
    }
    if (len(m["layer_types"]) != int(cfg["num_hidden_layers"])
            or set(m["layer_types"]) - {GDN, MHA} or cfg.get("attention_bias")
            or int(cfg["linear_num_value_heads"]) != m["Hl"]
            or int(cfg["num_key_value_heads"]) != heads
            or (cfg.get("rope_parameters") or {}).get("rope_theta") is not None):
        raise ValueError("only Olmo-Hybrid's two operators, as many value heads as key heads, a key "
                         "head a query head, no bias and no rotary are written here")
    m.update(fault)
    return m


def conv_silu(u, w, m):
    """``silu(c)``, ``c[t] = sum_j w[:, j] u[t - (taps - 1) + j]`` over the taps in use."""
    t = u.shape[0]
    c = jnp.zeros(u.shape, jnp.float32)
    for j in m["taps_used"]:
        back = m["taps"] - 1 - j  # tap j meets the row `back` before
        c = c + w[:, j].astype(jnp.float32) * jnp.pad(u, ((back, 0), (0, 0)))[:t]
    return jax.nn.silu(c)


def delta_rule(q, k, v, g, beta, m, compute):
    """The recurrence, token by token: ``q, k [T, H, dk]``, ``v [T, H, dv]``,
    ``g, beta [T, H]`` -> ``o [T, H, dv]``."""
    t, H, dk = q.shape
    hi = jax.lax.Precision.HIGHEST

    def step(S, x):
        q, k, v, g, b, i = x
        if m["carry"]:  # the fault: nothing crosses a boundary of `carry` tokens
            S = jnp.where(i % m["carry"] == 0, 0.0, S)
        S = S * jnp.exp(g)[:, None, None]
        k_r = _rounded(k, compute)
        u = b[:, None] * (v - jnp.einsum("hk,hkv->hv", k_r, _rounded(S, compute), precision=hi))
        S = S + jnp.einsum("hk,hv->hkv", k_r, _rounded(u, compute), precision=hi)
        if m["state"] != "float32":  # the fault: the state kept in a narrower type
            S = jax.lax.optimization_barrier(S.astype(m["state"])).astype(jnp.float32)
        return S, jnp.einsum("hkv,hk->hv", _rounded(S, compute), _rounded(q, compute), precision=hi)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, v.shape[-1]), jnp.float32),
                        (q, k, v, g, beta, jnp.arange(t)))
    return o


def gdn(p, x, m, compute):
    """Gated DeltaNet from the layer's input ``x [T, d]`` (under the reordered
    norm: the stream as it is)."""
    t, H, dk, dv = x.shape[0], m["Hl"], m["dk"], m["dv"]
    q, k, v = (conv_silu(_mm(x, p["w_" + u], compute), p["conv_" + u], m).reshape(t, H, width)
               for u, width in (("q", dk), ("k", dk), ("v", dv)))
    if m["l2"]:
        q, k = (u / jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS) for u in (q, k))
    q = q * dk ** -0.5
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        _mm(x, p["w_f"], compute) + p["dt_bias"].astype(jnp.float32))
    if m["decay"] == "none":
        g = jnp.zeros_like(g)
    elif m["decay"] == "first":
        g = jnp.broadcast_to(g[:, :1], g.shape)
    beta = m["beta_scale"] * jax.nn.sigmoid(_mm(x, p["w_beta"], compute))
    o = delta_rule(q, k, v, g, beta, m, compute)
    z = _mm(x, p["w_z"], compute).reshape(t, H, dv)
    act = jax.nn.silu if m["gate"] == "silu" else jax.nn.sigmoid
    if m["gate_first"]:  # the fault: Mamba-2's order
        o = rms(o * act(z), p["o_norm"], m["eps"])
    else:
        o = rms(o, p["o_norm"], m["eps"]) * act(z)
    return _mm(o.reshape(t, H * dv), p["wo"], compute)


def attention(p, x, m, compute, block):
    """Causal multi-head attention without positions from the layer's input
    ``x [T, d]``, q and k normed over the WHOLE projection before the heads
    are cut, a block of queries at a time."""
    t, H, dh, eps = x.shape[0], m["H"], m["dh"], m["eps"]
    q, k = _mm(x, p["wq"], compute), _mm(x, p["wk"], compute)
    if m["qk_norm"] == "projection":
        q, k = rms(q, p["q_norm"], eps), rms(k, p["k_norm"], eps)
    q, k = q.reshape(t, H, dh), k.reshape(t, H, dh)
    if m["qk_norm"] == "head":  # the other reading: a norm a head, each under its own 128 gains
        q, k = (rms(u, p[g].reshape(H, dh), eps) for u, g in ((q, "q_norm"), (k, "k_norm")))
    v = _mm(x, p["wv"], compute).reshape(t, H, dh)
    if m["rotary"]:  # the other reading (this model's file has a null rope_theta)
        ang = angles_1d(np.arange(t), m["theta"], dh // 2)
        q, k = rotate(q, ang), rotate(k, ang)
    q, k, v = jnp.transpose(q, (1, 0, 2)), jnp.transpose(k, (1, 2, 0)), jnp.transpose(v, (1, 0, 2))

    def block_out(t0):
        open_ = jnp.arange(t)[None, :] <= (t0 + jnp.arange(block))[:, None]
        logit = _mm(jax.lax.dynamic_slice_in_dim(q, t0, block, axis=1), k, compute) * dh ** -0.5
        prob = jax.nn.softmax(jnp.where(open_[None], logit, -jnp.inf), axis=-1)  # [H, block, T]
        return jnp.transpose(_mm(prob, v, compute), (1, 0, 2))

    o = jax.lax.map(block_out, jnp.arange(0, t, block)).reshape(t, H * dh)
    return _mm(o, p["wo"], compute)


def kinds(m) -> list:
    """Each layer's kind: its operator (the feed-forward is the same dense MLP in all)."""
    return list(m["layer_types"])


def layer(p, x, kind, m, compute=jnp.float32, block=128):
    """One layer (``kind``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    def op(a):
        return gdn(p, a, m, compute) if kind == GDN else attention(p, a, m, compute, block)

    if m["norm_place"] == "before":  # the other reading: the same gains, a pre-normed block
        x = x + op(rms(x, p["norm1_post"], m["eps"]))
        return x + dense_mlp(p, rms(x, p["norm2_post"], m["eps"]), compute)
    x = x + rms(op(x), p["norm1_post"], m["eps"])
    return x + rms(dense_mlp(p, x, compute), p["norm2_post"], m["eps"])


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``."""
    x = embed(params, patches, prompt_ids, compute)
    for p, kind in zip(params["layers"], kinds(m)):
        x = layer(p, x, kind, m, compute, block)
    return x
