"""Plain float32 forward pass of Granite-4.0-H-Micro's hybrid trunk
(``granitemoehybrid``) as the frame reader runs it: the reference for
``granite4_h_micro_prefill_epix10k2m``.

Sizes and multipliers from the model's public ``config.json``; what it does
not fix is listed in the configuration file's ``assumed``. One sequence of
``T`` tokens, ``x [T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g``
every layer is ``h = x + 0.22 Op(rms(x; g1))``, ``x' = h + 0.22 MLP(rms(h;
g2))`` (``residual_multiplier``), ``MLP(b) = (silu(b W_g) * b W_u) W_d`` at
``shared_intermediate_size`` (``num_local_experts`` 0: the always-on MLP is
the whole feed-forward), the embedded rows are times 12
(``embedding_multiplier``) and the logits ``rms(x; g) E^T / 8``
(``logits_scaling``; tied). ``Op`` is one of two (``layer_types``):

    mamba(a):      [z | xBC | dt] = a W_in                      (no bias)
                   xBC <- silu(c + b_c),  c[t] = sum_j w[:, j] xBC[t - 3 + j]   (zeros before the sequence)
                   [x | B | C] = xBC     x -> [T, H, P]     B, C [T, N]: ONE for all heads
                   Delta_t = softplus(dt_t + dt_bias) [H]      A = -exp(A_log) [H]
                   H_t[h] = exp(Delta_t[h] A[h]) H_{t-1}[h] + Delta_t[h] x_t[h] (x) B_t     H_0 = 0, [P, N]
                   y_t[h] = H_t[h] C_t + D[h] x_t[h]
                   mamba = rms(y * silu(z); gain) W_out       (the gate BEFORE the norm; all H*P channels)
    attention(a):  q = a W_q -> [T, 32, 64]   k, v = a W_k, a W_v -> [T, 8, 64]   no bias, no norm, NO rotary
                   o[t, h] = sum_{s<=t} softmax_s(q[t,h] . k[s,h//4] * attention_multiplier) v[s,h//4]
                   attention = concat_h(o) W_o

No kernel, no chunk, no batch: the recurrence TOKEN BY TOKEN (``lax.scan``
over ``t`` with the ``[H, P, N]`` float32 state, the two lines above as they
stand: a chunk-boundary fault cannot be shared with the program), the
convolution as four shifted sums, attention as a masked softmax over a
block of queries' whole rows. Float32 at ``Precision.HIGHEST``;
``compute=jnp.bfloat16`` gives the precision yardstick as ``keye_decoder``
describes it: the operands of every product rounded to ``compute`` (the
recurrence's two, ``(Delta x) (x) B`` and ``H C``, among them), sums and the
state itself float32. No code of the package under test. Departures from the
published code, none in the mathematics: the step is not clamped
(``time_step_limit`` (0, inf), the family's default), and the multipliers
are written out where they act and not folded into weights.

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/granite_controls.py``): ``state`` (``"bfloat16"``: the
state rounded after every token), ``carry`` (``n``: the state dropped every
``n`` tokens), ``skip`` (False: no ``D x``), ``dt_bias`` (False), ``gate_first``
(False: the norm BEFORE the gate), ``conv_bias`` (False), ``residual`` (the
multiplier in its place), ``attn_scale``, ``rotary`` (True: a plain rotary
at ``rope_theta`` in the attention layers), ``logits_scaling``, ``embedding``."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, angles_1d, dense_mlp, rms, rotate
from benchmark.reference.lfm2_decoder import patches_of  # noqa: F401 — the adapter reads it here
from benchmark.reference.ling3_decoder import _rounded

MAMBA, ATTENTION = "mamba", "attention"  # layer_types, as the file spells them


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping
    (Granite-4.0-H's Hugging Face keys), apart from the program's."""
    heads = int(cfg["num_attention_heads"])
    m = {
        "H": heads, "G": int(cfg["num_key_value_heads"]), "dh": int(cfg["hidden_size"]) // heads,
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "layer_types": tuple(cfg["layer_types"]),
        "Hs": int(cfg["mamba_n_heads"]), "P": int(cfg["mamba_d_head"]),
        "N": int(cfg["mamba_d_state"]), "taps": int(cfg["mamba_d_conv"]),
        "conv_bias": bool(cfg["mamba_conv_bias"]),
        "residual": float(cfg["residual_multiplier"]), "embedding": float(cfg["embedding_multiplier"]),
        "attn_scale": float(cfg["attention_multiplier"]), "logits_scaling": float(cfg["logits_scaling"]),
        "rotary": cfg["position_embedding_type"] != "nope",
        "state": "float32", "carry": 0, "skip": True, "dt_bias": True, "gate_first": True,
    }
    if (len(m["layer_types"]) != int(cfg["num_hidden_layers"])
            or set(m["layer_types"]) - {MAMBA, ATTENTION} or int(cfg["mamba_n_groups"]) != 1
            or int(cfg["num_local_experts"]) or cfg.get("mamba_proj_bias") or cfg.get("attention_bias")):
        raise ValueError("only Granite-4.0-H's two operators, one group of B and C, no experts "
                         "and no bias in a product are written here")
    m.update(fault)
    return m


def conv_silu(u, w, bias, m):
    """``silu(c + b)``, ``c[t] = sum_j w[:, j] u[t - (taps - 1) + j]``, zeros before the sequence."""
    t = u.shape[0]
    c = jnp.zeros(u.shape, jnp.float32)
    for j in range(m["taps"]):
        back = m["taps"] - 1 - j  # tap j meets the row `back` before
        c = c + w[:, j].astype(jnp.float32) * jnp.pad(u, ((back, 0), (0, 0)))[:t]
    if m["conv_bias"]:
        c = c + bias.astype(jnp.float32)
    return jax.nn.silu(c)


def scan(x, b, c, step, a, m, compute):
    """The recurrence, token by token: ``x [T, H, P]``, ``b, c [T, N]``,
    ``step [T, H]``, ``a [H]`` -> ``y [T, H, P]`` (without the skip)."""
    t, heads, p = x.shape
    hi = jax.lax.Precision.HIGHEST

    def one(state, u):
        x, b, c, d, i = u
        if m["carry"]:  # the fault: nothing crosses a boundary of `carry` tokens
            state = jnp.where(i % m["carry"] == 0, 0.0, state)
        state = jnp.exp(d * a)[:, None, None] * state + jnp.einsum(
            "hp,n->hpn", _rounded(d[:, None] * x, compute), _rounded(b, compute), precision=hi)
        if m["state"] != "float32":  # the fault: the state kept in a narrower type
            state = jax.lax.optimization_barrier(state.astype(m["state"])).astype(jnp.float32)
        return state, jnp.einsum("hpn,n->hp", _rounded(state, compute), _rounded(c, compute),
                                 precision=hi)

    _, y = jax.lax.scan(one, jnp.zeros((heads, p, b.shape[1]), jnp.float32),
                        (x, b, c, step, jnp.arange(t)))
    return y


def mamba(p, a, m, compute):
    """The state-space layer from the normed input ``a [T, d]``."""
    t, heads, width, n = a.shape[0], m["Hs"], m["P"], m["N"]
    wide = heads * width
    z, xbc, dt = jnp.split(_mm(a, p["w_in"], compute), [wide, 2 * wide + 2 * n], axis=1)
    xbc = conv_silu(xbc, p["conv_w"], p.get("conv_b"), m)
    x = xbc[:, :wide].reshape(t, heads, width)
    if m["dt_bias"]:
        dt = dt + p["dt_bias"].astype(jnp.float32)
    y = scan(x, xbc[:, wide:wide + n], xbc[:, wide + n:], jax.nn.softplus(dt),
             -jnp.exp(p["a_log"].astype(jnp.float32)), m, compute)
    if m["skip"]:
        y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * x
    y = y.reshape(t, wide)
    if m["gate_first"]:
        y = rms(y * jax.nn.silu(z), p["ssm_norm"], m["eps"])
    else:  # the fault: Mamba-2's other order
        y = rms(y, p["ssm_norm"], m["eps"]) * jax.nn.silu(z)
    return _mm(y, p["w_out"], compute)


def attention(p, a, m, compute, block):
    """Causal grouped-query attention without positions from the normed
    input ``a [T, d]``, a block of queries at a time."""
    t = a.shape[0]
    H, G, dh = m["H"], m["G"], m["dh"]
    q = _mm(a, p["wq"], compute).reshape(t, H, dh)
    k = _mm(a, p["wk"], compute).reshape(t, G, dh)
    v = _mm(a, p["wv"], compute).reshape(t, G, dh)
    if m["rotary"]:  # the fault (this model has none)
        ang = angles_1d(np.arange(t), m["theta"], dh // 2)
        q, k = rotate(q, ang), rotate(k, ang)

    def block_out(t0):
        open_ = jnp.arange(t)[None, :] <= (t0 + jnp.arange(block))[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block)
        out = []
        for h in range(H):
            g = h // (H // G)  # query head h reads key-value head h // (H/G)
            logit = _mm(qb[:, h], k[:, g].T, compute) * m["attn_scale"]
            prob = jax.nn.softmax(jnp.where(open_, logit, -jnp.inf), axis=-1)
            out.append(_mm(prob, v[:, g], compute))
        return jnp.concatenate(out, axis=-1)

    o = jax.lax.map(block_out, jnp.arange(0, t, block))
    return _mm(o.reshape(t, H * dh), p["wo"], compute)


def kinds(m) -> list:
    """Each layer's kind: its operator (the feed-forward is the same dense MLP in all)."""
    return list(m["layer_types"])


def layer(p, x, kind, m, compute=jnp.float32, block=128):
    """One layer (``kind``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    a = rms(x, p["norm1"], m["eps"])
    op = mamba(p, a, m, compute) if kind == MAMBA else attention(p, a, m, compute, block)
    x = x + m["residual"] * op
    return x + m["residual"] * dense_mlp(p, rms(x, p["norm2"], m["eps"]), compute)


def embed(params, patches, prompt_ids, compute=jnp.float32, m=None):
    """The patch embedding's rows, then the prompt's: both times
    ``embedding_multiplier`` (the published code scales ``inputs_embeds``
    whatever made them). ``m``: :func:`sizes`' mapping."""
    rows = jnp.concatenate([
        _mm(patches.astype(jnp.float32), params["patch"], compute),
        params["embed"][prompt_ids].astype(jnp.float32),
    ])
    return rows * m["embedding"]


def logits_of(params, x, m, compute=jnp.float32):
    """The tied head: the final norm, the embedding table's rows, over ``logits_scaling``."""
    return _mm(rms(x, params["norm"], m["eps"]), params["embed"].T, compute) / m["logits_scaling"]


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``."""
    x = embed(params, patches, prompt_ids, compute, m)
    for p, kind in zip(params["layers"], kinds(m)):
        x = layer(p, x, kind, m, compute, block)
    return x
