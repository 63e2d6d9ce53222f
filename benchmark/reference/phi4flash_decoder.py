"""Plain float32 forward pass of Phi-4-mini-flash-reasoning's trunk
(``phi4flash``: SambaY, arXiv:2507.06607, a decoder-hybrid-decoder over YOCO,
arXiv:2405.05254) as the frame reader runs it: the reference for
``phi4_mini_flash_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix (the scan's
four sizes, the differential form, the biases, the absence of a rotary) is
listed in the configuration file's ``assumed``, each with its other reading,
which ``sizes(cfg, **fault)`` computes too. One sequence of ``T`` tokens, ``x
[T, d]``; with ``LN(u; g, b) = (u - mean u) / sqrt(var u + eps) g + b`` every
layer ``l`` of ``L`` is

    h = x + Op_l(LN(x; g1, b1))        x' = h + (silu(a W_gate) * a W_up) W_down,  a = LN(h; g2, b2)

and ``Op_l`` follows the published rule over the index: ``l`` even and ``l <=
L/2`` a Mamba-1 layer; ``l`` odd and ``l < L/2`` differential attention under
the window; ``l = L/2 + 1`` differential attention over every causal key, whose
keys and values are KEPT; ``l`` even and ``l >= L/2 + 2`` a gated memory unit;
``l`` odd and ``l >= L/2 + 3`` differential CROSS attention to the kept keys
and values:

    mamba(a):   [xs | z] = a W_in            u = silu(conv4(xs) + b_c)      (zeros before the sequence)
                [delta | B | C] = u W_x      Delta = softplus(delta W_dt + b_dt) [T, C]     A = -exp(A_log) [C, N]
                h_t[d, n] = exp(Delta_t[d] A[d, n]) h_{t-1}[d, n] + Delta_t[d] B_t[n] u_t[d]        h_0 = 0
                y_t[d] = sum_n C_t[n] h_t[d, n] + D[d] u_t[d]          mamba = (y * silu(z)) W_out
                (layer L/2's y, with the skip and BEFORE the gate, is the memory m)
    diff(a):    [q | k | v] = a W_qkv + b_qkv   q -> [T, P, 2, dh]   k, v -> [T, G, 2, dh]
                o^c_j = softmax_i(q^c_j . k^c_{j // (P/G)} / sqrt(dh)) [v^1 | v^2]_{j // (P/G)}   c = 1, 2; [T, 2 dh]
                lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,  lambda_init = 0.8 - 0.6 exp(-0.3 l)
                o_j = rms(o^1_j - lambda o^2_j; gain [2 dh]) (1 - lambda_init)          diff = [o_0 .. o_{P-1}] W_o + b_o
                (keys i with t - window < i <= t under the window, else i <= t; no rotary)
    gmu(a):     ((silu(a W_1)) * m) W_2,        m the memory at the same token
    cross(a):   q = a W_q + b_q, the kept k and v, every causal key, the same differential form

after the last layer ``LN(x; g_f, b_f) E^T`` (tied). No kernel, no chunk, no
batch, NO ROW CUT (every layer on every row: the reference knows none): the
recurrence TOKEN BY TOKEN (``lax.scan`` over ``t`` with the ``[C, N]`` float32
state, the two lines above as they stand), the convolution as four shifted
sums, each of the two softmaxes dense and masked over a block of queries' whole
rows, the kept keys and values and the memory handed on as VALUES. Float32 at
``Precision.HIGHEST``; ``compute=jnp.bfloat16`` gives the precision yardstick
as ``keye_decoder`` describes it: the operands of every product rounded to
``compute`` (the recurrence's two, ``(Delta u) (x) B`` and ``sum_n C h``, among
them), sums and the state itself float32. No code of the package under test.
Departures from the publication, none in the mathematics: the fused ``[W_gate |
W_up]`` is held as its two halves.

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/phi4flash_controls.py``), or take the OTHER reading of an
assumed point: ``carry`` (``n``: the state dropped every ``n`` tokens), ``a``
(``"first"``: channel 0's ``A`` in every channel; ``"ramp"``: ``A = -(n + 1)``
whatever the weights say), ``softplus`` (False), ``skip`` (False),
``taps_used`` (the taps that stay), ``conv_bias``, ``dt_bias``, ``attn_bias``
(False: the other reading of each), ``lam`` (``"zero"``: one softmax;
``"init"``: ``lambda_init`` alone), ``lam_base`` (1: ``lambda_init`` from the
1-based index), ``sub_norm`` (False), ``values`` (``"second"``: ``[v^2 | v^2]``),
``window`` (0: none; another width), ``window_own`` (False: the band is the
``window`` keys BEFORE the query's own and its own), ``plain`` (True: plain
softmax at ``2P`` query and ``2G`` key heads of ``dh``: the other reading of the
attention), ``rotary`` (True: a plain rotary at ``theta`` 10,000 on q and k),
``memory`` (``"gated"``: m taken AFTER the gate), ``memory_from`` and
``kv_from`` (the layers whose output the memory units and the cross layers
read)."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, angles_1d, dense_mlp, rms, rotate
from benchmark.reference.lfm2_decoder import embed, patches_of  # noqa: F401 — the adapter reads them here
from benchmark.reference.ling3_decoder import _rounded

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping (the public
    file's keys, and the scan's sizes from the configuration class's
    defaults where the mapping has no ``mamba_*`` key), apart from the
    program's."""
    layers, width = int(cfg["num_hidden_layers"]), int(cfg["hidden_size"])
    heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    m = {
        "L": layers, "P": heads // 2, "G": kv_heads // 2, "dh": width // heads,
        "eps": float(cfg["layer_norm_eps"]), "window": int(cfg["sliding_window"]),
        "C": int(cfg.get("mamba_expand", 2)) * width, "N": int(cfg.get("mamba_d_state", 16)),
        "taps": int(cfg.get("mamba_d_conv", 4)),
        "R": int(cfg.get("mamba_dt_rank") or math.ceil(width / 16)),
        "memory_from": layers // 2, "kv_from": layers // 2 + 1,
        "carry": 0, "a": "own", "softplus": True, "skip": True, "conv_bias": True, "dt_bias": True,
        "attn_bias": True, "lam": "learned", "lam_base": 0, "sub_norm": True, "values": "both",
        "window_own": True, "plain": False, "rotary": False, "theta": 10000.0, "memory": "before_gate",
    }
    m["taps_used"] = tuple(range(m["taps"]))
    if (int(cfg["mb_per_layer"]) != 2 or layers % 4 or heads % 2 or kv_heads % 2
            or cfg.get("mlp_bias") or cfg.get("lm_head_bias")):
        raise ValueError("only a scan every 2 layers over a multiple of 4 layers, paired heads and "
                         "no bias in the MLP or the head are written here")
    m.update(fault)
    return m


def kinds(m) -> list:
    """Each layer's kind, by the published rule over its index."""
    half = m["L"] // 2

    def kind(i):
        if i % 2 == 0:
            return MAMBA if i <= half else GMU
        return WINDOW if i < half else FULL if i == half + 1 else CROSS

    return [kind(i) for i in range(m["L"])]


def reads(m, i: int):
    """The layer whose output layer ``i`` reads (``None``: none): a memory
    unit the scan output of ``memory_from``, a cross layer the keys and values
    of ``kv_from``."""
    return {GMU: m["memory_from"], CROSS: m["kv_from"]}.get(kinds(m)[i])


def ln(u, g, b, eps):
    u = u - jnp.mean(u, axis=-1, keepdims=True)
    return (u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32))


def conv_silu(u, w, bias, m):
    """``silu(c + b)``, ``c[t] = sum_j w[:, j] u[t - (taps - 1) + j]`` over the taps in use."""
    t = u.shape[0]
    c = jnp.zeros(u.shape, jnp.float32)
    for j in m["taps_used"]:
        back = m["taps"] - 1 - j  # tap j meets the row `back` before
        c = c + w[:, j].astype(jnp.float32) * jnp.pad(u, ((back, 0), (0, 0)))[:t]
    if m["conv_bias"]:
        c = c + bias.astype(jnp.float32)
    return jax.nn.silu(c)


def scan(u, b, c, step, a, m, compute):
    """The recurrence, token by token: ``u, step [T, C]``, ``b, c [T, N]``,
    ``a [C, N]`` -> ``y [T, C]`` (without the skip)."""
    def one(state, x):
        u, b, c, d, i = x
        if m["carry"]:  # the fault: nothing crosses a boundary of `carry` tokens
            state = jnp.where(i % m["carry"] == 0, 0.0, state)
        state = jnp.exp(d[:, None] * a) * state + (
            _rounded(d * u, compute)[:, None] * _rounded(b, compute)[None, :])
        return state, jnp.sum(_rounded(state, compute) * _rounded(c, compute)[None, :], axis=1)

    _, y = jax.lax.scan(one, jnp.zeros(a.shape, jnp.float32),
                        (u, b, c, step, jnp.arange(u.shape[0])))
    return y


def mamba(p, a, m, compute):
    """The Mamba-1 layer from the normed input ``a [T, d]`` -> ``(Op, y)``,
    ``y`` the memory a later layer may read."""
    wide, rank, n = m["C"], m["R"], m["N"]
    xs, z = jnp.split(_mm(a, p["w_in"], compute), [wide], axis=1)
    u = conv_silu(xs, p["conv_w"], p["conv_b"], m)
    low, b, c = jnp.split(_mm(u, p["w_x"], compute), [rank, rank + n], axis=1)
    delta = _mm(low, p["w_dt"], compute)
    if m["dt_bias"]:
        delta = delta + p["dt_bias"].astype(jnp.float32)
    step = jax.nn.softplus(delta) if m["softplus"] else delta
    a_own = -jnp.exp(p["a_log"].astype(jnp.float32))
    if m["a"] == "first":  # the fault: one channel's decays in every channel
        a_own = jnp.broadcast_to(a_own[:1], a_own.shape)
    elif m["a"] == "ramp":  # the fault: the initialiser's A assumed
        a_own = jnp.broadcast_to(-jnp.arange(1, n + 1, dtype=jnp.float32), a_own.shape)
    y = scan(u, b, c, step, a_own, m, compute)
    if m["skip"]:
        y = y + p["d_skip"].astype(jnp.float32) * u
    gated = y * jax.nn.silu(z)
    return _mm(gated, p["w_out"], compute), gated if m["memory"] == "gated" else y


def _biased(out, p, name, m):
    return out + p[name].astype(jnp.float32) if m["attn_bias"] and name in p else out


def keys_values(p, a, m, compute):
    """A differential layer's ``(k [T, G, 2, dh], v [T, G, 2, dh])`` from its normed input."""
    t, wide = a.shape[0], 2 * m["P"] * m["dh"]
    kv = _biased(_mm(a, p["w_qkv"], compute), p, "b_qkv", m)[:, wide:]
    k, v = jnp.split(kv, 2, axis=1)
    return k.reshape(t, m["G"], 2, m["dh"]), v.reshape(t, m["G"], 2, m["dh"])


def attention(p, a, k, v, m, compute, block, index, window):
    """Differential attention of the queries of ``a [T, d]`` over ``k, v [T,
    G, 2, dh]`` (the layer's own, or the kept ones), a block of queries at a
    time; ``window`` 0: every causal key."""
    t, pairs, g, dh = a.shape[0], m["P"], m["G"], m["dh"]
    if "w_q" in p:
        q = _biased(_mm(a, p["w_q"], compute), p, "b_q", m)
    else:
        q = _biased(_mm(a, p["w_qkv"], compute), p, "b_qkv", m)[:, :2 * pairs * dh]
    q = q.reshape(t, pairs, 2, dh)
    if m["rotary"]:  # the fault (this model has none): every half-head turned
        ang = angles_1d(np.arange(t), m["theta"], dh // 2)
        q = rotate(q.reshape(t, 2 * pairs, dh), ang).reshape(q.shape)
        k = rotate(k.reshape(t, 2 * g, dh), ang).reshape(k.shape)
    values = v if m["values"] == "both" else jnp.stack([v[:, :, 1]] * 2, axis=2)
    first = 0.8 - 0.6 * jnp.exp(-0.3 * (index + m["lam_base"]))
    lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(jnp.float32) * p["lambda_k1"].astype(jnp.float32)))
           - jnp.exp(jnp.sum(p["lambda_q2"].astype(jnp.float32) * p["lambda_k2"].astype(jnp.float32)))
           + first)
    lam = {"learned": lam, "zero": 0.0, "init": first}[m["lam"]]
    reach = window - 1 if m["window_own"] else window  # keys before the query's own

    def block_out(t0):
        rows = (t0 + jnp.arange(block))[:, None]
        open_ = jnp.arange(t)[None, :] <= rows
        if window:
            open_ = open_ & (jnp.arange(t)[None, :] >= rows - reach)
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block)

        def softmax_of(qh, kh, vh):
            logit = _mm(qh, kh.T, compute) * dh ** -0.5
            return _mm(jax.nn.softmax(jnp.where(open_, logit, -jnp.inf), axis=-1), vh, compute)

        out = []
        if m["plain"]:  # the other reading: plain softmax, 2P heads of dh over 2G
            for h in range(2 * pairs):
                kv_h = h // (pairs // g)
                out.append(softmax_of(qb[:, h // 2, h % 2], k[:, kv_h // 2, kv_h % 2],
                                      v[:, kv_h // 2, kv_h % 2]))
            return jnp.concatenate(out, axis=-1)
        for j in range(pairs):
            kv_j = j // (pairs // g)
            both = values[:, kv_j].reshape(t, 2 * dh)
            o1, o2 = (softmax_of(qb[:, j, c], k[:, kv_j, c], both) for c in (0, 1))
            o = o1 - lam * o2
            if m["sub_norm"]:
                o = rms(o, p["sub_norm"], m["eps"])
            out.append(o * (1.0 - first))
        return jnp.concatenate(out, axis=-1)

    o = jax.lax.map(block_out, jnp.arange(0, t, block)).reshape(t, 2 * pairs * dh)
    return _biased(_mm(o, p["wo"], compute), p, "b_o", m)


def layer(p, x, kind, m, compute=jnp.float32, block=128, index=0, read=None):
    """One layer (``kind``: an entry of :func:`kinds`; ``index`` its place;
    ``read`` what the layer :func:`reads` made): ``x [T, d]`` float32 ->
    ``(x', made)``, ``made`` the scan output of a Mamba layer or the ``(k, v)``
    of an attention layer (``None`` else), for a later layer to read."""
    a = ln(x, p["norm1"], p["norm1_b"], m["eps"])
    made = None
    if kind == MAMBA:
        op, made = mamba(p, a, m, compute)
    elif kind == GMU:
        op = _mm(jax.nn.silu(_mm(a, p["w_1"], compute)) * read, p["w_2"], compute)
    elif kind == CROSS:
        op = attention(p, a, *read, m, compute, block, index, 0)
    else:
        made = keys_values(p, a, m, compute)
        op = attention(p, a, *made, m, compute, block, index, m["window"] if kind == WINDOW else 0)
    x = x + op
    return x + dense_mlp(p, ln(x, p["norm2"], p["norm2_b"], m["eps"]), compute), made


def logits_of(params, x, m, compute=jnp.float32):
    """The tied head: the final LayerNorm, then the embedding table's rows."""
    return _mm(ln(x, params["norm"], params["norm_b"], m["eps"]), params["embed"].T, compute)


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``: every
    layer on every row."""
    x = embed(params, patches, prompt_ids, compute)
    made = {}
    for i, (p, kind) in enumerate(zip(params["layers"], kinds(m))):
        x, made[i] = layer(p, x, kind, m, compute, block, i, made.get(reads(m, i)))
    return x
