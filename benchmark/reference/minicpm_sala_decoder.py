"""Plain float32 forward pass of MiniCPM-SALA's hybrid trunk
(``minicpm_sala``) as the frame reader runs it: the reference for
``minicpm_sala_prefill_epix10k2m``.

Sizes and multipliers from the model's public ``config.json``; what it does
not fix (the selection's sizes, the place of two norms and a gate, the
slopes) is listed in the configuration file's ``assumed``, and each such
point's OTHER reading is computable here (below). One sequence of ``T``
tokens, ``x [T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g`` and
``m = scale_depth / sqrt(published layers)`` every layer is ``h = x + m
Op(rms(x; g1))``, ``x' = h + m MLP(rms(h; g2))``, ``MLP(b) = (silu(b W_g) * b
W_u) W_d``; the embedded rows are times ``scale_emb`` and the logits ``rms(x;
g) W_head / (hidden_size / dim_model_base)`` (untied). ``Op`` is one of two
(``mixer_types``):

    lightning-attn(a):  q, k, v = a W_q, a W_k, a W_v -> [T, H, d]    no bias
                        q, k <- rotary(rms_head(q; gq)), rotary(rms_head(k; gk))     over all d columns
                        S_t[h] = lambda_h S_{t-1}[h] + k_t[h] (x) v_t[h]       S_0 = 0, [d, d]
                        lambda_h = exp(-2^(-8 (h + 1) / H))
                        o_t[h] = d^-1/2 S_t[h]^T q_t[h]
                        lightning = (rms_head(o; go) * sigmoid(a W_z)) W_o     (the norm BEFORE the gate)
    minicpm4(a):        q = a W_q -> [T, H, dh]   k, v = a W_k, a W_v -> [T, G, dh]   no norm, NO rotary
                        K[g, j] = mean(k[16 j : 16 j + 32, g])                 (kernel 32, stride 16)
                        p[t, h, j] = softmax_j(q[t,h] . K[g,j] dh^-1/2)  over j with 16 j + 31 <= t (else all 0)
                        r[t, g, j] = sum_{h in g} p[t, h, j]
                        b[t, g, n] = max_{4n-1 <= j <= 4n+3} r[t, g, j]    for blocks n <= t // 64
                        b = +inf for n < init_blocks and for the latest window_size / 64 blocks
                        Sel(t, g) = the topk largest b, equal scores to the LOWER n (lax.top_k's order)
                        o[t, h] = sum over keys i <= t with i // 64 in Sel(t, g) of softmax(q . k dh^-1/2) v
                        minicpm4 = (concat_h(o) * sigmoid(a W_G)) W_o          (an ELEMENTWISE gate)
                        a sequence of T <= dense_len attends densely (plain causal)

No kernel, no chunk, no batch: the recurrence TOKEN BY TOKEN (``lax.scan``
over ``t`` with the ``[H, d, d]`` float32 state, the lines above as they
stand: a chunk-boundary fault cannot be shared with the program), the
selection by a dense softmax over a block of queries' whole row of pooled
keys and ``lax.top_k`` (``query_block`` rows at a time, so that ``[16, block,
T/16]`` fits), attention as a masked softmax over that block's whole rows.
Float32 at ``Precision.HIGHEST``; ``compute=jnp.bfloat16`` gives the precision
yardstick as ``keye_decoder`` describes it: the operands of every product
rounded to ``compute`` (the pooled scores' and the recurrence's two among
them), the selection decided from those rounded products, sums and the state
itself float32. No code of the package under test. Departures from the
publication, noted: InfLLM-V2's kernels approximate ``p``'s denominator
through a second, coarser pooling; here (and in the program) the softmax is
the score's own. The MLP goes in blocks of rows (``[T, 16,384]`` float32 three
times over is 6.7 GB at 34,304 tokens): no effect on any number.

``sizes(cfg, **fault)`` can put a fault, or an ``assumed`` point's other
reading, in the mathematics' place (``tests/minicpm_sala_controls.py``;
``tests/test_decoder_minicpm_sala.py -k other_reading``): ``select`` (False:
dense attention whatever the length), ``window`` (keys: 0 drops the forced
local blocks), ``forced`` (``"beside"``: the forced blocks do not count among
the topk: the OTHER reading), ``group_sum`` (False: the group's first head's
probabilities for the sum), ``pool`` (``"first"``: a pool's first key for its
mean), ``attn_rotary`` (True: a plain rotary in the sparse layers),
``attn_qk_norm`` (True: a norm a head, without gain, on the sparse layers' q
and k: the OTHER reading of ``qk_norm``), ``gate`` (False: none; ``"head"``: a
scalar a head, the head's first column's: the OTHER reading), ``decay``
(``"first"``: head 0's in every head; ``"none"``: lambda = 1; ``"minimax"``:
MiniMax-01's factor ``1 - l / (L - 1) + 1e-5`` on layer ``l``'s slopes: the
OTHER reading), ``carry`` (``n``: the state dropped every ``n`` tokens),
``rotary`` (False: the linear layers unturned), ``qk_norm`` and ``out_norm``
(``"projection"``: over all ``H * d`` columns: the OTHER reading),
``gate_first`` (True: ``rms(o * sigmoid(z))``: the OTHER reading),
``residual``, ``embedding``, ``logits_scaling`` (each multiplier in its
place)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, angles_1d, rms, rotate
from benchmark.reference.lfm2_decoder import patches_of  # noqa: F401 — the adapter reads it here
from benchmark.reference.ling3_decoder import _rounded

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"  # mixer_types, as the file spells them
# MiniCPM4's published sparse_config (arXiv:2509.24663), where the file has none
SPARSE_CONFIG = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
                 "init_blocks": 1, "window_size": 2048, "dense_len": 8192}


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping
    (MiniCPM-SALA's Hugging Face keys), apart from the program's."""
    heads = int(cfg["num_attention_heads"])
    published = int((cfg.get("published") or {}).get("num_hidden_layers", cfg["num_hidden_layers"]))
    m = {
        "H": heads, "G": int(cfg["num_key_value_heads"]), "dh": int(cfg["head_dim"]),
        "Hl": int(cfg["lightning_nh"]), "dl": int(cfg["lightning_head_dim"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "mixers": tuple(cfg["mixer_types"]), "published_layers": published,
        "residual": float(cfg["scale_depth"]) / published ** 0.5,
        "embedding": float(cfg["scale_emb"]),
        "logits_scaling": int(cfg["hidden_size"]) / float(cfg["dim_model_base"]),
        "rotary": bool(cfg["lightning_use_rope"]), "attn_rotary": bool(cfg["attn_use_rope"]),
        **{k: int(v) for k, v in {**SPARSE_CONFIG, **(cfg.get("sparse_config") or {})}.items()},
        "select": True, "forced": "among", "group_sum": True, "pool": "mean", "attn_qk_norm": False,
        "gate": True, "decay": "lightning", "carry": 0, "qk_norm": "head", "out_norm": "head",
        "gate_first": False,
    }
    if (len(m["mixers"]) != int(cfg["num_hidden_layers"]) or set(m["mixers"]) - {SPARSE, LIGHTNING}
            or int(cfg["lightning_nkv"]) != m["Hl"] or cfg.get("attention_bias")
            or not (cfg["qk_norm"] and cfg["use_output_norm"] and cfg["use_output_gate"]
                    and cfg["attn_use_output_gate"]) or cfg["lightning_scale"] != "1/sqrt(d)"):
        raise ValueError("only MiniCPM-SALA's two mixers, with their norms and gates and no bias "
                         "in a product, are written here")
    m.update(fault)
    return m


def slopes(m, layer: int):
    """``-log lambda_h`` of the linear layers' heads, float32 ``[H]``."""
    h = m["Hl"]
    s = np.exp2(-8.0 * np.arange(1, h + 1) / h)
    if m["decay"] == "first":  # the fault
        s = np.full(h, s[0])
    elif m["decay"] == "none":  # the fault: lambda = 1
        s = np.zeros(h)
    elif m["decay"] == "minimax":  # the OTHER reading: MiniMax-01's factor a layer
        s = s * (1.0 - layer / (m["published_layers"] - 1) + 1e-5)
    return jnp.asarray(s, jnp.float32)


def head_norm(u, g, m, span):
    """``u [T, H, d]`` normed over each head's columns (gain ``g [d]``) or,
    ``span == "projection"``, over all ``H * d`` (the gain a head's, repeated)."""
    if span == "head":
        return rms(u, g, m["eps"])
    t, h, d = u.shape
    return rms(u.reshape(t, h * d), jnp.tile(g, h), m["eps"]).reshape(t, h, d)


def lightning(p, a, m, compute, layer: int):
    """The linear layer from the normed input ``a [T, d]``."""
    t, heads, d = a.shape[0], m["Hl"], m["dl"]
    hi = jax.lax.Precision.HIGHEST
    q, k, v = (_mm(a, p[w], compute).reshape(t, heads, d) for w in ("w_q", "w_k", "w_v"))
    q, k = head_norm(q, p["q_norm"], m, m["qk_norm"]), head_norm(k, p["k_norm"], m, m["qk_norm"])
    if m["rotary"]:
        ang = angles_1d(np.arange(t), m["theta"], d // 2)
        q, k = rotate(q, ang), rotate(k, ang)
    lam = jnp.exp(-slopes(m, layer))

    def one(state, u):
        q, k, v, i = u
        if m["carry"]:  # the fault: nothing crosses a boundary of `carry` tokens
            state = jnp.where(i % m["carry"] == 0, 0.0, state)
        state = lam[:, None, None] * state + jnp.einsum(
            "hk,hv->hkv", _rounded(k, compute), _rounded(v, compute), precision=hi)
        return state, jnp.einsum("hkv,hk->hv", _rounded(state, compute), _rounded(q, compute),
                                 precision=hi)

    _, o = jax.lax.scan(one, jnp.zeros((heads, d, d), jnp.float32), (q, k, v, jnp.arange(t)))
    o = o * d ** -0.5
    gate = jax.nn.sigmoid(_mm(a, p["w_z"], compute)).reshape(t, heads, d)
    if m["gate_first"]:  # the OTHER reading: the gate before the norm
        y = head_norm(o * gate, p["o_norm"], m, m["out_norm"])
    else:
        y = head_norm(o, p["o_norm"], m, m["out_norm"]) * gate
    return _mm(y.reshape(t, heads * d), p["wo"], compute)


def pooled_keys(k, m):
    """``k [T, G, dh]`` -> ``[T / stride - 1, G, dh]``: pooled key ``j`` the
    mean of keys ``stride * j .. stride * j + kernel - 1``."""
    st, ks = m["kernel_stride"], m["kernel_size"]
    n = (k.shape[0] - ks) // st + 1
    if m["pool"] == "first":  # the fault: a pool's first key
        return k[:n * st:st]
    return jnp.stack([k[i:i + n * st:st] for i in range(ks)]).mean(axis=0)


def block_scores(qb, pooled, t0, g, m, compute):
    """``b [block, T / block_size]`` of the queries ``qb [block, H, dh]`` at
    ``t0 ..`` for key head ``g``: unseen blocks and forced ones not yet marked."""
    st, ks, ratio = m["kernel_stride"], m["kernel_size"], m["block_size"] // m["kernel_stride"]
    rep, n_pool = m["H"] // m["G"], pooled.shape[0]
    t = t0 + jnp.arange(qb.shape[0])
    seen = (st * jnp.arange(n_pool)[None, :] + ks - 1) <= t[:, None]
    total = jnp.zeros(seen.shape, jnp.float32)
    for h in range(g * rep, (g + 1) * rep if m["group_sum"] else g * rep + 1):
        logit = _mm(qb[:, h], pooled[:, g].T, compute) * m["dh"] ** -0.5
        prob = jax.nn.softmax(jnp.where(seen, logit, -jnp.inf), axis=-1)
        total = total + jnp.where(seen, prob, 0.0)  # (a row that sees none: NaN, and 0 here)
    n_blocks = -(-(n_pool + 1) // ratio)
    # pooled key j at column j + 1: block n reads columns R n .. R n + R
    padded = jnp.pad(total, ((0, 0), (1, ratio * n_blocks + ratio - n_pool)))
    return jnp.stack([padded[:, c:c + ratio * n_blocks:ratio] for c in range(ratio + 1)]).max(axis=0)


def selection(score, t0, m):
    """``Sel`` as a boolean ``[block, n_blocks]`` from ``b`` (:func:`block_scores`)."""
    rows, n_blocks = score.shape
    last = (t0 + jnp.arange(rows))[:, None] // m["block_size"]
    n = jnp.arange(n_blocks)[None, :]
    forced = (n < m["init_blocks"]) | (n > last - m["window_size"] // m["block_size"])
    beside = m["forced"] == "beside"  # the OTHER reading: the forced blocks beside the topk
    key = jnp.where(n <= last, jnp.where(forced, -jnp.inf if beside else jnp.inf, score), -jnp.inf)
    values, ids = jax.lax.top_k(key, min(m["topk"], n_blocks))
    kept = jnp.zeros(score.shape, bool).at[jnp.arange(rows)[:, None], ids].max(values > -jnp.inf)
    return kept | (forced & (n <= last)) if beside else kept


def sparse_attention(p, a, m, compute, block):
    """The sparse layer from the normed input ``a [T, d]``, a block of queries at a time."""
    t = a.shape[0]
    H, G, dh = m["H"], m["G"], m["dh"]
    q = _mm(a, p["wq"], compute).reshape(t, H, dh)
    k = _mm(a, p["wk"], compute).reshape(t, G, dh)
    v = _mm(a, p["wv"], compute).reshape(t, G, dh)
    if m["attn_qk_norm"]:  # the OTHER reading: a norm a head here too (no gain is drawn for it)
        q, k = (rms(u, jnp.ones((dh,), jnp.float32), m["eps"]) for u in (q, k))
    if m["attn_rotary"]:  # the fault (these layers have none)
        ang = angles_1d(np.arange(t), m["theta"], dh // 2)
        q, k = rotate(q, ang), rotate(k, ang)
    selects = m["select"] and t > m["dense_len"]
    pooled = pooled_keys(k, m) if selects else None
    of_key = jnp.arange(t) // m["block_size"]

    def block_out(t0):
        causal = jnp.arange(t)[None, :] <= (t0 + jnp.arange(block))[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block)
        out = []
        for g in range(G):
            open_ = causal
            if selects:
                kept = selection(block_scores(qb, pooled, t0, g, m, compute), t0, m)
                open_ = causal & jnp.take_along_axis(
                    kept, jnp.broadcast_to(of_key[None, :], causal.shape), axis=1)
            for h in range(g * (H // G), (g + 1) * (H // G)):
                logit = _mm(qb[:, h], k[:, g].T, compute) * dh ** -0.5
                prob = jax.nn.softmax(jnp.where(open_, logit, -jnp.inf), axis=-1)
                out.append(_mm(prob, v[:, g], compute))
        return jnp.concatenate(out, axis=-1)

    o = jax.lax.map(block_out, jnp.arange(0, t, block)).reshape(t, H * dh)
    if m["gate"] == "head":  # the OTHER reading: a scalar a head (the head's first column's)
        gate = jax.nn.sigmoid(_mm(a, p["w_attn_gate"][:, ::dh], compute))
        o = (o.reshape(t, H, dh) * gate[:, :, None]).reshape(t, H * dh)
    elif m["gate"]:
        o = o * jax.nn.sigmoid(_mm(a, p["w_attn_gate"], compute))
    return _mm(o, p["wo"], compute)


def dense_mlp(p, b, compute, block):
    """``(silu(b W_g) * b W_u) W_d``, ``block`` rows at a time."""
    def rows(t0):
        u = jax.lax.dynamic_slice_in_dim(b, t0, block)
        h = jax.nn.silu(_mm(u, p["w_gate"], compute)) * _mm(u, p["w_up"], compute)
        return _mm(h, p["w_down"], compute)

    return jax.lax.map(rows, jnp.arange(0, b.shape[0], block)).reshape(b.shape)


def kinds(m) -> list:
    """Each layer's kind: its mixer and its place (a linear layer's slopes may
    depend on it, under the other reading)."""
    return list(zip(m["mixers"], range(len(m["mixers"]))))


def layer(p, x, kind, m, compute=jnp.float32, block=128):
    """One layer (``kind``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    mixer, place = kind
    a = rms(x, p["norm1"], m["eps"])
    op = (lightning(p, a, m, compute, place) if mixer == LIGHTNING
          else sparse_attention(p, a, m, compute, block))
    x = x + m["residual"] * op
    return x + m["residual"] * dense_mlp(p, rms(x, p["norm2"], m["eps"]), compute, block)


def embed(params, patches, prompt_ids, compute=jnp.float32, m=None):
    """The patch embedding's rows, then the prompt's: both times ``scale_emb``."""
    rows = jnp.concatenate([
        _mm(patches.astype(jnp.float32), params["patch"], compute),
        params["embed"][prompt_ids].astype(jnp.float32),
    ])
    return rows * m["embedding"]


def logits_of(params, x, m, compute=jnp.float32):
    """The untied head: the final norm, the head, over ``hidden_size / dim_model_base``."""
    return _mm(rms(x, params["norm"], m["eps"]), params["head"], compute) / m["logits_scaling"]


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``."""
    x = embed(params, patches, prompt_ids, compute, m)
    for p, kind in zip(params["layers"], kinds(m)):
        x = layer(p, x, kind, m, compute, block)
    return x
