"""Plain float32 forward pass of the Keye-VL-2.0 text decoder as the frame
reader runs it: the reference for ``keye_vl2_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is
taken from the two published descriptions it points at (Qwen3-MoE, whose
sizes it repeats, and DeepSeek-V3.2-Exp's description of DSA, which its
``sa_config`` names) and listed in the configuration file's ``assumed``.
With ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g``, for ``x [S, d]`` at
positions ``pos [S, 3]``:

    a      = rms(x; g1)
    q, k   = mrope(rms_head(a Wq; gq)), mrope(rms_head(a Wk; gk))     v = a Wv
    qI     = rope1d(a W_Iq)    kI = rope1d(rms(a W_Ik; gI))    w = (a W_Iw) / sqrt(H_I)
    I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]) / sqrt(d_I)                   s <= t
    Sel(t) = the min(t+1, topk) keys s <= t of largest I[t,s]; equal scores: the earlier key
    o[t,h] = sum_{s in Sel(t)} softmax_{Sel(t)}(q[t,h] . k[s,h//(H/G)] / sqrt(d_h)) v[s,h//(H/G)]
    x1     = x + concat_h(o) Wo
    b      = rms(x1; g2)        p = softmax(b Wr)        T(t) = top-k_e of p[t]
    x2     = x1 + sum_{e in T(t), e held} p[t,e] / sum_{T(t)} p[t,.] * (silu(b W1_e) * (b W3_e)) W2_e

and the trunk ``x0 = concat(patches W_patch, Emb[prompt])``, the layers,
``logits = rms(x_L; gf) W_head``. No kernel, no grouped product, no cache:
the index scores of a block of queries against every key, ``Sel`` by a
stable ``argsort`` of the negated scores (equal scores keep their key
order), attention as a masked softmax over that block's row of keys, the
experts as a loop over all held ones with a 0/1 membership in the gate.
Everything float32 at ``Precision.HIGHEST`` (the caller sets
``jax.default_matmul_precision("highest")`` too).

``compute=jnp.bfloat16`` gives the precision yardstick: the same pass
with both operands of every matrix product rounded to bfloat16 first and
float32 accumulation, the selection and the routing decided from those
rounded products.

Departures from that description, each the program's own too:

- rotary pairs are components ``(i, i + pairs)`` (rotate-half), for the
  attention heads' 64 pairs and the indexer's 32 alike;
- a product of bfloat16-rounded operands is computed as a bfloat16
  product with float32 accumulation, not as a float32 product of the
  rounded values: the same sum of the same exact products, six times
  cheaper on the chip;
- each such rounding sits behind ``lax.optimization_barrier``: where XLA
  widens a product's operands again itself (the head's one-row product on
  the TPU) it drops the convert pair and the yardstick reads exactly 0;
- queries go in blocks of ``block`` (a block's ``[block, S]`` scores are
  what fits), under ``lax.map``: no effect on any number.
"""

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, compute):
    if compute == jnp.float32:
        return jnp.matmul(a.astype(compute), b.astype(compute), precision=HIGHEST)
    # the rounding behind a barrier, or XLA may drop it (module docstring)
    a, b = (jax.lax.optimization_barrier(x.astype(compute)) for x in (a, b))
    if compute == jnp.bfloat16:
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)
    # operands rounded to another type (float8: the precision below) and widened again
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST)


def rms(u, g, eps):
    return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def angles_1d(pos, theta, pairs):
    return jnp.asarray(pos, jnp.float32)[:, None] * jnp.asarray(
        theta ** (-np.arange(pairs, dtype=np.float64) / pairs), jnp.float32)


def angles_mrope(pos, theta, sections):
    """Pair ``i`` of ``sum(sections)`` turns by the position component of
    the section it falls in, times ``theta**(-i/pairs)``."""
    pairs = int(sum(sections))
    out = []
    for i in range(pairs):
        comp = 0 if i < sections[0] else (1 if i < sections[0] + sections[1] else 2)
        out.append(jnp.asarray(pos[:, comp], jnp.float32) * np.float32(theta ** (-i / pairs)))
    return jnp.stack(out, axis=1)


def positions(panels, rows, cols, prompt_len):
    """``[S, 3]`` positions ``(t, h, w)``, as the configuration's
    ``assumed`` places the tokens: the patch of panel ``p``, row ``r``,
    column ``c`` at ``(p, r, c)``, panel after panel and row after row;
    prompt token ``i`` at the first position past every patch's, all three
    components equal (48 + i for 16 panels of 44 x 48 patches)."""
    pos = [(p, r, c) for p in range(panels) for r in range(rows) for c in range(cols)]
    first = max(panels, rows, cols)
    return np.asarray(pos + [(first + i,) * 3 for i in range(prompt_len)], np.int32)


def rotate(x, angles):
    """``x [S, heads, 2*pairs]``: components ``(i, i+pairs)`` turn by ``angles[:, i]``."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    return jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                            x[..., half:] * cos + x[..., :half] * sin], axis=-1)


def select(scores, t, topk):
    """``scores [B, S]`` of queries at positions ``t [B]`` -> boolean
    ``[B, S]``: ``Sel``. Keys after the query sort last; a stable sort of
    the negated scores puts equal scores in key order."""
    s = scores.shape[1]
    causal = jnp.arange(s)[None, :] <= t[:, None]
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=1, stable=True)
    first = order[:, :topk]  # each query's topk best keys (later keys among them if t + 1 < topk)
    chosen = jnp.zeros(scores.shape, bool).at[jnp.arange(len(t))[:, None], first].set(True)
    return causal & chosen


def attention(p, a, pos, m, compute, block, with_sel=True):
    """The attention half of a layer from the normed input ``a [S, d]``:
    ``concat_h(o) [S, H*d_h]`` and, ``with_sel``, ``Sel`` as a boolean
    ``[S, S]`` (at small sizes: it is S^2 bytes)."""
    s = a.shape[0]
    H, G, dh, eps, theta = m["H"], m["G"], m["dh"], m["eps"], m["theta"]
    ang = angles_mrope(pos, theta, m["mrope_section"])
    q = rotate(rms(_mm(a, p["wq"], compute).reshape(s, H, dh), p["q_norm"], eps), ang)
    k = rotate(rms(_mm(a, p["wk"], compute).reshape(s, G, dh), p["k_norm"], eps), ang)
    v = _mm(a, p["wv"], compute).reshape(s, G, dh)
    k = jnp.repeat(k, H // G, axis=1)  # query head h reads key-value head h // (H/G)
    v = jnp.repeat(v, H // G, axis=1)
    if m["topk"]:
        HI, dI = m["HI"], m["dI"]
        ang_i = angles_1d(np.arange(s), theta, dI // 2)
        q_i = rotate(_mm(a, p["idx_wq"], compute).reshape(s, HI, dI), ang_i)
        k_i = rotate(rms(_mm(a, p["idx_wk"], compute), p["idx_k_norm"], eps)[:, None, :], ang_i)[:, 0]
        w_i = _mm(a, p["idx_ww"], compute) / np.sqrt(HI)

    def block_out(t0):
        t = t0 + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block)
        if m["topk"]:
            qi = jax.lax.dynamic_slice_in_dim(q_i, t0, block)
            wi = jax.lax.dynamic_slice_in_dim(w_i, t0, block)
            dots = _mm(qi.reshape(block * HI, dI), k_i.T, compute).reshape(block, HI, s)
            scores = jnp.sum(wi[:, :, None] * jax.nn.relu(dots), axis=1) / np.sqrt(dI)
            sel = select(scores, t, m["topk"])
        else:
            sel = jnp.arange(s)[None, :] <= t[:, None]
        out = []
        for h in range(H):
            logit = _mm(qb[:, h], k[:, h].T, compute) / np.sqrt(dh)
            prob = jax.nn.softmax(jnp.where(sel, logit, -jnp.inf), axis=-1)
            out.append(_mm(prob, v[:, h], compute))
        return jnp.concatenate(out, axis=-1), (sel if with_sel else None)

    o, sel = jax.lax.map(block_out, jnp.arange(0, s, block))
    return o.reshape(s, H * dh), (sel.reshape(s, s) if with_sel else None)


def experts(p, b, m, compute):
    """The expert half from the normed input ``b [S, d]``: the held
    experts' part of the layer's result, and each token's expert set."""
    E, k_e = m["E"], m["k_e"]
    first, count = m["experts_held"]
    probs = jax.nn.softmax(_mm(b, p["router"], compute), axis=-1)
    rank = jnp.argsort(jnp.argsort(-probs, axis=-1, stable=True), axis=-1)
    chosen = rank < k_e  # [S, E], 0/1: equal probabilities, the lower index first
    gate = probs * chosen
    if m["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    def one(e, y):
        h = jax.nn.silu(_mm(b, p["w_gate"][e], compute)) * _mm(b, p["w_up"][e], compute)
        g = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1, keepdims=True)
        return y + g * _mm(h, p["w_down"][e], compute)

    return jax.lax.fori_loop(0, count, one, jnp.zeros(b.shape, jnp.float32)), chosen


def dense_mlp(p, b, compute):
    h = jax.nn.silu(_mm(b, p["w_gate"], compute)) * _mm(b, p["w_up"], compute)
    return _mm(h, p["w_down"], compute)


def layer(p, x, pos, m, compute=jnp.float32, block=128, with_sel=True):
    """One block ``x [S, d]`` float32 -> ``(x2, Sel [S, S] or None, expert
    sets [S, E] or None)``."""
    o, sel = attention(p, rms(x, p["norm1"], m["eps"]), pos, m, compute, block, with_sel)
    x = x + _mm(o, p["wo"], compute)
    b = rms(x, p["norm2"], m["eps"])
    if m["E"]:
        y, chosen = experts(p, b, m, compute)
        return x + y, sel, chosen
    return x + dense_mlp(p, b, compute), sel, None


def embed(params, patches, prompt_ids, compute=jnp.float32):
    return jnp.concatenate([
        _mm(patches.astype(jnp.float32), params["patch"], compute),
        params["embed"][prompt_ids].astype(jnp.float32),
    ])


def logits_of(params, x, m, compute=jnp.float32):
    return _mm(rms(x, params["norm"], m["eps"]), params["head"], compute)


def sizes(cfg) -> dict:
    """The reference's own reading of the configuration mapping (the
    Hugging Face keys), apart from the program's."""
    sa = cfg.get("sa_config")
    n_exp = int(cfg.get("num_experts", 0))
    return {
        "H": int(cfg["num_attention_heads"]), "G": int(cfg["num_key_value_heads"]),
        "dh": int(cfg["head_dim"]), "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "mrope_section": tuple(cfg["rope_scaling"]["mrope_section"]),
        "HI": int(sa["indexer_num_heads"]) if sa else 0,
        "dI": int(sa["indexer_head_dim"]) if sa else 0,
        "topk": int(sa["topk"]) if sa else 0,
        "E": n_exp, "k_e": int(cfg.get("num_experts_per_tok", 0)),
        "experts_held": tuple(cfg.get("experts_held", (0, n_exp))),
        "norm_topk_prob": bool(cfg.get("norm_topk_prob", True)),
    }


def forward(params, patches, prompt_ids, pos, cfg, compute=jnp.float32, block=128):
    """All positions' logits ``[S, V]`` (small sizes: the tests)."""
    m = sizes(cfg)
    x = embed(params, patches, prompt_ids, compute)
    for p in params["layers"]:
        x, _, _ = layer(p, x, pos, m, compute, block)
    return logits_of(params, x, m, compute)
