"""Plain float32 forward pass of DeepSeek-V3.2's block (``deepseek_v32``)
as the frame reader runs it: the reference for
``deepseek_v32_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is
listed in the configuration file's ``assumed``. The block is DeepSeek-V3's
(``reference/kimi_k2_decoder.py`` writes it out: latent attention, YaRN,
the sigmoid router under a selection bias, the shared expert) with two
things more. One sequence of ``T`` tokens, ``x [T, d]``, ``a = rms(x; g1)``:

    c_q = rms(a W_dq; g_q)    q = c_q W_uq = [q_n | q_r]    [c_kv | k_r] = a W_dkv ...   (as kimi's)

    the indexer (DSA):
        qI[j] = c_q W_Iq[j]              j < H_I, each d_I wide: from the query's normed low rank
        kI    = LayerNorm(a W_Ik; g, b)  ONE index key, d_I wide (mean taken out, gain and bias)
        w     = a W_Iw / sqrt(H_I)
        the first dr components of every qI[j] and of kI turn by k_r's angles; the rest do not
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(d_I)                 s <= t
        Sel(t)  = the min(t + 1, topk) keys s <= t of largest I[t, s]; equal scores: the earlier key
    o[t, h] = sum_{s in Sel(t)} softmax_{Sel(t)}(score[t, s, h]) v[s, h]   the same Sel(t) in every head

    the router's GROUP LIMIT (n_group, topk_group), on the choosing scores c = sigmoid(b W_r) + bias:
        the E experts are n_group runs of E / n_group consecutive ones; a group's score is the sum
        of its two largest c; the topk_group best groups stay (equal scores: the lower group);
        T(t) = the k_e largest c among the experts of those groups (equal: the lower index)
        gate_e = s_e / (sum_{T(t)} s + 1e-20) * routed_scaling_factor        (s without the bias)

No kernel, no grouped product, no batch: the index scores of a block of
queries against every key, ``Sel`` by a stable sort
(``keye_decoder.select``), attention as a masked softmax over that block's
whole rows, all heads in one batched product; the group limit by ranks from stable sorts; the
experts as a loop over the held ones. The holder's SHARE is the
reference's too (the held experts' weights, the shared expert, the
vocabulary slice). Float32 at ``Precision.HIGHEST``; ``compute=jnp.bfloat16``
gives the precision yardstick as ``keye_decoder`` describes it. No code of
the package under test.

Departures from DeepSeek's published code, each the program's own too and
listed in the configuration file: no Hadamard rotation of ``qI`` and ``kI``
(orthogonal: it leaves ``qI . kI`` as it was) and no FP8 quantisation of
them; rotary pairs are components ``(i, i + dr/2)``; the multi-token
prediction module is not built (it adds nothing to a prefill's logits).

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/dsv32_controls.py``): ``indexer`` (False: every causal key
attended), ``select`` (``"latest"``: the latest ``topk`` keys),
``index_query`` (``"input"``: the index queries from the first ``rq``
components of ``a``, the layer's input, instead of ``c_q``), ``index_key_norm``
(``"rms"``), ``index_rope`` (``"whole"``: the rotary over all ``d_I``
components), ``group_limit`` (False: plain top ``k_e`` of ``E``),
``select_bias`` (False), ``shared`` (False), ``mscale`` (False)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, dense_mlp, rms, rotate, select
from benchmark.reference.kimi_k2_decoder import (  # noqa: F401 — the adapter reads them here
    _get_mscale, embed, kinds, logits_of, patches_of, shared_expert, yarn_inv_freq)


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping
    (DeepSeek-V3.2's Hugging Face keys), apart from the program's."""
    held = int(cfg["n_routed_experts"])
    m = {
        "H": int(cfg["num_attention_heads"]), "rq": int(cfg["q_lora_rank"]),
        "rkv": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
        "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
        "HI": int(cfg["index_n_heads"]), "dI": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "rope_scaling": dict(cfg["rope_scaling"]), "layers": int(cfg["num_hidden_layers"]),
        "n_dense": int(cfg["first_k_dense_replace"]),
        "E": int(cfg.get("router_experts", held)), "k_e": int(cfg["num_experts_per_tok"]),
        "n_group": int(cfg["n_group"]), "topk_group": int(cfg["topk_group"]),
        "experts_held": tuple(cfg.get("experts_held", (0, held))),
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "scale": float(cfg["routed_scaling_factor"]), "n_shared": int(cfg["n_shared_experts"]),
        "yarn": True,  # kimi_k2_decoder.yarn_inv_freq reads it
        "indexer": True, "select": "scores", "index_query": "rank", "index_key_norm": "layer",
        "index_rope": "part", "group_limit": True,
        "select_bias": cfg["topk_method"] == "noaux_tc", "shared": True, "mscale": True,
    }
    if m["rope_scaling"]["type"] != "yarn" or cfg["scoring_func"] != "sigmoid":
        raise ValueError("only YaRN's rotary and sigmoid affinities are written here")
    m.update(fault)
    return m


def layer_norm(u, g, b, eps):
    u = u - jnp.mean(u, axis=-1, keepdims=True)
    return (u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32))


def turn_leading(x, ang, width):
    """The first ``width`` components of ``x [T, heads, w]`` turned, the rest as they are."""
    return jnp.concatenate([rotate(x[..., :width], ang), x[..., width:]], axis=-1)


def index_vectors(p, a, c_q, ang, m, compute):
    """``(qI [T, H_I, d_I], kI [T, d_I], w [T, H_I])`` after norm and rotary."""
    t, HI, dI = a.shape[0], m["HI"], m["dI"]
    q_from = c_q if m["index_query"] == "rank" else a[:, :m["rq"]]  # the fault: the layer's input
    q_i = _mm(q_from, p["idx_wq"], compute).reshape(t, HI, dI)
    k_i = _mm(a, p["idx_wk"], compute)
    if m["index_key_norm"] == "layer":
        k_i = layer_norm(k_i, p["idx_k_norm"], p["idx_k_bias"], m["eps"])
    else:  # the fault: an RMS norm, no mean taken out, no bias
        k_i = rms(k_i, p["idx_k_norm"], m["eps"])
    if m["index_rope"] == "part":
        q_i, k_i = turn_leading(q_i, ang, m["dr"]), turn_leading(k_i[:, None], ang, m["dr"])[:, 0]
    else:  # the fault: the whole index head turns, by YaRN's frequencies for its width
        whole = jnp.asarray(np.arange(t, dtype=np.float64)[:, None]
                            * yarn_inv_freq({**m, "dr": dI}), jnp.float32)
        q_i, k_i = rotate(q_i, whole), rotate(k_i[:, None], whole)[:, 0]
    return q_i, k_i, _mm(a, p["idx_ww"], compute) / np.sqrt(HI)


def latent_attention(p, a, m, compute, block, with_sel=False):
    """MLA under the indexer's selection from the normed input ``a [T,
    d]``, a block of queries at a time: ``MLA(a) [T, d]`` and,
    ``with_sel``, ``Sel`` as a boolean ``[T, T]`` (small sizes)."""
    t = a.shape[0]
    H, dn, dr, dv, eps, rs = m["H"], m["dn"], m["dr"], m["dv"], m["eps"], m["rope_scaling"]
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * yarn_inv_freq(m), jnp.float32)
    turned = _get_mscale(rs["factor"], rs["mscale"]) / _get_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (dn + dr) ** -0.5
    if m["mscale"]:
        scale = scale * _get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    c_q = rms(_mm(a, p["wq_a"], compute), p["q_a_norm"], eps)
    q = _mm(c_q, p["wq_b"], compute).reshape(t, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], ang) * turned], axis=-1)
    down = _mm(a, p["wkv_a"], compute)
    c_kv = rms(down[:, :m["rkv"]], p["kv_a_norm"], eps)
    k_r = rotate(down[:, None, m["rkv"]:], ang) * turned
    kv = _mm(c_kv, p["wkv_b"], compute).reshape(t, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (t, H, dr))], axis=-1)
    v = kv[..., dn:]
    q_i, k_i, w_i = index_vectors(p, a, c_q, ang, m, compute)
    # head-major, so that a block's scores are ONE batched product over the heads (128 products
    # written out one by one took the chip's compiler a minute a layer)
    q, k, v = jnp.transpose(q, (1, 0, 2)), jnp.transpose(k, (1, 2, 0)), jnp.transpose(v, (1, 0, 2))

    def block_out(t0):
        at = t0 + jnp.arange(block)
        sel = jnp.arange(t)[None, :] <= at[:, None]
        if m["indexer"] and m["select"] == "latest":  # the fault: a sliding window of topk keys
            sel = sel & (jnp.arange(t)[None, :] > at[:, None] - m["topk"])
        elif m["indexer"]:
            qi = jax.lax.dynamic_slice_in_dim(q_i, t0, block)
            wi = jax.lax.dynamic_slice_in_dim(w_i, t0, block)
            dots = _mm(qi.reshape(block * m["HI"], m["dI"]), k_i.T, compute)
            scores = jnp.sum(wi[:, :, None] * jax.nn.relu(dots.reshape(block, m["HI"], t)),
                             axis=1) / np.sqrt(m["dI"])
            sel = select(scores, at, m["topk"])
        logit = _mm(jax.lax.dynamic_slice_in_dim(q, t0, block, axis=1), k, compute) * scale
        prob = jax.nn.softmax(jnp.where(sel[None], logit, -jnp.inf), axis=-1)  # [H, block, T]
        out = jnp.transpose(_mm(prob, v, compute), (1, 0, 2))
        return out.reshape(block, H * dv), (sel if with_sel else None)

    o, sel = jax.lax.map(block_out, jnp.arange(0, t, block))
    return _mm(o.reshape(t, H * dv), p["wo"], compute), (sel.reshape(t, t) if with_sel else None)


def _rank(scores):
    """Each entry's place in its row, largest first, equal scores the lower index first."""
    return jnp.argsort(jnp.argsort(-scores, axis=-1, stable=True), axis=-1)


def chosen_experts(s, by, m):
    """``[T, E]`` 0/1: each token's expert set from the affinities ``s``
    and the selection bias ``by``."""
    c = s + by
    if m["group_limit"] and m["n_group"] > 1:
        t, per = c.shape[0], m["E"] // m["n_group"]
        groups = c.reshape(t, m["n_group"], per)
        two = jnp.sum(jnp.where(_rank(groups) < 2, groups, 0.0), axis=-1)  # [T, n_group]
        stays = _rank(two) < m["topk_group"]
        c = jnp.where(jnp.repeat(stays, per, axis=1), c, -jnp.inf)
    return _rank(c) < m["k_e"]


def experts(p, b, m, compute):
    """The routed experts from the normed input ``b [T, d]``: the held
    experts' part of their sum, and each token's expert set ``[T, E]``."""
    first, count = m["experts_held"]
    s = jax.nn.sigmoid(_mm(b, p["router"], compute))
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


def layer(p, x, dense, m, compute=jnp.float32, block=128):
    """One layer (``dense``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    x = x + latent_attention(p, rms(x, p["norm1"], m["eps"]), m, compute, block)[0]
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
    for p, dense in zip(params["layers"], kinds(m)):
        x = layer(p, x, dense, m, compute, block)
    return x
