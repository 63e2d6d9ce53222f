"""Plain float32 forward pass of Ouro-2.6B's LOOPED trunk (``ouro``) as the
frame reader runs it: the reference for ``ouro_2p6b_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix (the
sandwich's four norms, the norm at the end of every pass, the gate's form)
is listed in the configuration file's ``assumed``. One sequence of ``T``
tokens, ``x [T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g``
one layer is (no bias, no norm on q or k)

    a = rms(x; g1)    q, k, v = a W_q, a W_k, a W_v -> [T, H, d_h]    q, k = rope(q), rope(k)
    o[t,h] = sum_{s<=t} softmax_s(q[t,h] . k[s,h//(H/G)] / sqrt(d_h)) v[s,h//(H/G)]
    x <- x + rms(concat_h(o) W_o; g1')                     (the sandwich: the branch normed AGAIN)
    b = rms(x; g2)    x <- x + rms((silu(b W_g) * b W_u) W_d; g2')

and the model, over ``R = total_ut_steps`` passes through the ONE stack of
``L`` layers:

    h_0 = the embedded rows
    h_r = rms(Layers_{1..L}(h_{r-1}); g_f)                 r = 1..R, the SAME weights in every pass
    lambda_r = sigmoid(h_r w_e + b_e)                      a scalar a token and pass
    p_r = lambda_r prod_{j<r} (1 - lambda_j)   (r < R)     p_R = prod_{j<R} (1 - lambda_j)
    logits = h_R W_head                                    (no second norm; the head untied)

``rope`` turns pair ``(i, i + d_h/2)`` by ``t * theta**(-2i/d_h)``, ``t`` the
index within the sequence, the same table in every pass. At the published
``early_exit_threshold`` 1 the model answers from the LAST pass, so every
pass runs. The passes are WRITTEN OUT (:func:`passes`: a Python loop over
``range(R)`` around a Python loop over the layers; no ``scan``, no carry: a
fault in the program's loop cannot be shared), attention is a softmax over
a block of queries' whole rows. Float32 at ``Precision.HIGHEST``;
``compute=jnp.bfloat16`` gives the precision yardstick as ``keye_decoder``
describes it, whose ``_mm``, ``rms``, ``rotate``, ``angles_1d`` and
``dense_mlp`` are used here; no code of the package under test.

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/ouro_controls.py``): ``passes`` (one too few),
``unshared_pass`` (``r``: in pass ``r`` layer ``i`` reads layer ``i + 1``'s
weights, another draw of the same distribution: that pass has weights of
its own), ``norm_between`` (False: ``g_f`` after the last pass only),
``sandwich`` (False: the branches added as they are), ``gate_before_norm``
(True: the gate reads the pass's rows before ``g_f``)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, angles_1d, dense_mlp, rms, rotate
from benchmark.reference.lfm2_decoder import embed, patches_of  # noqa: F401 — the adapter reads them here


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping (Ouro's
    Hugging Face keys), apart from the program's."""
    m = {
        "H": int(cfg["num_attention_heads"]), "G": int(cfg["num_key_value_heads"]),
        "dh": int(cfg["head_dim"]), "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]), "L": int(cfg["num_hidden_layers"]),
        "passes": int(cfg["total_ut_steps"]),
        "unshared_pass": None, "norm_between": True, "sandwich": True, "gate_before_norm": False,
    }
    if (set(cfg["layer_types"]) != {"full_attention"} or len(cfg["layer_types"]) != m["L"]
            or float(cfg["early_exit_threshold"]) != 1 or cfg.get("use_sliding_window")
            or cfg.get("rope_scaling") or cfg["tie_word_embeddings"]):
        raise ValueError("only Ouro's stack of full-attention layers with a plain rotary and an "
                         "untied head, every pass run (early_exit_threshold 1), is written here")
    m.update(fault)
    return m


def attention(p, a, m, compute, block):
    """Causal grouped-query attention and ``W_o`` from the normed input ``a
    [T, d]``, a block of queries at a time."""
    t = a.shape[0]
    H, G, dh = m["H"], m["G"], m["dh"]
    ang = angles_1d(np.arange(t), m["theta"], dh // 2)
    q = rotate(_mm(a, p["wq"], compute).reshape(t, H, dh), ang)
    k = rotate(_mm(a, p["wk"], compute).reshape(t, G, dh), ang)
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


def layer(p, x, m, compute=jnp.float32, block=128):
    """One sandwich layer: ``x [T, d]`` float32 -> ``x'``."""
    eps = m["eps"]
    o = attention(p, rms(x, p["norm1"], eps), m, compute, block)
    x = x + (rms(o, p["norm1_post"], eps) if m["sandwich"] else o)
    f = dense_mlp(p, rms(x, p["norm2"], eps), compute)
    return x + (rms(f, p["norm2_post"], eps) if m["sandwich"] else f)


def pass_end(params, x, m, compute=jnp.float32, last=True):
    """The end of a pass on its rows ``x [T, d]`` -> ``(h, lambda [T])``:
    the final norm (``norm_between`` False: after the last pass alone) and
    the exit gate's ``sigmoid(h w_e + b_e)``."""
    h = rms(x, params["norm"], m["eps"]) if m["norm_between"] or last else x
    read = x if m["gate_before_norm"] else h
    gate = params["exit_gate"]
    logit = _mm(read, gate["w"][:, None], compute)[:, 0] + gate["b"].astype(jnp.float32)
    return h, jax.nn.sigmoid(logit)


def exit_distribution(lam):
    """``lambda [R][T]``, one a pass, -> ``p [R, T]``: the probability of
    answering from pass ``r``."""
    stayed, p = jnp.ones_like(lam[0]), []
    for lam_r in lam[:-1]:
        p.append(lam_r * stayed)
        stayed = stayed * (1.0 - lam_r)
    return jnp.stack(p + [stayed])  # the last pass takes whoever has not left


def passes(params, x, m, compute=jnp.float32, block=128, one_layer=None, end=None):
    """The embedded rows ``x [T, d]`` through ``R`` passes of the stack ->
    ``(h_R [T, d], p [R, T])``. ``one_layer(p, x)`` and ``end(params, x,
    last)`` are :func:`layer` and :func:`pass_end` (an adapter hands them
    jitted: a layer's bf16 weights are then widened inside its own program,
    one layer at a time)."""
    one_layer = one_layer or (lambda p, x: layer(p, x, m, compute, block))
    end = end or (lambda params, x, last: pass_end(params, x, m, compute, last))
    ends = {k: params[k] for k in ("norm", "exit_gate")}
    lam = []
    for r in range(m["passes"]):
        layers = list(params["layers"])
        if r == m["unshared_pass"]:  # the fault: this pass has weights of its own
            layers = layers[1:] + layers[:1]
        for p in layers:
            x = one_layer(p, x)
        x, lam_r = end(ends, x, r == m["passes"] - 1)
        lam.append(lam_r)
    return x, exit_distribution(lam)


def logits_of(params, x, m, compute=jnp.float32):
    """The untied head on the last pass's rows, which are normed already."""
    return _mm(x, params["head"], compute)


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """``(h_R [T, d], p [R, T])`` of one sequence."""
    return passes(params, embed(params, patches, prompt_ids, compute), m, compute, block)
