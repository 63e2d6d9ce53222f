"""Plain float32 forward pass of Xing4.0-29B-A4B's block (``xing4_0``:
DeepSeek-V3's block under manifold-constrained hyper-connections) as the frame
reader runs it: the reference for ``xing4_29b_a4b_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is listed
in the configuration file's ``assumed``. One sequence of ``T`` tokens. The
stream is ``X [T, n, D]``, ``n = hc_mult`` rows a token (DeepSeek-AI, "mHC:
Manifold-Constrained Hyper-Connections", arXiv:2512.24880, after
"Hyper-Connections", arXiv:2409.19606). Each of a layer's two branches ``F``
(``MLA(rms(.; g1))``; ``FF(rms(.; g2))``: ``kimi_k2_decoder``'s, by import:
that module is a reference too, and no code of the package under test) has its
own ``phi [n D, n (n + 2)]`` (``[pre | post | res]``, the last row-major), three
scalars ``alpha`` and a bias ``b [n (n + 2)]``:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)          over all n D channels
    h  = alpha * (x~ phi) + b                                  alpha: one scalar a part
    H_pre = sigmoid(h_pre) [n]     H_post = 2 sigmoid(h_post) [n]
    M = exp(clip(h_res, clamp_min, clamp_max)) [n, n], then hc_sinkhorn_iters times:
        every ROW over its sum + hc_eps, then every COLUMN over its sum + hc_eps;  H_res = M
    u = sum_j H_pre[j] X_j          y = F(u)
    X'_i = sum_j H_res[i, j] X_j + H_post[i] y

The embedded row enters as ``n`` equal streams (:func:`widen`); the streams are
SUMMED ahead of the final norm (:func:`narrow`). The Sinkhorn is a Python loop
of row and column divisions over ``[T, n, n]``, the mixes are ``einsum``s over
``[T, n, D]``. Float32 at ``Precision.HIGHEST``; ``compute=jnp.bfloat16``
gives the precision yardstick as ``keye_decoder`` describes it (every
PRODUCT's operands rounded: ``x~ phi`` among them; the stream and the mixes
stay float32).

``sizes(cfg, **fault)`` takes ``kimi_k2_decoder.sizes``' faults and, of the
mechanism's own:

- the OTHER READING of each point the configuration's ``assumed`` lists:
  ``order`` (``"columns_first"``), ``eps_in`` (``"norm"``: ``hc_eps`` is the
  wide norm's and the sums are unguarded), ``wide_gain`` (True: ``x~`` times a
  gain ``hcK_gain [n D]`` of the layer's), ``exit`` (``"mean"``), ``alpha_on``
  (``"sum"``: ``alpha (x~ phi + b)``);
- faults, for the controls (``tests/xing4_controls.py``): ``res``
  (``"identity"``: a plain residual a stream), ``iters`` (0: ``exp`` alone; 1;
  19), ``sinkhorn`` (``"rows"``: columns never normed), ``post_two`` (False),
  ``pre_sigmoid`` (False), ``wide_norm`` (False), ``alpha_scale`` (0.0),
  ``reads`` (``"stream0"``: the branch fed stream 0, not the mix), ``exit``
  (``"stream0"``), ``ff_mix`` (``"attention"``: the attention branch's mixing
  numbers used for the feed-forward's), ``clamp`` (False)."""

import jax
import jax.numpy as jnp

from benchmark.reference import kimi_k2_decoder as block
from benchmark.reference.keye_decoder import _mm, dense_mlp, rms
from benchmark.reference.kimi_k2_decoder import embed, kinds, logits_of, patches_of  # noqa: F401


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping."""
    own = {"order": "rows_first", "eps_in": "sums", "wide_gain": False, "exit": "sum",
           "alpha_on": "product", "res": "sinkhorn", "iters": int(cfg["hc_sinkhorn_iters"]),
           "sinkhorn": "both", "post_two": True, "pre_sigmoid": True, "wide_norm": True,
           "alpha_scale": 1.0, "reads": "mix", "ff_mix": "own", "clamp": True}
    m = block.sizes(cfg, **{k: v for k, v in fault.items() if k not in own})
    m.update(own, n=int(cfg["hc_mult"]), hc_eps=float(cfg["hc_eps"]),
             clamp_at=(float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"])))
    m.update({k: v for k, v in fault.items() if k in own})
    return m


def mixing(p, x, which, m, compute):
    """Branch ``which`` (``"hc1"``: attention's; ``"hc2"``: the feed-forward's)
    of a layer, from the stream ``x [T, n, D]`` -> ``(H_pre [T, n], H_post [T,
    n], H_res [T, n, n])``."""
    t, n = x.shape[0], m["n"]
    flat = x.reshape(t, -1)
    if m["wide_norm"]:
        eps = m["hc_eps"] if m["eps_in"] == "norm" else m["eps"]
        flat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    if m["wide_gain"]:
        flat = flat * p[which + "_gain"].astype(jnp.float32)
    h = _mm(flat, p[which + "_phi"], compute)
    alpha = jnp.repeat(p[which + "_alpha"].astype(jnp.float32) * m["alpha_scale"],
                       jnp.asarray([n, n, n * n]), total_repeat_length=n * (n + 2))
    b = p[which + "_b"].astype(jnp.float32)
    h = alpha * (h + b) if m["alpha_on"] == "sum" else alpha * h + b
    h_pre, h_post, h_res = h[:, :n], h[:, n:2 * n], h[:, 2 * n:].reshape(t, n, n)
    pre = jax.nn.sigmoid(h_pre) if m["pre_sigmoid"] else h_pre
    post = (2.0 if m["post_two"] else 1.0) * jax.nn.sigmoid(h_post)
    if m["res"] == "identity":
        return pre, post, jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), (t, n, n))
    res = jnp.exp(jnp.clip(h_res, *m["clamp_at"]) if m["clamp"] else h_res)
    guard = m["hc_eps"] if m["eps_in"] == "sums" else 0.0
    steps = (2, 1) if m["order"] == "rows_first" else (1, 2)  # a row's sum runs over axis 2
    if m["sinkhorn"] == "rows":
        steps = (2,)
    for _ in range(m["iters"]):
        for axis in steps:
            res = res / (jnp.sum(res, axis=axis, keepdims=True) + guard)
    return pre, post, res


def mix_in(x, pre, m):
    """What a branch reads, ``[T, D]``."""
    return x[:, 0] if m["reads"] == "stream0" else jnp.einsum("tj,tjd->td", pre, x)


def mix_out(x, y, post, res):
    """The stream after a branch whose output is ``y [T, D]``."""
    return jnp.einsum("tij,tjd->tid", res, x) + post[:, :, None] * y[:, None, :]


def feed_forward(p, b, dense, m, compute):
    if dense:
        return dense_mlp(p, b, compute)
    y = block.experts(p, b, m, compute)[0]
    if m["n_shared"] and m["shared"]:
        y = y + block.shared_expert(p, b, compute)
    return y


def layer(p, x, dense, m, compute=jnp.float32, block_rows=128):
    """One layer (``dense``: an entry of :func:`kinds`): the stream ``x [T, n *
    D]`` float32 -> ``x'`` (the embedded rows ``[T, D]``, as :func:`embed`
    leaves them, enter as ``n`` equal streams: :func:`widen`)."""
    t = x.shape[0]
    if x.shape[1] == p["norm1"].shape[0]:
        x = widen(x, m)
    x = x.reshape(t, m["n"], -1)
    first = mixing(p, x, "hc1", m, compute)
    y = block.latent_attention(p, rms(mix_in(x, first[0], m), p["norm1"], m["eps"]), m, compute,
                               block_rows)
    x = mix_out(x, y, *first[1:])
    second = first if m["ff_mix"] == "attention" else mixing(p, x, "hc2", m, compute)
    y = feed_forward(p, rms(mix_in(x, second[0], m), p["norm2"], m["eps"]), dense, m, compute)
    return mix_out(x, y, *second[1:]).reshape(t, -1)


def widen(x, m):
    """The embedded rows ``[T, D]`` as ``n`` equal streams: ``[T, n * D]``."""
    return jnp.tile(x, (1, m["n"]))


def narrow(x, m):
    """The streams ``[T, n * D]`` ahead of the final norm: their sum."""
    x = x.reshape(x.shape[0], m["n"], -1)
    if m["exit"] == "stream0":
        return x[:, 0]
    return jnp.mean(x, axis=1) if m["exit"] == "mean" else jnp.sum(x, axis=1)


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, D]``: the
    streams after the last layer, summed."""
    x = widen(embed(params, patches, prompt_ids, compute), m)
    for p, dense in zip(params["layers"], kinds(m)):
        x = layer(p, x, dense, m, compute, block)
    return narrow(x, m)
