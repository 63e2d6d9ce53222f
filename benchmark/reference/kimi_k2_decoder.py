"""Plain float32 forward pass of Kimi-K2-Instruct's block (``kimi_k2``:
DeepSeek-V3's) as the frame reader runs it: the reference for
``kimi_k2_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is
listed in the configuration file's ``assumed``. One sequence of ``T``
tokens, ``x [T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g``,
every layer is

    h  = x + MLA(rms(x; g1))             x' = h + FF(rms(h; g2))

    MLA(a):   c_q = rms(a W_dq; g_q)                  q = c_q W_uq -> [T, H, dn + dr] = [q_n | q_r]
              [c_kv | k_r] = a W_dkv                  c_kv <- rms(c_kv; g_kv)      (k_r is not normed)
              [k_n | v] = c_kv W_ukv -> [T, H, dn + dv]
              q_r (each head) and k_r (ONE for all heads) turn by YaRN's angles at the token's index
              score[t, s, h] = (q_n[t,h] . k_n[s,h] + q_r[t,h] . k_r[s]) (dn + dr)^(-1/2) m^2,  s <= t
              o[t, h] = sum_s softmax_s(score) v[s, h]        MLA = concat_h(o) W_o
    FF, l < first_k_dense_replace:   (silu(b W1) * (b W3)) W2
    FF, else: s = sigmoid(b W_r)     T(t) = the k_e largest of s[t] + bias  (equal: the lower index)
              gate_e = s_e / (sum_{T(t)} s + 1e-20) * routed_scaling_factor
              FF = sum_{e in T(t), e held} gate_e E_e(b) + Shared(b),  E(b) = (silu(b W1) * (b W3)) W2

YaRN (``rope_scaling.type: yarn``, as DeepSeek-V3's modelling code builds
it): pair ``i`` of the ``dr/2`` has the frequency ``theta^(-2i/dr)`` where
the linear ramp between the two correction dimensions (the pairs that make
``beta_fast`` and ``beta_slow`` turns over ``original_max_position_embeddings``,
floor and ceiling; equal: the upper + 0.001) reads 0, that over ``factor``
where it reads 1, and their blend between; cosines and sines are
multiplied by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
and the softmax scale by ``m^2``, ``m = mscale(factor, mscale_all_dim)``,
``mscale(f, c) = 0.1 c ln f + 1``. The output is ``rms(x_L; g) W_head``
over the ids this holder has.

No kernel, no grouped product, no batch: attention as a softmax over a
block of queries' whole rows, head by head, the key's rotary part written
out beside each head's own (the plain way); the experts as a loop over the
held ones with a 0/1 membership in the gate; the shared expert as one more
gated MLP. The holder's SHARE is the reference's too: it is given the held
experts' weights (``experts_held``) and the vocabulary slice, and what the
absent experts would add is left out. Float32 at ``Precision.HIGHEST``;
``compute=jnp.bfloat16`` gives the precision yardstick as ``keye_decoder``
describes it, whose ``_mm`` (the rounding behind
``lax.optimization_barrier``), ``rms``, ``rotate`` and ``dense_mlp`` are
used here: the same few lines, and no code of the package under test.

Departures from the published code, each the program's own too: rotary
pairs are components ``(i, i + dr/2)`` (DeepSeek's code de-interleaves
adjacent pairs into that form first; with random weights a fixed
permutation of ``W_uq``'s and ``W_dkv``'s rotary columns); no group-limited
routing (``n_group`` = ``topk_group`` = 1: it does not bind).

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls that show that ``correct`` can tell one
(``tests/kimi_k2_controls.py``): ``turn_key`` (False: ``k_r`` left
unturned), ``mscale`` (False: the scale without ``m^2``), ``yarn`` (False:
plain rotary frequencies), ``kv_norm`` (False: ``c_kv`` not normed),
``shared`` (False: the shared expert left out), ``scoring``
(``"softmax"``), ``select_bias`` (False)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.keye_decoder import _mm, dense_mlp, rms, rotate
from benchmark.reference.lfm2_decoder import patches_of  # the same frames, cut the same way


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping
    (DeepSeek-V3's Hugging Face keys), apart from the program's."""
    held = int(cfg["n_routed_experts"])
    m = {
        "H": int(cfg["num_attention_heads"]), "rq": int(cfg["q_lora_rank"]),
        "rkv": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
        "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "rope_scaling": dict(cfg["rope_scaling"]), "layers": int(cfg["num_hidden_layers"]),
        "n_dense": int(cfg["first_k_dense_replace"]),
        "E": int(cfg.get("router_experts", held)), "k_e": int(cfg["num_experts_per_tok"]),
        "experts_held": tuple(cfg.get("experts_held", (0, held))),
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "scale": float(cfg["routed_scaling_factor"]), "n_shared": int(cfg["n_shared_experts"]),
        "scoring": str(cfg["scoring_func"]), "select_bias": cfg["topk_method"] == "noaux_tc",
        "turn_key": True, "mscale": True, "yarn": True, "kv_norm": True, "shared": True,
    }
    if m["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {m['rope_scaling']['type']!r} is not written here")
    m.update(fault)
    return m


def _get_mscale(factor: float, coefficient: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * coefficient * np.log(factor) + 1.0


def yarn_inv_freq(m) -> np.ndarray:
    """``[dr/2]`` frequencies, float64."""
    rs, dim, base = m["rope_scaling"], m["dr"], m["theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not m["yarn"]:  # the fault: plain rotary
        return extra
    inter = extra / float(rs["factor"])

    def correction_dim(turns):
        return dim * np.log(float(rs["original_max_position_embeddings"]) / (turns * 2 * np.pi)) \
            / (2 * np.log(base))

    low = max(int(np.floor(correction_dim(float(rs["beta_fast"])))), 0)
    high = min(int(np.ceil(correction_dim(float(rs["beta_slow"])))), dim - 1)
    top = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (top - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def latent_attention(p, a, m, compute, block):
    """MLA from the normed input ``a [T, d]``, a block of queries at a time."""
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
    c_kv, k_r = down[:, :m["rkv"]], down[:, None, m["rkv"]:]
    if m["kv_norm"]:
        c_kv = rms(c_kv, p["kv_a_norm"], eps)
    if m["turn_key"]:
        k_r = rotate(k_r, ang) * turned
    kv = _mm(c_kv, p["wkv_b"], compute).reshape(t, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (t, H, dr))], axis=-1)
    v = kv[..., dn:]

    def block_out(t0):
        open_ = jnp.arange(t)[None, :] <= (t0 + jnp.arange(block))[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, t0, block)
        out = []
        for h in range(H):
            logit = _mm(qb[:, h], k[:, h].T, compute) * scale
            prob = jax.nn.softmax(jnp.where(open_, logit, -jnp.inf), axis=-1)
            out.append(_mm(prob, v[:, h], compute))
        return jnp.concatenate(out, axis=-1)

    o = jax.lax.map(block_out, jnp.arange(0, t, block))
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
    rank = jnp.argsort(jnp.argsort(-(s + by), axis=-1, stable=True), axis=-1)
    chosen = rank < m["k_e"]  # equal scores: the lower index first
    gate = s * chosen
    if m["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * m["scale"]

    def one(e, y):
        h = jax.nn.silu(_mm(b, p["w_gate"][e], compute)) * _mm(b, p["w_up"][e], compute)
        g = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1, keepdims=True)
        return y + g * _mm(h, p["w_down"][e], compute)

    return jax.lax.fori_loop(0, count, one, jnp.zeros(b.shape, jnp.float32)), chosen


def shared_expert(p, b, compute):
    """Every token's, ungated: one gated MLP ``n_shared_experts`` experts wide."""
    return dense_mlp({"w_gate": p["shared_gate"], "w_up": p["shared_up"],
                      "w_down": p["shared_down"]}, b, compute)


def kinds(m) -> list:
    """Each layer's kind: is its feed-forward dense?"""
    return [i < m["n_dense"] for i in range(m["layers"])]


def layer(p, x, dense, m, compute=jnp.float32, block=128):
    """One layer (``dense``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x'``."""
    x = x + latent_attention(p, rms(x, p["norm1"], m["eps"]), m, compute, block)
    b = rms(x, p["norm2"], m["eps"])
    if dense:
        return x + dense_mlp(p, b, compute)
    y = experts(p, b, m, compute)[0]
    if m["n_shared"] and m["shared"]:
        y = y + shared_expert(p, b, compute)
    return x + y


def embed(params, patches, prompt_ids, compute=jnp.float32):
    return jnp.concatenate([
        _mm(patches.astype(jnp.float32), params["patch"], compute),
        params["embed"][prompt_ids].astype(jnp.float32),
    ])


def logits_of(params, x, m, compute=jnp.float32):
    """The final norm, then the head's columns this holder has (untied)."""
    return _mm(rms(x, params["norm"], m["eps"]), params["head"], compute)


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``."""
    x = embed(params, patches, prompt_ids, compute)
    for p, dense in zip(params["layers"], kinds(m)):
        x = layer(p, x, dense, m, compute, block)
    return x
