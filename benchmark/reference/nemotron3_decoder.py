"""Plain float32 forward pass of Nemotron-3-Nano-30B-A3B's hybrid trunk
(``nemotron_h``) as the frame reader runs it on one chip of the 2 that share
each layer: the reference for ``nemotron3_nano_prefill_epix10k2m``.

Sizes from the model's public ``config.json``; what it does not fix is listed
in the configuration file's ``assumed``. One sequence of ``T`` tokens, ``x
[T, d]``; with ``rms(u; g) = u / sqrt(mean(u^2) + eps) * g`` EVERY layer is
ONE block, ``x' = x + Mixer(rms(x; g))``, its mixer one letter of
``hybrid_override_pattern`` (the first ``num_hidden_layers`` letters):

    M  mamba(a):   [z | xBC | dt] = a W_in                      (no bias)
                   xBC <- silu(c + b_c),  c[t] = sum_j w[:, j] xBC[t - 3 + j]   (zeros before the sequence)
                   [x | B | C] = xBC     x -> [T, H, P]     B, C -> [T, G, N]: one a GROUP of H/G heads
                   Delta_t = softplus(dt_t + dt_bias) [H]      A = -exp(A_log) [H]      g(h) = h // (H/G)
                   H_t[h] = exp(Delta_t[h] A[h]) H_{t-1}[h] + Delta_t[h] x_t[h] (x) B_t[g(h)]   H_0 = 0, [P, N]
                   y_t[h] = H_t[h] C_t[g(h)] + D[h] x_t[h]
                   mamba = rms_by_group(y * silu(z); gain) W_out   (the gate BEFORE the norm; the norm
                                                                    over each group's H*P/G channels)
    *  attention(a): q = a W_q -> [T, 32, 128]   k, v = a W_k, a W_v -> [T, 2, 128]   no bias, no norm,
                   NO rotary; o[t, h] = sum_{s<=t} softmax_s(q[t,h] . k[s,h//16] * 128^(-1/2)) v[s,h//16]
                   attention = concat_h(o) W_o
    E  experts(a): s = sigmoid(a W_r) [T, 128] float32; the k = 6 experts are the top 6 of s + bias
                   (n_group 1: no group limit); weight_i = s_i / (sum of the six s + 1e-20) * 2.5
                   experts = sum_i weight_i relu(a W_up,i)^2 W_down,i  over the chosen experts HELD here
                           + relu(a V_up)^2 V_down                     (the shared expert, every token's)
    -  mlp(a):     relu(a W_up)^2 W_down                               (no layer of this model)

then ``rms(x; g_f)`` and the untied head, over the vocabulary slice held.
UNGATED: an expert is two matrices, not three (``mlp_hidden_act: relu2``).

No kernel, no chunk, no batch, no sort: the recurrence TOKEN BY TOKEN
(``lax.scan`` over ``t`` with the ``[H, P, N]`` float32 state, ``B_t`` and
``C_t`` indexed by the head's group), the convolution as four shifted sums,
attention as a masked softmax over a block of queries' whole rows
(``granite_decoder.attention``, which is that published module's too: same
projections, no positions), every HELD expert as a dense product over all
tokens weighted by its gate column. Float32 at ``Precision.HIGHEST``;
``compute=jnp.bfloat16`` gives the precision yardstick as ``keye_decoder``
describes it (the operands of every product rounded, sums and the state
float32). No code of the package under test. Departures from the published
module (``modeling_nemotron_h.py``), none in the mathematics: the step is
not clamped (``time_step_limit`` (0, inf), its default); the chosen experts
that live on the OTHER chip of the pair are left out (the program's share:
``experts_held``), the router keeping all its outputs; the published
module's chunked scan (``chunk_size``) is its kernel's tile.

``sizes(cfg, **fault)`` can put a fault in the mathematics' place, for the
controls (``tests/nemotron3_controls.py``): ``act`` (``"relu"``, or
``"gated_silu"``: ``silu(u) * u``), ``one_bc`` (True: group 0's B and C for
all heads), ``norm_groups`` (1: the norm over all channels), ``gate_first``
(False), ``carry`` (``n``: the state dropped every ``n`` tokens), ``rotary``
(True: a plain rotary at ``rope_theta`` in the attention layers),
``scoring`` (``"softmax"``), ``select_bias`` (False), ``scale``, ``shared``
(False), ``skip``, ``dt_bias``, ``conv_bias`` (False each)."""

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v32_decoder import chosen_experts
from benchmark.reference.granite_decoder import attention, conv_silu
from benchmark.reference.keye_decoder import _mm, rms
from benchmark.reference.kimi_k2_decoder import embed, logits_of  # noqa: F401 — the adapter reads them here
from benchmark.reference.lfm2_decoder import patches_of  # noqa: F401 — and this
from benchmark.reference.ling3_decoder import _rounded

MAMBA, ATTENTION, EXPERTS, DENSE = "M", "*", "E", "-"  # the pattern's letters


def sizes(cfg, **fault) -> dict:
    """The reference's own reading of the configuration mapping
    (Nemotron-H's Hugging Face keys), apart from the program's."""
    layers = int(cfg["num_hidden_layers"])
    held = int(cfg["n_routed_experts"])
    m = {
        "H": int(cfg["num_attention_heads"]), "G": int(cfg["num_key_value_heads"]),
        "dh": int(cfg["head_dim"]), "eps": float(cfg["layer_norm_epsilon"]),
        "theta": float(cfg["rope_theta"]), "pattern": str(cfg["hybrid_override_pattern"])[:layers],
        "Hs": int(cfg["mamba_num_heads"]), "P": int(cfg["mamba_head_dim"]),
        "N": int(cfg["ssm_state_size"]), "Gs": int(cfg["n_groups"]), "taps": int(cfg["conv_kernel"]),
        "conv_bias": bool(cfg["use_conv_bias"]), "attn_scale": int(cfg["head_dim"]) ** -0.5,
        "E": int(cfg.get("router_experts", held)), "k_e": int(cfg["num_experts_per_tok"]),
        "experts_held": tuple(cfg.get("experts_held", (0, held))),
        "n_group": int(cfg["n_group"]), "topk_group": int(cfg["topk_group"]), "group_limit": True,
        "norm_topk_prob": bool(cfg["norm_topk_prob"]), "scale": float(cfg["routed_scaling_factor"]),
        "act": str(cfg["mlp_hidden_act"]),
        # what the module does and a control undoes
        "rotary": False, "scoring": "sigmoid", "select_bias": True, "shared": True, "state": "float32",
        "carry": 0, "skip": True, "dt_bias": True, "gate_first": True, "one_bc": False,
    }
    m["norm_groups"] = m["Gs"]
    if (len(m["pattern"]) != layers or set(m["pattern"]) - {MAMBA, ATTENTION, EXPERTS, DENSE}
            or m["act"] != "relu2" or m["Hs"] % m["Gs"] or cfg.get("mamba_proj_bias")
            or cfg.get("attention_bias") or cfg.get("mlp_bias")):
        raise ValueError("only Nemotron-H's four blocks, ungated relu2 MLPs and no bias in a "
                         "product are written here")
    m.update(fault)
    return m


def scan(x, b, c, step, a, m, compute):
    """The recurrence, token by token: ``x [T, H, P]``, ``b, c [T, G, N]``,
    ``step [T, H]``, ``a [H]`` -> ``y [T, H, P]`` (without the skip); head
    ``h`` reads ``b[:, h // (H/G)]`` and ``c`` alike."""
    t, heads, p = x.shape
    group = jnp.arange(heads) // (heads // b.shape[1])  # g(h)
    if m["one_bc"]:  # the fault: every head reads group 0's
        group = jnp.zeros_like(group)
    hi = jax.lax.Precision.HIGHEST

    def one(state, u):
        x, b, c, d, i = u
        if m["carry"]:  # the fault: nothing crosses a boundary of `carry` tokens
            state = jnp.where(i % m["carry"] == 0, 0.0, state)
        state = jnp.exp(d * a)[:, None, None] * state + jnp.einsum(
            "hp,hn->hpn", _rounded(d[:, None] * x, compute), _rounded(b[group], compute), precision=hi)
        return state, jnp.einsum("hpn,hn->hp", _rounded(state, compute), _rounded(c[group], compute),
                                 precision=hi)

    _, y = jax.lax.scan(one, jnp.zeros((heads, p, b.shape[2]), jnp.float32),
                        (x, b, c, step, jnp.arange(t)))
    return y


def mamba(p, a, m, compute):
    """The state-space mixer (NemotronHMamba2Mixer) from the normed input ``a [T, d]``."""
    t, heads, width, n, groups = a.shape[0], m["Hs"], m["P"], m["N"], m["Gs"]
    wide = heads * width  # d_inner = mamba_num_heads * mamba_head_dim (`expand` does not set it)
    z, xbc, dt = jnp.split(_mm(a, p["w_in"], compute), [wide, 2 * wide + 2 * groups * n], axis=1)
    xbc = conv_silu(xbc, p["conv_w"], p.get("conv_b"), m)
    x = xbc[:, :wide].reshape(t, heads, width)
    b = xbc[:, wide:wide + groups * n].reshape(t, groups, n)
    c = xbc[:, wide + groups * n:].reshape(t, groups, n)
    if m["dt_bias"]:
        dt = dt + p["dt_bias"].astype(jnp.float32)
    # time_step_limit (0, inf): the step is not clamped
    y = scan(x, b, c, jax.nn.softplus(dt), -jnp.exp(p["a_log"].astype(jnp.float32)), m, compute)
    if m["skip"]:
        y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * x
    y = y.reshape(t, wide)

    def by_group(u):  # MambaRMSNormGated's norm: over each group's channels, one gain [wide]
        u = u.reshape(t, m["norm_groups"], -1)
        u = u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + m["eps"])
        return u.reshape(t, wide) * p["ssm_norm"].astype(jnp.float32)

    if m["gate_first"]:  # norm_before_gate false: the gate BEFORE the norm
        y = by_group(y * jax.nn.silu(z))
    else:  # the fault: the other order
        y = by_group(y) * jax.nn.silu(z)
    return _mm(y, p["w_out"], compute)


def activation(u, m):
    """``mlp_hidden_act``: relu2, ``relu(u)^2``, of the UP product alone."""
    if m["act"] == "relu2":
        return jnp.square(jax.nn.relu(u))
    if m["act"] == "relu":  # the fault: the square left out
        return jax.nn.relu(u)
    return jax.nn.silu(u) * u  # the fault ("gated_silu"): the gated form over the one product there is


def mlp(w_up, w_down, b, m, compute):
    """NemotronHMLP: ``act(b W_up) W_down``, no gate."""
    return _mm(activation(_mm(b, w_up, compute), m), w_down, compute)


def experts(p, b, m, compute):
    """NemotronHMOE's routed part from the normed input ``b [T, d]``: the
    held experts' part of their sum (the other chip's experts left out), and
    each token's expert set ``[T, E]``."""
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

    def one(e, y):  # a dense pass over all tokens, weighted by the expert's gate column (0 where not chosen)
        g = jax.lax.dynamic_index_in_dim(gate, first + e, axis=1, keepdims=True)
        return y + g * mlp(p["w_up"][e], p["w_down"][e], b, m, compute)

    return jax.lax.fori_loop(0, count, one, jnp.zeros(b.shape, jnp.float32)), chosen


def kinds(m) -> list:
    """Each layer's kind: its one block's letter."""
    return list(m["pattern"])


def layer(p, x, kind, m, compute=jnp.float32, block=128):
    """One layer (``kind``: an entry of :func:`kinds`): ``x [T, d]``
    float32 -> ``x + Mixer(rms(x))``, the ONE block it is."""
    if kind == MAMBA:
        return x + mamba(p, rms(x, p["norm1"], m["eps"]), m, compute)
    if kind == ATTENTION:
        return x + attention(p, rms(x, p["norm1"], m["eps"]), m, compute, block)
    b = rms(x, p["norm2"], m["eps"])
    if kind == DENSE:
        return x + mlp(p["w_up"], p["w_down"], b, m, compute)
    y = experts(p, b, m, compute)[0]
    if m["shared"]:
        y = y + mlp(p["shared_up"], p["shared_down"], b, m, compute)
    return x + y


def hidden(params, patches, prompt_ids, m, compute=jnp.float32, block=128):
    """The trunk's output at every token of one sequence ``[T, d]``."""
    x = embed(params, patches, prompt_ids, compute)
    for p, kind in zip(params["layers"], kinds(m)):
        x = layer(p, x, kind, m, compute, block)
    return x
