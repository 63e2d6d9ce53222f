"""Operations and bytes Olmo-Hybrid's reader NEEDS, from its shapes alone, in
``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes). The full layers' causal
attention is ``roofline/lfm2.causal_attention``'s (granite's too), as it
stands: every head its own keys, 128 wide."""

from benchmark.roofline import lfm2

GDN = "linear_attention"  # layer_types, as the file spells them


def delta_rule(batch: int, tokens: int, heads: int, key_dim: int, value_dim: int) -> dict:
    """ONE layer's gated delta rule with ONE decay a head over a ``[key_dim,
    value_dim]`` state, as the RECURRENCE has it (it knows no chunk and no
    lane: a change of the chunk, of the heads a grid step or of how 96-wide
    heads reach the lanes moves the share and not its yardstick): per token
    and head ``key_dim * value_dim`` multiplications for the decay of the
    state and ``2 * key_dim * value_dim`` each for ``k^T S``, the rank-one
    update and ``S^T q``; ``q``, ``k`` (``key_dim`` wide), ``v``, the gate's
    ``z`` and the output (``value_dim`` wide) moved once at 2 bytes, and two
    float32 scalars a token and head (the decay's pre-activation, the step
    size)."""
    rows = batch * tokens
    return {"flops": float(7 * key_dim * value_dim * heads * rows),
            "bytes": float(rows * heads * (2 * 2 * key_dim + 3 * 2 * value_dim + 2 * 4))}


def causal_attention(batch: int, tokens: int, hidden: int, heads: int, kv_heads: int) -> dict:
    """ONE full layer's attention over the causal pairs, a head ``hidden /
    heads`` wide (granite's count, at 30 / 30 x 128 here)."""
    return lfm2.causal_attention(batch, tokens, hidden, heads, kv_heads)


def step(batch: int, tokens: int, hidden: int, layer_types: list, dense_width: int, heads: int,
         kv_heads: int, linear_heads: int, key_dim: int, value_dim: int, taps: int, vocab: int,
         prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader (``batch`` frames of
    ``tokens`` tokens through every layer, then each frame's next token's
    logits over the whole vocabulary): every matrix product the mathematics
    has at the PUBLISHED widths (a linear layer's ``W_q``, ``W_k`` onto
    ``linear_heads * key_dim``, ``W_v``, the gate's ``W_g`` and ``W_o`` at
    ``linear_heads * value_dim``, the decay's and the step size's ``hidden x
    linear_heads``; the three convolutions' taps; the recurrence by
    :func:`delta_rule`'s count; a full layer's four projections, attention
    over the causal pairs only; the dense MLP of every layer), nothing
    recomputed and no zero column counted. Bytes: not counted (a whole step
    has no one roofline), 0."""
    rows = batch * tokens
    keys, values = linear_heads * key_dim, linear_heads * value_dim
    linear = (2 * rows * hidden * (2 * keys + 3 * values + 2 * linear_heads)
              + 2 * taps * rows * (2 * keys + values)
              + delta_rule(batch, tokens, linear_heads, key_dim, value_dim)["flops"])
    width = hidden // heads
    attention = (2 * rows * hidden * width * (2 * heads + 2 * kv_heads)
                 + causal_attention(batch, tokens, hidden, heads, kv_heads)["flops"])
    dense = 3 * 2 * rows * hidden * dense_width
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * batch * hidden * vocab
    for op in layer_types:
        total += (linear if op == GDN else attention) + dense
    return {"flops": float(total), "bytes": 0.0}
