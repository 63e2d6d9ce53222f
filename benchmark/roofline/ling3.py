"""Operations and bytes Ling-3.0's reader NEEDS, from its shapes alone, in
``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes). The latent layer's attention is
``roofline/kimi_k2.latent_attention``'s and the held experts' products
``roofline/kimi_k2.held_products``', as they stand."""

from benchmark.roofline import kimi_k2

KDA = "linear_attention"  # layer_types, as the file spells them


def delta_rule(batch: int, tokens: int, heads: int, head_dim: int) -> dict:
    """ONE layer's gated delta rule with a decay per channel, as the
    RECURRENCE has it (it knows no chunk: a change of the chunk or of the
    form moves the share and not its yardstick): per token and head
    ``head_dim^2`` multiplications for the decay of the state and ``2 *
    head_dim^2`` each for ``k^T S``, the rank-one update and ``S^T q``;
    ``q``, ``k``, ``v``, the log-decay and the output moved once (2 bytes
    each: the least; the kernel moves the decay's pre-activation in float32)
    and one float32 step size a token and head."""
    rows = batch * tokens
    return {"flops": float(7 * head_dim * head_dim * heads * rows),
            "bytes": float(rows * heads * (5 * 2 * head_dim + 4))}


def step(batch: int, tokens: int, hidden: int, layer_types: list, dense_layers: int,
         dense_width: int, expert_width: int, experts: int, held: int, per_token: int,
         shared: int, heads: int, head_dim: int, taps: int, kv_rank: int, nope: int, rope: int,
         value: int, vocab: int, prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader on this holder
    (``batch`` frames of ``tokens`` tokens through every layer, then each
    frame's next token's logits over the vocabulary slice): every matrix
    product the mathematics has (a linear layer's six ``hidden x heads *
    head_dim`` products and its step sizes, the convolutions' taps, the
    recurrence by :func:`delta_rule`'s count; the latent layer's full-rank
    query, its latent paths, its gate and ``W_o``, attention over the causal
    pairs only), the routed experts over the EVEN share of slots that falls
    to the ``held`` of ``experts``, nothing recomputed. Bytes: not counted, 0."""
    rows = batch * tokens
    wide = heads * head_dim
    linear = (2 * rows * hidden * (6 * wide + heads) + 2 * taps * rows * 3 * wide
              + delta_rule(batch, tokens, heads, head_dim)["flops"])
    latent = (2 * rows * (hidden * heads * (nope + rope) + hidden * (kv_rank + rope)
                          + kv_rank * heads * (nope + value) + heads * value * hidden
                          + hidden * heads)
              + kimi_k2.latent_attention(batch, tokens, heads, nope, rope, value)["flops"])
    dense = 3 * 2 * rows * hidden * dense_width
    sparse = (3 * 2 * rows * hidden * expert_width * (shared + per_token * held / experts)
              + 2 * rows * hidden * experts)
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * batch * hidden * vocab
    for i, op in enumerate(layer_types):
        total += (linear if op == KDA else latent) + (dense if i < dense_layers else sparse)
    return {"flops": float(total), "bytes": 0.0}
