"""Operations and bytes DeepSeek-V3.2's reader NEEDS, from its shapes
alone, in ``roofline/calib.py``'s sense: the algorithm's least, not what a
kernel happens to compute. bf16 operands (2 bytes). The index scores are
``roofline/decoder.select_keys``' and the held experts' products
``roofline/kimi_k2.held_products``', as they stand."""

from benchmark.roofline import decoder, kimi_k2


def sparse_latent_attention(tokens: int, heads: int, nope: int, rope: int, value: int,
                            topk: int) -> dict:
    """Latent attention over the SELECTED pairs only, one sequence: a
    score over ``nope + rope`` and a weighted sum of ``value`` per selected
    pair and head (``2 * (nope + rope + value)`` FLOPs; the absorbed form
    would do more, and is not the least), whichever form the kernel takes:
    a masked-dense kernel does the causal pairs' work and shows the
    difference as a low share, as it should. Each head's q (both parts),
    k_nope, v and o moved once, the ONE rotary key once, and one mask byte
    read a causal pair."""
    pairs = decoder.selected_pairs(tokens, topk)
    moved = (2 * tokens * (heads * (2 * nope + rope + 2 * value) + rope)
             + decoder.causal_pairs(tokens))
    return {"flops": float(2 * (nope + rope + value) * heads * pairs), "bytes": float(moved)}


def step(tokens: int, hidden: int, layers: int, dense_layers: int, dense_width: int,
         expert_width: int, experts: int, held: int, per_token: int, shared: int, heads: int,
         q_rank: int, kv_rank: int, nope: int, rope: int, value: int, index_heads: int,
         index_dim: int, topk: int, vocab: int, prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader on this holder (one
    frame of ``tokens`` tokens through every layer, then its next token's
    logits over the vocabulary slice): ``kimi_k2.step``'s count (every
    matrix product the mathematics has, the routed experts over the EVEN
    share of slots that falls to the ``held`` of ``experts``) with
    attention over the SELECTED pairs, plus the indexer: its three
    projections and the index scores of every causal pair. Bytes: not
    counted, 0."""
    base = kimi_k2.step(1, tokens, hidden, layers, dense_layers, dense_width, expert_width,
                        experts, held, per_token, shared, heads, q_rank, kv_rank, nope, rope,
                        value, vocab, prompt, patch)["flops"]
    causal = kimi_k2.latent_attention(1, tokens, heads, nope, rope, value)["flops"]
    selected = sparse_latent_attention(tokens, heads, nope, rope, value, topk)["flops"]
    indexer = (2 * tokens * (q_rank * index_heads * index_dim + hidden * index_dim
                             + hidden * index_heads)
               + decoder.select_keys(tokens, index_heads, index_dim)["flops"])
    return {"flops": float(base + layers * (selected - causal + indexer)), "bytes": 0.0}
