"""Operations and bytes the decoder's kernels NEED for one call, from
their shapes alone, in ``roofline/calib.py``'s sense: the algorithm's
least, not what a kernel happens to compute. bf16 operands (2 bytes)."""


def causal_pairs(tokens: int) -> int:
    return tokens * (tokens + 1) // 2


def selected_pairs(tokens: int, topk: int) -> int:
    """Sum over queries of ``min(t + 1, topk)``."""
    full = max(tokens - topk, 0)
    head = min(tokens, topk)
    return head * (head + 1) // 2 + full * topk


def select_keys(tokens: int, heads: int, head_dim: int) -> dict:
    """Index scores of every causal pair: ``heads`` dot products of
    ``head_dim`` a pair. The threshold search over those scores is the
    kernel's own way to ``Sel`` and counts as nothing needed; the bytes are
    the index vectors read once and one mask byte written a pair."""
    pairs = causal_pairs(tokens)
    moved = 2 * tokens * head_dim * (heads + 1) + 4 * tokens * heads + pairs
    return {"flops": float(2 * heads * head_dim * pairs), "bytes": float(moved)}


def selected_attention(tokens: int, heads: int, kv_heads: int, head_dim: int, topk: int) -> dict:
    """Attention over the SELECTED pairs only: a score and a weighted sum
    of ``head_dim`` each, per pair and query head (``4 * head_dim``
    FLOPs). A masked-dense kernel does the causal pairs' work and shows
    the difference as a low share, as it should."""
    pairs = selected_pairs(tokens, topk)
    moved = 2 * tokens * head_dim * (2 * heads + 2 * kv_heads)  # q, o, k, v once
    return {"flops": float(4 * head_dim * heads * pairs), "bytes": float(moved)}


def grouped_product(tokens: int, per_token: int, hidden: int, width: int, held: int) -> dict:
    """ONE of an expert layer's three grouped products (gate, up, down:
    the same count each): every token slot's row against its expert's
    ``hidden x width`` matrix, the held experts' matrices read once, the
    rows read and written once."""
    rows = tokens * per_token
    moved = 2 * (held * hidden * width + rows * (hidden + width))
    return {"flops": float(2 * rows * hidden * width), "bytes": float(moved)}
