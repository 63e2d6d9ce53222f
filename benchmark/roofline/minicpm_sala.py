"""Operations and bytes MiniCPM-SALA's reader NEEDS, from its shapes alone, in
``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes). One sequence a step (a selection
is one sequence's)."""

import numpy as np

from benchmark.roofline.decoder import causal_pairs

SPARSE = "minicpm4"  # mixer_types, as the file spells them


def selected_pairs(tokens: int, block: int, topk: int) -> int:
    """The (query, key) pairs ONE key head's selection keeps: a query at ``t``
    keeps ``min(t // block + 1, topk)`` blocks, its own among them (the latest
    blocks are forced), and of that one the keys up to ``t``."""
    t = np.arange(tokens)
    return int(np.sum((np.minimum(t // block + 1, topk) - 1) * block + t % block + 1))


def select_blocks(tokens: int, heads: int, kv_heads: int, head_dim: int, stride: int,
                  block: int) -> dict:
    """ONE layer's selection: every query head's score against the pooled
    keys that END at or before the query (one every ``stride`` keys: ``t /
    stride`` of them or so, ``2 * head_dim`` FLOPs each). The softmax over
    them, the group's sum, the block's maximum and the threshold search are
    the kernel's own way to ``Sel`` and count as nothing needed; the bytes are
    q and k read once and a flag a (key head, query, block) written."""
    seen = causal_pairs(tokens) // stride  # sum over t of the pooled keys a query sees, or so
    moved = 2 * tokens * head_dim * (heads + kv_heads) + kv_heads * tokens * (tokens // block)
    return {"flops": float(2 * head_dim * heads * seen), "bytes": float(moved)}


def sparse_attention(tokens: int, heads: int, kv_heads: int, head_dim: int, block: int,
                     topk: int) -> dict:
    """ONE layer's attention over the SELECTED pairs only, whatever tile or
    mask form runs them: a score and a weighted sum of ``head_dim`` each, per
    pair and query head (``4 * head_dim`` FLOPs). A masked-dense kernel does
    the causal pairs' work and shows the difference as a low share."""
    pairs = selected_pairs(tokens, block, topk)
    moved = 2 * tokens * head_dim * (2 * heads + 2 * kv_heads)  # q, o, k, v once
    return {"flops": float(4 * head_dim * heads * pairs), "bytes": float(moved)}


def lightning_attention(tokens: int, heads: int, head_dim: int) -> dict:
    """ONE layer's linear attention with a fixed decay, as the RECURRENCE has
    it (it knows no chunk: a change of the chunk or of the heads a grid step
    moves the share and not its yardstick): per token and head ``2 *
    head_dim^2`` for the rank-one update and ``2 * head_dim^2`` for ``S^T q``
    (the decay of the state is the kernel's once a chunk, not a token); q and
    k read float32 as their products wrote them (4 bytes), v, the gate's z and
    the output at 2."""
    return {"flops": float(4 * head_dim * head_dim * heads * tokens),
            "bytes": float(tokens * heads * head_dim * (2 * 4 + 3 * 2))}


def step(tokens: int, hidden: int, mixers: list, dense_width: int, heads: int, kv_heads: int,
         head_dim: int, linear_heads: int, linear_dim: int, stride: int, block: int, topk: int,
         vocab: int, prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader (one frame of
    ``tokens`` tokens through every layer, then its next token's logits over
    the whole vocabulary): every matrix product the mathematics has at the
    PUBLISHED widths (a sparse layer's ``W_q``, ``W_G``, ``W_o`` at ``heads *
    head_dim`` and ``W_k``, ``W_v`` at ``kv_heads * head_dim``; a linear
    layer's five at ``linear_heads * linear_dim``; the dense MLP of every
    layer), the SELECTED pairs, the pooled scores, the recurrence, nothing
    recomputed. Bytes: not counted (a whole step has no one roofline), 0."""
    wide, narrow, linear = heads * head_dim, kv_heads * head_dim, linear_heads * linear_dim
    sparse = (2 * tokens * hidden * (3 * wide + 2 * narrow)
              + sparse_attention(tokens, heads, kv_heads, head_dim, block, topk)["flops"]
              + select_blocks(tokens, heads, kv_heads, head_dim, stride, block)["flops"])
    lightning = (2 * tokens * hidden * 5 * linear
                 + lightning_attention(tokens, linear_heads, linear_dim)["flops"])
    dense = 3 * 2 * tokens * hidden * dense_width
    total = 2 * (tokens - prompt) * patch * patch * hidden + 2 * hidden * vocab
    for mixer in mixers:
        total += (sparse if mixer == SPARSE else lightning) + dense
    return {"flops": float(total), "bytes": 0.0}
