"""Operations and bytes LFM2's reader NEEDS, from its shapes alone, in
``roofline/calib.py``'s sense: the algorithm's least. bf16 operands (2
bytes). The expert layer's grouped product is ``roofline/decoder.py``'s."""

from benchmark.roofline.decoder import causal_pairs


def causal_attention(batch: int, tokens: int, hidden: int, heads: int, kv_heads: int) -> dict:
    """Plain causal attention of ``batch`` sequences of ``tokens``, heads
    of ``hidden / heads``: a score and a weighted sum of the head's width
    per causal pair and query head (``4 * width`` FLOPs); q, o, k and v
    moved once."""
    width = hidden // heads
    moved = 2 * batch * tokens * width * (2 * heads + 2 * kv_heads)
    return {"flops": float(4 * width * heads * batch * causal_pairs(tokens)),
            "bytes": float(moved)}


def gated_conv_taps(rows: int, hidden: int, taps: int) -> dict:
    """The gates and taps of one gated short convolution: ``[B | C | z]``
    read and ``y`` written once (bf16), two gates and ``taps``
    multiply-adds an element."""
    return {"flops": float(rows * hidden * (2 * taps + 2)), "bytes": float(2 * rows * hidden * 4)}


def step(batch: int, tokens: int, hidden: int, layer_types: list, dense_layers: int,
         dense_width: int, expert_width: int, experts: int, per_token: int, heads: int,
         kv_heads: int, taps: int, vocab: int, prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader (``batch`` frames
    of ``tokens`` tokens each through every layer, then each frame's next
    token's logits): every matrix product the mathematics has, attention
    over the causal pairs only, nothing recomputed. Bytes: not counted (a
    whole step has no one roofline), 0."""
    rows = batch * tokens
    width = hidden // heads
    attention = (2 * rows * hidden * width * (2 * heads + 2 * kv_heads)
                 + causal_attention(batch, tokens, hidden, heads, kv_heads)["flops"])
    conv = 2 * rows * hidden * 4 * hidden + 2 * taps * rows * hidden + 2 * rows * hidden
    dense = 3 * 2 * rows * hidden * dense_width
    sparse = 3 * 2 * rows * per_token * hidden * expert_width + 2 * rows * hidden * experts
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * batch * hidden * vocab
    for i, op in enumerate(layer_types):
        total += (conv if op == "conv" else attention) + (dense if i < dense_layers else sparse)
    return {"flops": float(total), "bytes": 0.0}
