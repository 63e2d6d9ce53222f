"""Operations Ouro's LOOPED reader NEEDS, from its shapes alone, in
``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes). A layer's causal attention is
``roofline/lfm2.causal_attention``'s, as it stands: the loop adds no kernel,
it runs the ones there are ``passes`` times."""

from benchmark.roofline import lfm2


def step(batch: int, tokens: int, hidden: int, layers: int, passes: int, dense_width: int,
         heads: int, kv_heads: int, head_dim: int, vocab: int, prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader (``batch`` frames of
    ``tokens`` tokens through the ONE stack of ``layers`` layers ``passes``
    times over, then each frame's next token's logits over the whole
    vocabulary): a step is ``passes x layers`` layer applications (the
    weights are shared, the work is not), each the four projections,
    attention over the causal pairs only and the dense MLP's three products;
    the exit gate's product at the end of every pass, the head on ``batch``
    rows and the patch embedding; nothing recomputed, the norms not counted.
    Bytes: not counted (a whole step has no one roofline), 0."""
    rows = batch * tokens
    products = (2 * rows * hidden * head_dim * (2 * heads + 2 * kv_heads)
                + 3 * 2 * rows * hidden * dense_width)
    attention = lfm2.causal_attention(batch, tokens, heads * head_dim, heads, kv_heads)["flops"]
    total = (passes * (layers * (products + attention) + 2 * rows * hidden)
             + 2 * batch * hidden * vocab + 2 * batch * (tokens - prompt) * patch * patch * hidden)
    return {"flops": float(total), "bytes": 0.0}
