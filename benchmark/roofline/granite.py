"""Operations and bytes Granite-4.0-H's reader NEEDS, from its shapes alone,
in ``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes). The attention layers' causal
attention is ``roofline/lfm2.causal_attention``'s, as it stands."""

from benchmark.roofline import lfm2

MAMBA = "mamba"  # layer_types, as the file spells them


def ssd_scan(batch: int, tokens: int, heads: int, head_dim: int, state: int) -> dict:
    """ONE layer's selective state-space scan as the RECURRENCE has it (it
    knows no chunk: a change of the chunk, of the form or of the number of
    kernels moves the share and not its yardstick): per token and head
    ``head_dim * state`` multiplications for the state's decay and ``2 *
    head_dim * state`` each for the rank-one update and the read-out; ``x``,
    ``z`` and the output moved once at 2 bytes, ``B`` and ``C`` (one for all
    heads) at 2 bytes and the step's float32 pre-activation, one a head."""
    rows = batch * tokens
    return {"flops": float(5 * head_dim * state * heads * rows),
            "bytes": float(rows * (3 * 2 * heads * head_dim + 2 * 2 * state + 4 * heads))}


def step(batch: int, tokens: int, hidden: int, layer_types: list, dense_width: int, heads: int,
         kv_heads: int, scan_heads: int, scan_head_dim: int, state: int, taps: int, vocab: int,
         prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader (``batch`` frames of
    ``tokens`` tokens through every layer, then each frame's next token's
    logits over the whole vocabulary): every matrix product the mathematics
    has (a state-space layer's ``W_in`` onto ``[z | xBC | dt]`` and its
    ``W_out``, the convolution's taps, the scan by :func:`ssd_scan`'s count;
    an attention layer's four projections, attention over the causal pairs
    only; the dense MLP of every layer), nothing recomputed. Bytes: not
    counted (a whole step has no one roofline), 0."""
    rows = batch * tokens
    wide = scan_heads * scan_head_dim
    conv = wide + 2 * state
    mamba = (2 * rows * hidden * (wide + conv + scan_heads) + 2 * rows * wide * hidden
             + 2 * taps * rows * conv + ssd_scan(batch, tokens, scan_heads, scan_head_dim, state)["flops"])
    width = hidden // heads
    attention = (2 * rows * hidden * width * (2 * heads + 2 * kv_heads)
                 + lfm2.causal_attention(batch, tokens, hidden, heads, kv_heads)["flops"])
    dense = 3 * 2 * rows * hidden * dense_width
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * batch * hidden * vocab
    for op in layer_types:
        total += (mamba if op == MAMBA else attention) + dense
    return {"flops": float(total), "bytes": 0.0}
