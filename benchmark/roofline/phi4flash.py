"""Operations and bytes Phi-4-mini-flash-reasoning's reader NEEDS, from its
shapes alone, in ``roofline/calib.py``'s sense: the algorithm's least, not
what a kernel happens to compute. bf16 operands (2 bytes). The schedule is the
published rule over the layer's index (``reference/phi4flash_decoder.kinds``
has it again, apart): a Mamba-1 layer at every even index up to ``L/2``,
differential attention under the window at every odd one below it, the full
layer at ``L/2 + 1``, then gated memory units and cross attention in turn."""

from benchmark.roofline.decoder import selected_pairs


def selective_scan(batch: int, tokens: int, hidden: int, expand: int, state: int) -> dict:
    """ONE layer's selective scan as the RECURRENCE has it (it knows no chunk
    and no tile): per token, channel and state SEVEN operations — the
    exponent's product, the exponential, the decay times the state, ``(Delta
    u) B``, the addition, ``C h`` and the sum over the state — none of them the
    matrix unit's; ``u``, ``z`` and the output moved once at 2 bytes, the
    step's float32 pre-activation at 4, ``B`` and ``C`` at 2 each. ON PAPER
    BYTES BOUND IT (at 5,120 channels over a state of 16, a frame of 8,704
    tokens: 4.99 G operations and 446 MB, 0.025 ms at the matrix unit's peak
    against 0.545 ms) and the share reads LOW: what bounds this kernel are the
    vector and transcendental units (an exponential an element), and
    ``peaks.json`` has no peak for either. The share is kept as the
    yardstick's, not as a distance from a roofline the chip could reach."""
    rows, channels = batch * tokens, expand * hidden
    return {"flops": float(7 * state * channels * rows),
            "bytes": float(rows * (3 * 2 * channels + 4 * channels + 2 * 2 * state))}


def diff_attention(batch: int, tokens: int, window: int, heads: int, kv_heads: int,
                   hidden: int) -> dict:
    """ONE CALL of the batched causal kernel, which is ONE HALF of a layer's
    differential attention (a layer makes two: the pairs' first half-heads,
    then their second), over the band's pairs only (``sum_t min(t + 1,
    window)`` a sequence; ``window`` 0: every causal pair): a half-head's
    score of ``head_dim`` and its weighted sum of the pair's ``2 * head_dim``
    values per pair, 384 FLOPs at heads of 64, in each of the call's ``heads
    / 2`` half-heads; the half's queries and keys, the pairs' values and the
    half's output (``heads / 2`` pairs of ``2 * head_dim``) moved once."""
    head_dim = hidden // heads
    pairs = selected_pairs(tokens, window or tokens)
    moved = 2 * batch * tokens * head_dim * (heads // 2 + kv_heads // 2 + kv_heads + heads)
    return {"flops": float(6 * head_dim * (heads // 2) * batch * pairs), "bytes": float(moved)}


def conv_silu_taps(batch: int, tokens: int, hidden: int, expand: int, taps: int) -> dict:
    """ONE Mamba layer's causal depthwise convolution with its bias and SiLU
    over ``expand * hidden`` channels: a multiplication and an addition a tap
    and element, the bias, and the SiLU's four (an exponential among them);
    read once, written once at 2 bytes: bound by bytes."""
    elements = batch * tokens * expand * hidden
    return {"flops": float((2 * taps + 5) * elements), "bytes": float(2 * 2 * elements)}


def scans_vector_ops(batch: int, tokens: int, hidden: int, layers: int, expand: int,
                     state: int) -> float:
    """The vector work of a step's scans, stated APART: it is no matrix
    product, and :func:`step` does not add it to the model's FLOPs."""
    return (layers // 4 + 1) * selective_scan(batch, tokens, hidden, expand, state)["flops"]


def step(batch: int, tokens: int, hidden: int, layers: int, dense_width: int, heads: int,
         kv_heads: int, window: int, expand: int, state: int, taps: int, dt_rank: int, vocab: int,
         prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader (``batch`` frames of
    ``tokens`` tokens, then each frame's next token's logits over the whole
    vocabulary), every matrix product AT THE ROWS IT RUNS in the served step:
    layers 0 .. ``L/2`` on all rows (a Mamba layer's ``W_in``, ``W_x``,
    ``W_dt``, ``W_out`` and its taps; a windowed layer's ``W_qkv``, ``W_o`` and
    the BAND's pairs; the MLP of each), layer ``L/2 + 1``'s ``W_k`` and ``W_v``
    on all rows, and on each frame's LAST row alone its ``W_q``, its scores
    over the frame's keys, ``W_o`` and its MLP, the memory units' and the
    cross layers' products and scores, and the head. The scans' vector work
    (:func:`scans_vector_ops`) is NOT added: it is no matrix product. Nothing
    recomputed. Bytes: not counted (a whole step has no one roofline), 0."""
    rows, served, half = batch * tokens, batch, layers // 2
    head_dim, channels = hidden // heads, expand * hidden
    q, kv = heads * head_dim, 2 * kv_heads * head_dim
    mlp = 3 * 2 * hidden * dense_width
    mamba = (2 * hidden * 2 * channels + 2 * channels * (dt_rank + 2 * state)
             + 2 * dt_rank * channels + 2 * channels * hidden + 2 * taps * channels)
    band = 2 * diff_attention(batch, tokens, window, heads, kv_heads, hidden)["flops"]  # both halves
    row_scores = 6 * head_dim * heads * tokens  # one served row against all its frame's keys
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * served * hidden * vocab
    for i in range(layers):
        if i % 2 == 0 and i <= half:
            total += rows * (mamba + mlp)
        elif i < half:
            total += rows * (2 * hidden * (q + kv) + 2 * q * hidden + mlp) + band
        elif i == half + 1:
            total += rows * 2 * hidden * kv + served * (4 * hidden * q + row_scores + mlp)
        elif i % 2 == 0:
            total += served * (4 * hidden * channels + mlp)
        else:
            total += served * (4 * hidden * q + row_scores + mlp)
    return {"flops": float(total), "bytes": 0.0}
