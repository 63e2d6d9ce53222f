"""Operations and bytes Nemotron-3-Nano's reader NEEDS, from its shapes
alone, in ``roofline/calib.py``'s sense: the algorithm's least, not what a
kernel happens to compute. bf16 operands (2 bytes). A layer is ONE block, a
letter of ``hybrid_override_pattern`` (its first ``layers`` letters)."""

from benchmark.roofline import granite, lfm2

MAMBA, ATTENTION, EXPERTS, DENSE = "M", "*", "E", "-"  # the pattern's letters


def ssd_scan(batch: int, tokens: int, heads: int, head_dim: int, state: int, groups: int) -> dict:
    """ONE layer's selective state-space scan as the RECURRENCE has it
    (``granite.ssd_scan``'s count: per token and head ``head_dim * state``
    multiplications for the state's decay and ``2 * head_dim * state`` each
    for the rank-one update and the read-out), with ``B`` and ``C`` moved once
    a GROUP of heads (``groups * 2 * state`` values of 2 bytes a token, not
    ``2 * state``); ``x``, ``z`` and the output once at 2 bytes and the step's
    float32 pre-activation, one a head."""
    one = granite.ssd_scan(batch, tokens, heads, head_dim, state)
    return {"flops": one["flops"],
            "bytes": one["bytes"] + float(batch * tokens * (groups - 1) * 2 * 2 * state)}


def causal_attention(batch: int, tokens: int, heads: int, kv_heads: int, head_dim: int) -> dict:
    """ONE attention layer's plain causal attention at a STATED head width
    (``hidden / heads`` is not this model's): ``lfm2.causal_attention``'s
    count at ``heads * head_dim`` in place of the hidden size."""
    return lfm2.causal_attention(batch, tokens, heads * head_dim, heads, kv_heads)


def held_products(tokens: int, per_token: int, hidden: int, width: int, held: int, layers: int,
                  pattern: str, held_share: float) -> dict:
    """ALL the grouped products of one step on a holder of a share of the
    UNGATED experts: TWO an expert layer (up, down; no gate's), over the rows
    whose expert is held, ``held_share`` of the ``tokens * per_token`` slots
    (the program's own count); the held experts' matrices read once a
    product, the rows read and written once. ``call_sites``: the places the
    program calls the kernel from, one a product."""
    rows = tokens * per_token * held_share
    products = 2 * pattern[:layers].count(EXPERTS)
    moved = 2 * (held * hidden * width + rows * (hidden + width))
    return {"flops": float(products * 2 * rows * hidden * width), "bytes": float(products * moved),
            "call_sites": products}


def step(batch: int, tokens: int, hidden: int, layers: int, pattern: str, heads: int,
         kv_heads: int, head_dim: int, scan_heads: int, scan_head_dim: int, state: int,
         groups: int, taps: int, expert_width: int, shared_width: int, dense_width: int,
         experts: int, held: int, per_token: int, vocab: int, prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader on this holder
    (``batch`` frames of ``tokens`` tokens through every layer, then each
    frame's next token's logits over the vocabulary slice): every matrix
    product the mathematics has (an ``M`` layer's ``W_in`` onto ``[z | xBC |
    dt]`` and its ``W_out``, the convolution's taps, the scan by
    :func:`ssd_scan`'s count; a ``*`` layer's four projections, attention
    over the causal pairs only; an ``E`` layer's router, its TWO products an
    expert over the EVEN share of slots that falls to the ``held`` of
    ``experts``, the shared expert once; a ``-`` layer's two), nothing
    recomputed. Bytes: not counted (a whole step has no one roofline), 0."""
    rows = batch * tokens
    wide = scan_heads * scan_head_dim
    conv = wide + 2 * groups * state
    by_letter = {
        MAMBA: (2 * rows * hidden * (wide + conv + scan_heads) + 2 * rows * wide * hidden
                + 2 * taps * rows * conv
                + ssd_scan(batch, tokens, scan_heads, scan_head_dim, state, groups)["flops"]),
        ATTENTION: (2 * rows * hidden * head_dim * (2 * heads + 2 * kv_heads)
                    + causal_attention(batch, tokens, heads, kv_heads, head_dim)["flops"]),
        EXPERTS: (2 * 2 * rows * hidden * (shared_width + expert_width * per_token * held / experts)
                  + 2 * rows * hidden * experts),
        DENSE: 2 * 2 * rows * hidden * dense_width,
    }
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * batch * hidden * vocab
    for letter in pattern[:layers]:
        total += by_letter[letter]
    return {"flops": float(total), "bytes": 0.0}
