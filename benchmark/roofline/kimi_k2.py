"""Operations and bytes Kimi-K2's reader NEEDS, from its shapes alone, in
``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes)."""

from benchmark.roofline.decoder import causal_pairs


def latent_attention(batch: int, tokens: int, heads: int, nope: int, rope: int,
                     value: int) -> dict:
    """Latent attention's prefill, decompressed: a score over ``nope +
    rope`` and a weighted sum of ``value`` per causal pair and head (``2 *
    (nope + rope + value)`` FLOPs), whichever way a kernel reaches the
    rotary part; each head's q (both parts), k_nope, v and o moved once,
    and the ONE rotary key once."""
    rows = batch * tokens
    moved = 2 * rows * (heads * (2 * nope + rope + 2 * value) + rope)
    return {"flops": float(2 * (nope + rope + value) * heads * batch * causal_pairs(tokens)),
            "bytes": float(moved)}


def held_products(tokens: int, per_token: int, hidden: int, width: int, held: int,
                  layers: int, dense_layers: int, held_share: float) -> dict:
    """ALL the grouped products of one step on a holder of a share of the
    experts: three an expert layer, over the rows whose expert is held,
    ``held_share`` of the ``tokens * per_token`` slots (the program's own
    count: ``expert_rows_held_total / expert_rows_routed_total``); the held
    experts' matrices read once a product, the rows read and written
    once. ``call_sites``: the places the program calls the kernel from,
    one a product (``readers/roofline_share_per_run.py`` holds the trace
    to it)."""
    rows = tokens * per_token * held_share
    products = 3 * (layers - dense_layers)
    moved = 2 * (held * hidden * width + rows * (hidden + width))
    return {"flops": float(products * 2 * rows * hidden * width), "bytes": float(products * moved),
            "call_sites": products}


def step(batch: int, tokens: int, hidden: int, layers: int, dense_layers: int, dense_width: int,
         expert_width: int, experts: int, held: int, per_token: int, shared: int, heads: int,
         q_rank: int, kv_rank: int, nope: int, rope: int, value: int, vocab: int, prompt: int,
         patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader on this holder
    (``batch`` frames of ``tokens`` tokens through every layer, then each
    frame's next token's logits over the vocabulary slice): every matrix
    product the mathematics has, attention over the causal pairs only, the
    routed experts over the EVEN share of slots that falls to the ``held``
    of ``experts``, nothing recomputed. Bytes: not counted, 0."""
    rows = batch * tokens
    projections = 2 * rows * (hidden * q_rank + q_rank * heads * (nope + rope)
                              + hidden * (kv_rank + rope) + kv_rank * heads * (nope + value)
                              + heads * value * hidden)
    attention = projections + latent_attention(batch, tokens, heads, nope, rope, value)["flops"]
    dense = 3 * 2 * rows * hidden * dense_width
    sparse = (3 * 2 * rows * hidden * expert_width * (shared + per_token * held / experts)
              + 2 * rows * hidden * experts)
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * batch * hidden * vocab
    total += layers * attention + dense_layers * dense + (layers - dense_layers) * sparse
    return {"flops": float(total), "bytes": 0.0}
