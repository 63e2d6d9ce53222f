"""Operations and bytes Laguna-S-2.1's reader NEEDS, from its shapes alone,
in ``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes). A full layer's attention is
``roofline/lfm2.causal_attention``'s count at this model's heads, the
held experts' products ``roofline/kimi_k2.held_products``', as they stand;
a layer's query heads are read from the file's per-layer lists."""

from benchmark.roofline import kimi_k2, lfm2
from benchmark.roofline.decoder import selected_pairs

FULL, SLIDING = "full_attention", "sliding_attention"  # layer_types, as the file spells them


def _heads(heads_per_layer: list, layer_types: list, op: str) -> int:
    """The query heads of the layers of one type (one count a type)."""
    heads = {h for h, kind in zip(heads_per_layer, layer_types) if kind == op}
    if len(heads) != 1:
        raise ValueError(f"{op} layers have {sorted(heads)} query heads: one count is counted")
    return heads.pop()


def full_attention(batch: int, tokens: int, heads_per_layer: list, layer_types: list,
                   kv_heads: int, head_dim: int) -> dict:
    """ONE full layer's causal attention: ``lfm2.causal_attention`` at this
    layer type's query heads of ``head_dim``."""
    heads = _heads(heads_per_layer, layer_types, FULL)
    return lfm2.causal_attention(batch, tokens, heads * head_dim, heads, kv_heads)


def windowed_attention(batch: int, tokens: int, window: int, heads_per_layer: list,
                       layer_types: list, kv_heads: int, head_dim: int) -> dict:
    """ONE windowed layer's attention over the BAND's pairs only (``sum_t
    min(t + 1, window)`` a sequence), whichever tiles a kernel visits to
    cover them: a score and a weighted sum of ``head_dim`` per pair and
    query head (``4 * head_dim`` FLOPs); q, o, k and v moved once."""
    heads = _heads(heads_per_layer, layer_types, SLIDING)
    moved = 2 * batch * tokens * head_dim * (2 * heads + 2 * kv_heads)
    return {"flops": float(4 * head_dim * heads * batch * selected_pairs(tokens, window)),
            "bytes": float(moved)}


def held_products(tokens: int, per_token: int, hidden: int, width: int, held: int, layers: int,
                  dense_only: list, held_share: float) -> dict:
    """``kimi_k2.held_products`` where the file lists its dense layers
    (``mlp_only_layers``) and does not count them."""
    return kimi_k2.held_products(tokens, per_token, hidden, width, held, layers, len(dense_only),
                                 held_share)


def step(batch: int, tokens: int, hidden: int, layer_types: list, heads_per_layer: list,
         kv_heads: int, head_dim: int, window: int, dense_only: list, dense_width: int,
         expert_width: int, experts: int, held: int, per_token: int, shared_width: int,
         vocab: int, prompt: int, patch: int) -> dict:
    """The model FLOPs of ONE step of the frame reader on this holder
    (``batch`` frames of ``tokens`` tokens through every layer, then each
    frame's next token's logits over the vocabulary slice): every matrix
    product the mathematics has (a layer's ``W_q`` and ``W_o`` at ITS query
    heads, ``W_k``, ``W_v``, the gate's), a full layer's attention over the
    causal pairs and a windowed layer's over the band's pairs only, the
    routed experts over the EVEN share of slots that falls to the ``held`` of
    ``experts``, the shared expert once, nothing recomputed. Bytes: not
    counted, 0."""
    rows = batch * tokens
    attention = {FULL: full_attention(batch, tokens, heads_per_layer, layer_types, kv_heads,
                                      head_dim)["flops"] if FULL in layer_types else 0.0,
                 SLIDING: windowed_attention(batch, tokens, window, heads_per_layer, layer_types,
                                             kv_heads, head_dim)["flops"]
                 if SLIDING in layer_types else 0.0}
    dense = 3 * 2 * rows * hidden * dense_width
    sparse = (3 * 2 * rows * hidden * (shared_width + expert_width * per_token * held / experts)
              + 2 * rows * hidden * experts)
    total = 2 * batch * (tokens - prompt) * patch * patch * hidden + 2 * batch * hidden * vocab
    for i, (op, heads) in enumerate(zip(layer_types, heads_per_layer)):
        total += 2 * rows * hidden * (2 * heads * head_dim + 2 * kv_heads * head_dim + heads)
        total += attention[op] + (dense if i in dense_only else sparse)
    return {"flops": float(total), "bytes": 0.0}
