"""Operations and bytes Xing4.0's reader NEEDS, from its shapes alone, in
``roofline/calib.py``'s sense: the algorithm's least, not what a kernel
happens to compute. bf16 operands (2 bytes); the stream between the layers
bf16, the mixing numbers float32."""

from benchmark.roofline.decoder import grouped_product  # noqa: F401  (an expert layer's one product)
from benchmark.roofline.kimi_k2 import latent_attention  # noqa: F401  (the same operator, 32 heads)
from benchmark.roofline import kimi_k2


def _numbers(streams: int) -> int:
    """The mixing numbers a token and branch: H_pre, H_post and H_res."""
    return streams * (streams + 2)


def hyper_in(rows: int, hidden: int, streams: int, iters: int) -> dict:
    """The way IN of one branch, whatever implements it: the ``streams *
    hidden``-wide stream read once (the statistic, the product with ``phi``
    and the mix all read the same rows), ``phi`` read once, ``u`` written
    once and the ``streams * (streams + 2)`` mixing numbers written in
    float32. FLOPs: the product's ``2 * streams * hidden * numbers`` a row,
    the statistic's and the mix's ``2 * streams * hidden`` each, and the
    Sinkhorn's ``iters`` times two passes of a divide and an add an element
    of the ``streams x streams`` matrix."""
    n, numbers = streams, _numbers(streams)
    moved = 2 * (rows * hidden * (n + 1) + n * hidden * numbers) + 4 * rows * numbers
    flops = rows * (2 * n * hidden * numbers + 4 * n * hidden + iters * 4 * n * n)
    return {"flops": float(flops), "bytes": float(moved)}


def hyper_out(rows: int, hidden: int, streams: int, iters: int) -> dict:
    """The way BACK of one branch: the stream and the branch's output read
    once, the mixing numbers read, the new stream written once; ``2 *
    hidden * (streams^2 + streams)`` FLOPs a row. (``iters``: the
    signature of the three functions is one.)"""
    n = streams
    moved = 2 * rows * hidden * (n + 1 + n) + 4 * rows * _numbers(n)
    return {"flops": float(2 * rows * hidden * (n * n + n)), "bytes": float(moved)}


def hyper_connection(rows: int, hidden: int, streams: int, iters: int) -> dict:
    """ONE branch's hyper-connection, the mechanism's own work whatever
    implements it (two kernels, one, or XLA's fusions): :func:`hyper_in` and
    :func:`hyper_out`, bytes ``rows * hidden * (streams + 1 + streams + 1 +
    streams) * 2`` and the mixing numbers, which at 17,408 rows of 4 x 3,584
    is 1.75 GB: 2.1 ms at 819 GB/s where its 0.08 T FLOPs take 0.4."""
    a, b = (f(rows, hidden, streams, iters) for f in (hyper_in, hyper_out))
    return {"flops": a["flops"] + b["flops"], "bytes": a["bytes"] + b["bytes"]}


def held_products(tokens: int, per_token: int, hidden: int, width: int, held: int, layers: int,
                  dense_layers: int) -> dict:
    """ALL the grouped products of one step where EVERY expert is held (three
    an expert layer, over all ``tokens * per_token`` slots): kimi_k2's count
    at a share of 1."""
    return kimi_k2.held_products(tokens, per_token, hidden, width, held, layers, dense_layers, 1.0)


def step(batch: int, tokens: int, hidden: int, layers: int, dense_layers: int, dense_width: int,
         expert_width: int, experts: int, per_token: int, shared: int, heads: int, q_rank: int,
         kv_rank: int, nope: int, rope: int, value: int, vocab: int, prompt: int, patch: int,
         streams: int, iters: int) -> dict:
    """The model FLOPs of ONE step of the frame reader: DeepSeek-V3's block
    as ``kimi_k2.step`` counts it with every expert held, and two
    hyper-connections a layer (:func:`hyper_connection`'s FLOPs: the ``phi``
    products, the statistic, the mixes, the Sinkhorn). Bytes: not counted, 0."""
    block = kimi_k2.step(batch, tokens, hidden, layers, dense_layers, dense_width, expert_width,
                         experts, experts, per_token, shared, heads, q_rank, kv_rank, nope, rope,
                         value, vocab, prompt, patch)["flops"]
    mixes = 2 * layers * hyper_connection(batch * tokens, hidden, streams, iters)["flops"]
    return {"flops": float(block + mixes), "bytes": 0.0}
