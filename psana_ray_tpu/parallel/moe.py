"""Expert parallelism: capacity-bounded switch-routing mixture of experts.

The reference has no model code, hence no expert parallelism (SURVEY.md §2
"Parallelism strategies: TP/PP/SP/EP — none"); the task spec makes EP a
first-class sharding for the TPU build. This is the TPU-idiomatic
formulation — the GShard/Switch dense-dispatch pattern rather than any
ragged scatter/gather:

- routing produces a fixed-shape dispatch tensor (expert capacity is
  STATIC, derived from the token count at trace time), so the whole layer
  is three einsums with no dynamic shapes — XLA tiles them onto the MXU
  and, with the expert axis of the weights sharded ``P('expert')``,
  lowers the token⇄expert re-layout to an all-to-all over ICI;
- the token axis is CHUNKED into groups (the GShard/MaxText ``group_size``
  idiom): capacity is allocated per group of ``G`` consecutive tokens and
  the dispatch tensor is ``[B·T/G, G, E, C_g]`` with
  ``C_g = ceil(G·cf/E)`` — its footprint scales with ``T·C_g``, not
  ``T·C``. The monolithic form at the ViT serving shape (T=8448, E=4,
  cf=2) is a ~1.1 GB f32 tensor PER LAYER; grouped at G≤512 it is ~9 MB.
  The trade is that overflow drops are decided within each group instead
  of globally FIFO (the standard grouped-Switch semantics);
- tokens that overflow an expert's capacity are *dropped at this layer
  only*: their combine weight is zero, and the transformer block's
  residual connection passes them through unchanged (the standard Switch
  behavior);
- the router's load-balancing loss (Switch eq. 4: ``E · Σ_e f_e · p_e``)
  is sown into the ``intermediates`` collection;
  ``parallel.steps.make_train_step(aux_loss_weight=...)`` folds it into
  the training objective.

Sharding: expert weights carry the logical axis ``('expert', ...)`` which
``ShardingRules`` maps to the mesh's ``expert`` axis; activations need no
manual constraints — XLA propagates the expert sharding through the
dispatch einsum (scaling-book recipe: annotate the weights, let the
compiler place the collectives).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from psana_ray_tpu.ops.row_gather import gather_rows, sum_counted_rows

Dtype = Any


def pick_group_size(t: int, max_group_size: int) -> int:
    """Largest divisor of ``t`` that is <= ``max_group_size`` (falls back
    to ``t`` when nothing smaller divides it — tiny sequences simply stay
    monolithic). Static: derived from trace-time shapes."""
    if max_group_size <= 0 or t <= max_group_size:
        return t
    for g in range(max_group_size, 0, -1):
        if t % g == 0:
            return g
    return t


class SwitchMoEMlp(nn.Module):
    """Drop-in replacement for a transformer MLP: ``[B, T, D] -> [B, T, D]``.

    Top-1 (switch) routing over ``num_experts`` independent
    ``D -> mlp_ratio·D -> D`` GELU FFNs with per-group expert capacity
    ``C_g = ceil(G · capacity_factor / E)``. The gate value scales the
    chosen expert's output, so the router receives gradients through the
    scale (the Switch trick that makes hard top-1 routing trainable).

    ``group_size`` chunks the token axis for dispatch (see module
    docstring): None auto-picks the largest divisor of T that is
    <= ``max_group_size``; pass an explicit divisor of T to pin it.
    Routing probabilities and gates are per-token and unaffected; only
    which overflow tokens drop changes (per group vs globally)."""

    embed_dim: int
    num_experts: int
    mlp_ratio: int = 4
    capacity_factor: float = 2.0
    dtype: Dtype = jnp.bfloat16
    group_size: Any = None  # None = auto (largest divisor <= max_group_size)
    max_group_size: int = 512

    @nn.compact
    def __call__(self, x):
        b_in, t_in, d = x.shape
        e, f = self.num_experts, self.mlp_ratio * self.embed_dim
        g = (
            int(self.group_size)
            if self.group_size is not None
            else pick_group_size(t_in, self.max_group_size)
        )
        if t_in % g:
            raise ValueError(
                f"group_size={g} does not divide the {t_in}-token sequence"
            )
        # groups fold into the batch axis: every downstream einsum sees
        # [B*T/G, G, ...] and the dispatch tensor scales with G, not T
        x = x.reshape(b_in * (t_in // g), g, d)
        b, t = x.shape[:2]
        cap = max(1, math.ceil(t * self.capacity_factor / e))  # static

        # ---- route (f32: softmax over a handful of logits, negligible) ----
        logits = nn.Dense(
            e, dtype=jnp.float32, param_dtype=jnp.float32, name="router"
        )(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)  # [B, T, E]
        gate = jnp.max(probs, axis=-1)  # [B, T]
        sel = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e, dtype=jnp.float32)
        # FIFO position of each token in its expert's queue; -1 where unrouted,
        # so the capacity one-hot below zeroes both overflow AND unrouted slots
        pos = jnp.cumsum(sel, axis=1) * sel - 1.0  # [B, T, E]
        dispatch = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
        combine = dispatch * gate[..., None, None]  # [B, T, E, C]

        # load-balance loss on the PRE-capacity assignment (Switch eq. 4)
        f_frac = jnp.mean(sel, axis=(0, 1))  # fraction of tokens per expert
        p_mean = jnp.mean(probs, axis=(0, 1))  # mean router prob per expert
        self.sow("intermediates", "aux_loss", e * jnp.sum(f_frac * p_mean))

        # ---- dispatch -> expert FFN -> combine (three MXU einsums) ----
        def ep_param(name, init, shape, axes):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape, jnp.float32
            )

        w_up = ep_param(
            "w_up",
            nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e, d, f),
            ("expert", "embed", "mlp"),
        )
        b_up = ep_param("b_up", nn.initializers.zeros, (e, f), ("expert", "mlp"))
        w_dn = ep_param(
            "w_dn",
            nn.initializers.variance_scaling(2.0, "fan_in", "truncated_normal"),
            (e, f, d),
            ("expert", "mlp", "embed"),
        )
        b_dn = ep_param("b_dn", nn.initializers.zeros, (e, d), ("expert", "embed"))

        dt = self.dtype
        xin = jnp.einsum("btec,btd->ebcd", dispatch.astype(dt), x.astype(dt))
        h = nn.gelu(
            jnp.einsum("ebcd,edf->ebcf", xin, w_up.astype(dt))
            + b_up[:, None, None, :].astype(dt)
        )
        # empty capacity slots compute gelu(bias) garbage here; their combine
        # weight is zero, so nothing of it reaches the output
        out = (
            jnp.einsum("ebcf,efd->ebcd", h, w_dn.astype(dt))
            + b_dn[:, None, None, :].astype(dt)
        )
        y = jnp.einsum("btec,ebcd->btd", combine.astype(dt), out)
        return y.reshape(b_in, t_in, d).astype(x.dtype)


def total_aux_loss(intermediates) -> jax.Array:
    """Sum every sown ``aux_loss`` in an ``intermediates`` collection
    (sown values are tuples; scanned trunks stack them along depth).

    Filters by key path — only leaves under a dict key named ``aux_loss``
    count, so other sown intermediates (debug stats, activation probes)
    can never silently leak into the training objective via
    ``make_train_step(aux_loss_weight=...)``."""
    total = jnp.zeros((), jnp.float32)
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        if any(
            isinstance(k, jax.tree_util.DictKey) and k.key == "aux_loss"
            for k in path
        ):
            total = total + jnp.sum(leaf)
    return total


# ---------------------------------------------------------------------------
# Dropless top-k routing over the experts HELD here
# ---------------------------------------------------------------------------


# one trace and one lowering a process, as gated_row_sum: k passes, k gates and k compares a layer
# cost a warm start 0.35 s in a six-layer step where they were traced a layer (my chip runs, PR 51)
@functools.partial(jax.jit, static_argnames=("k", "renormalise", "gate_eps", "gate_scale", "groups",
                                             "groups_kept"))
def route_top_k(probs: jax.Array, k: int, renormalise: bool = True, *, select_bias=None,
                gate_eps: float = 0.0, gate_scale: float = 1.0, groups: int = 1,
                groups_kept: int = 1):
    """``probs [T, E]`` float32 -> ``(ids [T, k] int32, gates [T, k]
    float32)``: each token's ``k`` highest-scoring experts (equal scores:
    the lower index first) and their gates, renormalised to sum to one
    over the ``k`` (``norm_topk_prob``) or as they are. With
    ``select_bias [E]`` the experts are CHOSEN by ``probs + select_bias``
    and WEIGHTED by ``probs`` without it (DeepSeek-V3's bias-steered
    selection); ``gate_eps`` joins the renormalising sum and
    ``gate_scale`` multiplies the gates (``routed_scaling_factor``).
    With ``groups`` > 1 the choice is GROUP-LIMITED (``n_group``,
    ``topk_group``): the experts are ``groups`` runs of ``E / groups``
    consecutive ones, a group's score is the sum of its two largest
    choosing scores, the ``groups_kept`` best groups stay (equal scores:
    the lower group) and the ``k`` are chosen among their experts only.

    Nothing here indexes by data: no sort, gather or scatter. The ``k``
    are taken by ``k`` dense passes over ``[T, E]`` and their affinities
    read by a compare and a row sum (what ``lax.top_k`` and
    ``take_along_axis`` gave, bit for bit, ties included); the
    renormalising sum is written out (:func:`_sum_by_halves`). On the v5e
    the whole routing of a layer at 34,816 x 512, 8 of 4 groups in 8,
    took 12.0 ms that way and takes 2.0 this way (my chip runs, PR 51)."""
    scores = probs if select_bias is None else probs + select_bias.astype(probs.dtype)
    if groups > 1:
        width = scores.shape[1] // groups
        if groups_kept * width < k:  # a struck winner and a barred expert both read -inf
            raise ValueError(f"{groups_kept} groups of {width} experts hold fewer than k={k}")
        scores = jnp.where(_groups_kept(scores, groups, groups_kept), scores, -jnp.inf)
    ids = _first_best(scores, k)
    chosen = _at_columns(probs, ids)  # k columns [T]
    top_p = jnp.stack(chosen, axis=1)
    if renormalise:
        total = _sum_by_halves(chosen)[:, None]
        top_p = top_p / (total + gate_eps if gate_eps else total)
    if gate_scale != 1.0:
        top_p = top_p * gate_scale
    return ids, top_p


def _sum_by_halves(terms):
    """The sum of a list of arrays in ONE written order on every backend:
    term ``i`` meets term ``i + n/2`` first, ``n`` the next power of two
    (of eight: ``((t0 + t4) + (t2 + t6)) + ((t1 + t5) + (t3 + t7))``). It is
    the order XLA's lane reduce gave ``jnp.sum(top_p, axis=-1)`` on the TPU
    while ``top_p`` was a gather's output (278,528 rows of 278,528 at k 8,
    139,264 at k 4: my chip runs, PR 51), kept so that the gates, and with
    them a served step's outputs, did not move by a bit when the gather
    went; on the CPU that ``jnp.sum`` adds in index order, 1-2 ulps off."""
    terms = list(terms)
    terms += [None] * ((1 << (len(terms) - 1).bit_length()) - len(terms))
    while len(terms) > 1:
        half = len(terms) // 2
        terms = [a if b is None else a + b for a, b in zip(terms[:half], terms[half:])]
    return terms[0]


def _first_best(scores, k: int):
    """``scores [T, E]`` -> ``[T, k]`` int32: each row's ``k`` largest in
    descending order, equal scores the lower index first (``lax.top_k``'s
    order), by ``k`` passes over ``[T, E]``: a pass takes the first index
    of the row's maximum and strikes it."""
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    ids = []
    for _ in range(k):
        best = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        ids.append(best)
        scores = jnp.where(col == best[:, None], -jnp.inf, scores)
    return jnp.stack(ids, axis=1)


def _at_columns(values, ids):
    """``values[t, ids[t, j]]`` for each ``j``, ``k`` arrays ``[T]``, without
    a gather: a sum over the row with one nonzero term, so exact."""
    col = jax.lax.broadcasted_iota(jnp.int32, values.shape, 1)
    return [jnp.sum(jnp.where(col == ids[:, j, None], values, 0), axis=-1)
            for j in range(ids.shape[1])]


def _groups_kept(scores, groups: int, kept: int):
    """``[T, E]`` bool: the experts of each row's ``kept`` best groups of
    ``groups`` runs of consecutive experts, a group's score the sum of its
    two largest (equal scores: the lower group stays)."""
    t, e = scores.shape
    by_group = scores.reshape(t, groups, e // groups)
    best = jnp.max(by_group, axis=-1)
    first = jnp.argmax(by_group, axis=-1)
    within = jax.lax.broadcasted_iota(jnp.int32, by_group.shape, 2)
    second = jnp.max(jnp.where(within == first[..., None], -jnp.inf, by_group), axis=-1)
    score = best + second  # [T, groups]
    g = jnp.arange(groups)
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None]) & (g[None, :] < g[:, None]))
    stays = jnp.sum(ahead, axis=-1) < kept  # a group's rank among the row's groups
    return jnp.repeat(stays, e // groups, axis=1)


@functools.partial(jax.jit, static_argnames=("first", "count"))
def _slots_per_expert(ids, first: int, count: int):
    """``[count]`` int32: how many of the token slots ``ids [T, k]`` chose
    each of the experts ``first .. first + count - 1``: column sums of a
    compare, no scatter."""
    experts = first + jnp.arange(count, dtype=ids.dtype)
    chose = sum((ids[:, j, None] == experts).astype(jnp.int32) for j in range(ids.shape[1]))
    return jnp.sum(chose, axis=0)


SCORINGS = {"softmax": lambda logits: jax.nn.softmax(logits, axis=-1), "sigmoid": jax.nn.sigmoid}


WEIGHT_TILE_BYTES = 4 * 2 ** 20  # a bf16 weight tile [tk, tn] of the grouped product, at most
GMM_VMEM_BYTES = 21 * 2 ** 20  # the kernel's tiles in all, by `_gmm_vmem` (Mosaic allows 22 MB)


def grouped_tiles(m: int, groups: int, k: int, n: int, out_bytes: int, gated: bool = False):
    """The tiles ``(tm, tk, tn)`` a grouped product (megablox's, and :func:`gmm`,
    the one that ends in the activation) runs ``[m, k] x [groups, k, n]`` in,
    from its operands' shapes alone (``out_bytes``: the output's item size;
    ``gated``: a float32 tile of the gate's product rides beside the output's,
    :func:`_gmm_vmem`). The kernel's grid is (output tiles, VISITS,
    contraction tiles): a visit is one (row tile, group) pair and multiplies
    a whole ``tm x tk x tn`` block whatever part of the tile's rows are the
    group's, a row tile two groups share is visited twice, and a last output
    tile is computed whole where ``tn`` does not divide ``n``. Three things
    follow, one rule (ms a product on the v5e, the product ALONE under the
    profiler: my chip runs, PR 54; PERF.md section 5 has every candidate):

    (1) ``tn`` divides ``n`` in whole 128-lane tiles, as wide as a bf16 weight
    tile ``[tk, tn]`` within 4 MiB allows: no column is computed that is not
    stored. Until PR 54 a width over 2,048 went in tiles of 2,048: ling3's
    down product ``[104448, 768] x [128, 768, 2560]`` (68,762 rows held)
    computed 4,096 columns for 2,560 and took 4.45 ms at (512, 768, 2048),
    2.77 at (512, 768, 2560). LFM2's ``2048 x 1792`` goes in 896 (whole it
    overflows Mosaic's VMEM), laguna's ``1024 x 3072`` in 1,536. A width no
    128-multiple divides (1,856 = 14.5 lane tiles, the ungated experts' of PR
    64) goes in the tile within those 4 MiB that computes the fewest columns
    past it, the widest of those: 640, three tiles, 1,920 columns for 1,856
    (whole, as until then, its weight tile ``[2688, 1856]`` is 10 MB and the
    kernel's 41.7 MB of VMEM where Mosaic allows 37: compiled for a described
    v5e, PR 64).

    (2) ``tk = k`` where a weight tile of the whole contraction and 512
    columns (or all ``n``) stays within those 4 MiB, that is up to ``k``
    4,096: with ONE contraction tile the weight block's index is the same
    over a group's consecutive visits, the pipeline does not fetch it again
    and a visit moves its row tile only (ling3's gate / up ``[104448, 2560] x
    [128, 2560, 768]``: 3.14 ms at (256, 1280, 768), 2.36 at (256, 2560,
    768); cut in two at 128 rows 4.43: then every visit fetches its weights).
    Laguna's ``3072 x 1024`` takes two output tiles of 512 for it and reads
    its rows twice: 2.75 ms at (256, 1536, 1024), 2.25 at (128, 3072, 512). A
    wider contraction (7,168) goes in its largest 128-multiple of at most
    2,048 that divides it (1,792: no masked remainder tile), as before.

    (3) ``tm`` follows the rows a group can have, ``m // groups``: what a
    group's boundary wastes is ``tm`` over the group's rows, so under a whole
    contraction the row tile is the largest of (512, 256, 128) of at most a
    QUARTER of them (ling3's pass, 816: 128 rows, its three products 3.14 +
    3.14 + 4.45 -> 2.33 + 2.33 + 2.39 at 60% of the peak for 44 and 31;
    laguna's, 1,020: 128, 2.75 + 2.75 + 2.82 -> 2.25 + 2.25 + 2.26; at 256
    rows both read within 4% of that), never under the MXU's own 128. Where
    even 128 is more than a quarter (a group of under 512 rows: a turn of
    2,048 over kimi's 12 or dsv32's 8 experts) or the contraction is cut
    (every visit fetches its weights, and more visits fetch more) the tile is
    as it was, the largest that divides ``m`` with ``tm * tk`` at most 512 Ki:
    such a product is bound by reading its weights, and at 128 rows kimi's
    down product read 1.38 ms a layer for 1.37, dsv32's 0.78 for 0.79, their
    gate / up 2.15 for 1.52 and 1.15 for 0.87. Keye's ``[274432, 2048] x
    [128, 2048, 768]`` stays (256, 2048, 768), 5.40 ms at 82% of the peak
    (5.60 at 512 rows, 5.53 at 128), its down product (512, 768, 2048) 5.62;
    LFM2's (256, 2048, 896) 5.88 at 89% (6.04 at 128). Last, the row tile
    halves while the kernel's tiles pass 21 MiB (:func:`_gmm_vmem`): 512
    rows beside ``[768, 2560]`` into float32, the down product of ling3's idle
    loop, would take 25.2 MB."""
    def dividing(x, most):  # the largest 128-multiple of at most `most` that divides x
        return next((t for t in range(most - most % 128, 0, -128) if x % t == 0), None)

    def covering(x, most):  # where none divides x: the one that computes the fewest columns past x
        return min(range(128, most - most % 128 + 1, 128), key=lambda t: (-(-x // t) * t, -t),
                   default=None)

    whole = WEIGHT_TILE_BYTES // (2 * k) >= min(n, 512)
    tk = k if whole else dividing(k, 2048) or 2048
    fits = WEIGHT_TILE_BYTES // (2 * tk)
    tn = n if n <= fits else dividing(n, fits) or covering(n, fits) or min(n, 2048)
    quarter = m // groups // 4
    most = quarter if whole and quarter >= 128 else 512
    tm = next((t for t in (512, 256, 128, 64, 32, 16, 8)
               if t <= most and m % t == 0 and t * tk <= 512 * 1024), m)
    while tm > 8 and _gmm_vmem(tm, tk, tn, out_bytes, gated) > GMM_VMEM_BYTES:
        tm //= 2
    return tm, tk, tn


def _gmm_vmem(tm: int, tk: int, tn: int, out_bytes: int, gated: bool = False) -> int:
    """Bytes of VMEM the grouped product's tiles take: two buffers of each
    bf16 operand's tile and of the output's, the float32 accumulator, and
    where the product ends in a GATED activation (:func:`gmm`) two buffers of
    the gate's float32 tile (the loop's down product at ``[2048, 1024] x [64,
    1024, 3072]`` into float32 took 23.4 MB at (512, 1024, 2048) where Mosaic
    allows 22: compiled for a described v5e, PR 53)."""
    return 2 * (2 * tm * tk + 2 * tk * tn + (out_bytes + 4 * gated) * tm * tn) + 4 * tm * tn


def _tiled(rows, weights, out_dtype, gated=False):
    """``(weights as the kernel reads them, whether that is transposed, the
    tiles)`` of a grouped product ``rows x weights`` into ``out_dtype``."""
    (m, k), (groups, _, n) = rows.shape, weights.shape
    tiling = grouped_tiles(m, groups, k, n, jnp.dtype(out_dtype).itemsize, gated)
    if n % 128 and not k % 128:
        # the device lays a weight whose columns are no whole lane tiles out with its CONTRACTION
        # minor (`{1,2,0}`: no lane padded), the kernel's operand is row-major, and XLA copied the
        # weight at every use (2.02 ms for `[64, 2688, 1856]`, under no scope: my chip run, PR 64).
        # In that layout its transpose IS row-major, a bitcast: the kernel contracts both minor axes
        return jnp.swapaxes(weights, 1, 2), True, tiling
    return weights, False, tiling


def _grouped_product(rows, weights, tokens, out_dtype, interpret):
    """``rows[group e] @ weights[e]`` (``tokens [E]``: the rows of each group,
    the first group at row 0; rows past the last group's stay unwritten), by
    the megablox grouped matrix product (``pallas.ops.tpu.megablox.gmm``) in
    the tiles :func:`grouped_tiles` gives the operands' shapes: an expert
    layer's gate and down products (its up product ends in the activation:
    :func:`gmm`). On the v5e at ``[274432, 2048] x [128, 2048, 768]`` it took
    6.3 ms and the down product 6.4, against ``lax.ragged_dot``'s 11.3 and
    11.0 (my chip runs, PR 36)."""
    from jax.experimental.pallas.ops.tpu import megablox

    weights, transposed, tiling = _tiled(rows, weights, out_dtype)
    return megablox.gmm(rows, weights, tokens, preferred_element_type=out_dtype, tiling=tiling,
                        transpose_rhs=transposed, interpret=interpret)


# named as megablox's: a device trace names a kernel after the jitted function it was traced in, and
# an expert layer's products are read there by that name (`trace_names.grouped_product`)
@functools.partial(jax.jit, static_argnames=("out_dtype", "tiling", "transpose_rhs", "interpret"))
def gmm(rows, weights, tokens, gate=None, *, out_dtype, tiling, transpose_rhs=False,
        interpret=False):
    """An expert's hidden rows from its UP product's accumulator: megablox's
    grouped product ``rows[group e] @ weights[e]`` (its grid of (output
    tiles, visits, contraction tiles), its group metadata and its store mask;
    ``weights [E, n, k]`` under ``transpose_rhs``) whose last contraction step
    stores :func:`hidden_rows`' form and not the sum: ``silu(gate) * acc``
    where ``gate [m, n]`` float32, the gate's product, is given (one more
    operand, in the output's own block), ``relu(acc)^2`` where it is not;
    float32 throughout, rounded ONCE, to ``out_dtype``. A Pallas call takes no
    epilogue from XLA: as a fusion of its own between the products the form
    read two float32 ``[139264, 1792]`` and wrote a bf16 one, 3.5 ms a layer
    of lfm2's under products the MXU bounds (my chip runs, PR 39)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import _get_store_mask, make_group_metadata

    (m, k), n = rows.shape, weights.shape[1 if transpose_rhs else 2]
    tm, tk, tn = tiling
    tiles_k, k_rem = -(-k // tk), k % tk
    operand = jnp.bfloat16 if rows.dtype == weights.dtype == jnp.bfloat16 else jnp.float32
    gates = [] if gate is None else [gate]
    metadata, visits = make_group_metadata(
        group_sizes=tokens, m=m, tm=tm, start_group=0, num_nonzero_groups=weights.shape[0],
        visit_empty_groups=False)

    def kernel(metadata, lhs, rhs, *rest):
        *gate_tile, out, acc = rest
        visit, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        def within_k(x, axis):  # the last contraction tile's part past `k` holds anything: zero
            col = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
            return jnp.where(col < k_rem, x.astype(jnp.float32), 0).astype(x.dtype)

        def step(last):
            a, b = lhs[...], rhs[...]
            if last and k_rem:
                a, b = within_k(a, 1), within_k(b, int(transpose_rhs))
            acc[...] += jax.lax.dot_general(
                a.astype(operand), b.astype(operand), (((1,), (int(transpose_rhs),)), ((), ())),
                preferred_element_type=jnp.float32)
            if last:  # the form's products are this tile of the gate's and the accumulator
                h = hidden_rows(lambda tile: tile[...], gate_tile[0] if gate_tile else None, acc)
                here = _get_store_mask(grid_id=visit, group_metadata=metadata, tm=tm, tn=tn)
                # a row tile two groups share is visited twice: the other group's rows stay
                out[...] = jax.lax.select(here, h, out[...].astype(jnp.float32)).astype(out.dtype)

        jax.lax.cond(k_i == tiles_k - 1, functools.partial(step, True), functools.partial(step, False))

    def row_tile(n_i, visit, k_i, metadata):
        return metadata[2][visit], k_i

    def weight_tile(n_i, visit, k_i, metadata):
        return (metadata[1][visit], n_i, k_i) if transpose_rhs else (metadata[1][visit], k_i, n_i)

    def out_tile(n_i, visit, k_i, metadata):
        return metadata[2][visit], n_i

    out_spec = pl.BlockSpec((tm, tn), out_tile)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), row_tile),
                      pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn), weight_tile),
                      *[out_spec for _ in gates]],
            out_specs=out_spec,
            grid=(-(-n // tn), visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=m * n * len(gates),
            bytes_accessed=(rows.size * rows.itemsize * -(-n // tn)
                            + k * n * weights.itemsize * metadata[1].size
                            + m * n * (jnp.dtype(out_dtype).itemsize + 4 * len(gates)))))
    return call(metadata, rows, weights, *gates)


def hidden_rows(product, w_gate, w_up):
    """An expert's (or a dense MLP's) hidden rows, float32, from
    ``product(w)``, its input rows times a weight in float32: the gated SiLU
    ``silu(product(w_gate)) * product(w_up)`` where a gate's weights are
    given, ``relu(product(w_up))^2`` where none are (``w_gate`` None: an
    UNGATED expert, two products a layer and not three). The one place the
    form stands: the decoder's dense and shared MLPs read it here over XLA's
    own products (which fuse it as their epilogue) and round; the routed
    experts' grouped up product (:func:`gmm`) reads it tile by tile over its
    accumulator, in the kernel, and rounds there."""
    if w_gate is None:
        return jnp.square(jax.nn.relu(product(w_up)))
    h = jax.nn.silu(product(w_gate))
    return h * product(w_up)


def _expert_hidden(rows, w_gate, w_up, tokens, dtype, interpret):
    """:func:`hidden_rows` of the experts' input ``rows`` (``tokens [E]``: the
    rows of each expert's group), rounded once to ``dtype``: the gate's
    grouped product float32, then the up product that ends in the activation
    (:func:`gmm`) - no float32 array is written between an expert layer's
    products but the gate's, and an ungated layer writes none."""
    gate = None if w_gate is None else _grouped_product(rows, w_gate, tokens, jnp.float32, interpret)
    weights, transposed, tiling = _tiled(rows, w_up, dtype, gated=gate is not None)
    return gmm(rows, weights, tokens, gate, out_dtype=dtype, tiling=tiling,
               transpose_rhs=transposed, interpret=interpret)


def dropless_moe(x, router_w, w_gate, w_up, w_down, *, k: int, num_experts: int,
                 experts_held=None, renormalise: bool = True, scoring: str = "softmax",
                 select_bias=None, gate_eps: float = 0.0, gate_scale: float = 1.0,
                 groups: int = 1, groups_kept: int = 1, interpret=None):
    """Top-``k`` of ``num_experts`` experts with NO dropped token: ``x [T,
    D]`` -> ``(y [T, D], tokens [count] int32)``. An expert is a gated-SiLU
    MLP, or, where ``w_gate`` is ``None``, the UNGATED ``relu(x W_up)^2
    W_down`` (:func:`hidden_rows`).

    The layer is told which experts it holds, ``experts_held = (first,
    count)`` (default: all), and given THEIR weights only (``w_gate, w_up
    [count, D, F]``, ``w_down [count, F, D]``). It routes over all
    ``num_experts`` (``router_w [D, E]``; the affinities, ``scoring``
    ``"softmax"`` or ``"sigmoid"`` of the logits, in float32; ``select_bias``,
    ``gate_eps``, ``gate_scale``, ``groups`` and ``groups_kept`` as
    :func:`route_top_k` takes them), and
    returns its own experts' part of the result: the sum over the chosen
    experts that live here of ``gate * (silu(x W_gate) * (x W_up))
    W_down``; what the absent experts would add is left out, and the
    parts of all holders add up to the whole layer. ``tokens`` is how
    many token slots each held expert served.

    The routing (scope ``moe_route``: the router's product, the choice,
    the gates, each held expert's count, the slots' sort) is dense passes
    over ``[T, E]`` and one or two ``argsort``s of the ``T * k`` slots.
    Token slots are sorted by expert (stable: a token's order within an
    expert is its order in ``x``) and each row moves ONCE each way. Out:
    one in-bounds gather puts the rows in expert order
    (``ops/row_gather.gather_rows``). The three products (two where the
    experts have no gate) are grouped matrix products over the sorted rows
    (each expert's weights meet its own rows only, so the work is ``T * k``
    rows whatever the load), and the activation is the UP product's last
    step (:func:`gmm`: float32 over the accumulator's tile, rounded once; no
    pass of XLA's stands between an expert layer's products). Back
    (:func:`gated_row_sum`): a token's ``k`` rows are read where the sort
    put them, ``k`` in-bounds gathers of ``[T, D]``, and one pass writes
    their gated sum, float32 inside, the ``k`` added in the order of the
    token's choices: a token's result depends on its own rows only,
    wherever it sits among the others. A holder of a SHARE of the experts
    moves and multiplies the held slots' rows only (:func:`_held_rows_moe`;
    its way back reads the COUNTED rows only, ``ops/row_gather.sum_counted_rows``).
    On one holder this runs without an exchange; nothing here stands in
    for the other holders. Off the TPU the kernels run in Pallas interpret
    mode."""
    t, d = x.shape
    first, count = (0, num_experts) if experts_held is None else map(int, experts_held)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x, router_w.astype(x.dtype), preferred_element_type=jnp.float32)
        ids, gates = route_top_k(SCORINGS[scoring](logits), k, renormalise,
                                 select_bias=select_bias, gate_eps=gate_eps,
                                 gate_scale=gate_scale, groups=groups, groups_kept=groups_kept)
    if count < num_experts:  # a share of the experts: the rows HELD move, no others
        return _held_rows_moe(x, ids, gates, w_gate, w_up, w_down, first, count, interpret,
                              rows_ahead(t * k, count, num_experts))
    with jax.named_scope("moe_route"):
        order = jnp.argsort(ids.reshape(-1), stable=True)
        per_expert = _slots_per_expert(ids, 0, num_experts)
    with jax.named_scope("moe_experts"):
        rows = gather_rows(x, order // k, interpret=interpret)  # [T*k, D], expert-major
        h = _expert_hidden(rows, w_gate, w_up, per_expert, x.dtype, interpret)
        # out in x's type: the sorted rows are T*k*D, and float32 would be 2.2 GB at 34,304 x 8
        out = _grouped_product(h, w_down, per_expert, x.dtype, interpret)
        y = gated_row_sum(out, order, gates)
    return y, per_expert


# rows a turn of the held rows' loop (two tiles of the row gather's kernel). On the v5e at 17,408
# x 8 slots of 7,168, 12 of 384 experts held, 3,805 held rows: 2,048 a turn 15.3 ms a layer,
# 1,024 15.3, 4,096 26.3, 8,192 29.2: XLA's scatter-add of float32 rows takes 4.05 ms for 2,048
# rows and 16.6 for 4,096 (promised sorted and unique: 16.0 and 16.6); the parent's way, all
# 139,264 slots' rows gathered and the grouped product offset to the held groups, 55.9 ms and
# 6.2 GB (my chip runs, PR 42)
HELD_CHUNK = 2048
# a holder of at least this share of the experts takes its even share of the slots, and half as
# much again, in ONE pass ahead of the loop. On the v5e at 34,816 x 8 slots of 2,560, 128 of 512
# experts held, 74,085 held rows: the loop alone 53.6 ms a layer, 37 turns whose scatter-add is 24.1
# of them (0.32 us a row; the three products 10.2), and what a step takes follows the rows a seed
# happens to hold (my chip runs, PR 50). The break-even was set while the pass went back by k
# gathers of [T, D] (15.4 ms at that shape, dearer than the two or three turns a holder of 1/32
# takes); since PR 52 it goes back by the counted rows alone (4.9 ms there), so the share at which
# the pass pays is lower than this: moving it moves kimi's and dsv32's steps, and is not done here
AHEAD_SHARE = 1 / 8


def goes_ahead(count: int, num_experts: int) -> bool:
    """Whether a holder of ``count`` of ``num_experts`` takes a pass ahead of
    its held rows' loop: the one place the rule stands (the decoder's
    statistics ask here too)."""
    return count >= AHEAD_SHARE * num_experts


def rows_ahead(slots: int, count: int, num_experts: int) -> int:
    """The rows :func:`_held_rows_moe` takes in one pass ahead of its loop:
    none where the holder's share does not :func:`goes_ahead`, else 1.5 even
    shares in whole 8-row tiles (104,448 = 204 x 512 of 278,528 slots at 128
    of 512 experts)."""
    if not goes_ahead(count, num_experts):
        return 0
    even = -(-slots * count // num_experts)  # rounded up, as the next two
    ahead = -(-3 * even // 2)
    return min(-(-ahead // 8) * 8, slots // 8 * 8)


def _held_rows_moe(x, ids, gates, w_gate, w_up, w_down, first: int, count: int, interpret,
                   ahead: int = 0, chunk: int = HELD_CHUNK):
    """:func:`dropless_moe` where the holder has ``count`` of the experts,
    from the routing on (``ids, gates [T, k]``): time and memory follow the
    token slots whose expert lives HERE, not ``T * k``. The slots are
    sorted held experts first (expert-major among them, a token's order
    within an expert its order in ``x``), and a ``lax.while_loop`` takes
    them ``chunk`` rows a turn, as many turns as the held rows fill: a
    turn gathers its rows, runs the three grouped products over the part
    of each expert's group that falls into it, and adds each row, under
    its gate, to its token's sum (float32, rounded once at the end). No
    array of ``T * k`` rows exists; when every token chooses held experts
    the loop runs ``T * k / chunk`` turns and still drops nothing.

    ``ahead`` > 0 (a holder of a LARGE share, :func:`rows_ahead`): the
    first ``ahead`` sorted rows go through ONE pass before the loop, the
    all-held path's way at that size out (one gather, the three products
    over ``[ahead, .]``); back, a token's COUNTED rows alone are copied
    from where the sort put them and summed under their gates in the
    order of its choices (:func:`gated_row_sum`'s rule; a slot past the
    held rows or past ``ahead`` counts nothing, and but for the few that
    fill a burst of copies moves nothing:
    ``ops/row_gather.sum_counted_rows``, 4.9 ms a layer at ling3's shape
    where ``k`` gathers of ``[T, D]`` and their sum took 15.4: my chip
    runs, PR 52); the loop then takes what lies beyond ``ahead``, on an
    even load nothing."""
    t, d = x.shape
    k = ids.shape[1]
    slots = t * k
    chunk = min(chunk, slots)
    with jax.named_scope("moe_route"):
        here = (ids >= first) & (ids < first + count)
        local = jnp.where(here, ids - first, count).reshape(-1)  # not held: sorts last
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        back = jnp.argsort(order).reshape(t, k) if ahead else None  # slot (t, j) -> its sorted row
        # a turn's slice never runs off the end
        order = jnp.pad(order, (0, -(slots - ahead) % chunk))
        per_expert = _slots_per_expert(ids, first, count)
        ends = jnp.cumsum(per_expert)
        starts, n_held = ends - per_expert, ends[-1]
        flat_gates = gates.reshape(-1)

    def rows_before(c):  # turn c's first row (no `+ 0` traced where nothing goes ahead)
        return c * chunk + ahead if ahead else c * chunk

    def turn(state):
        c, y = state
        lo = rows_before(c)
        slot = jax.lax.dynamic_slice(order, (lo,), (chunk,))
        live = lo + jnp.arange(chunk, dtype=jnp.int32) < n_held
        token = slot // k
        rows = gather_rows(x, token, interpret=interpret)
        sizes = jnp.clip(ends, lo, lo + chunk) - jnp.clip(starts, lo, lo + chunk)
        h = _expert_hidden(rows, w_gate, w_up, sizes, x.dtype, interpret)
        out = _grouped_product(h, w_down, sizes, jnp.float32, interpret)
        # past the held rows the products left `out` unwritten: 0 x garbage is not 0
        term = jnp.where(live[:, None], out * flat_gates[slot][:, None], 0.0)
        return c + 1, y.at[token].add(term)

    with jax.named_scope("moe_experts"):
        y = (_held_rows_ahead(x, order[:ahead], back, gates, w_gate, w_up, w_down, starts, ends,
                              interpret) if ahead else jnp.zeros((t, d), jnp.float32))
        _, y = jax.lax.while_loop(lambda state: rows_before(state[0]) < n_held, turn,
                                  (jnp.int32(0), y))
    return y.astype(x.dtype), per_expert


def _held_rows_ahead(x, slot, back, gates, w_gate, w_up, w_down, starts, ends, interpret):
    """The first ``len(slot)`` sorted rows of :func:`_held_rows_moe` in one
    pass -> each token's gated sum over them, ``[T, D]`` float32."""
    k = gates.shape[1]
    ahead = slot.shape[0]
    rows = gather_rows(x, slot // k, interpret=interpret)
    sizes = jnp.clip(ends, 0, ahead) - jnp.clip(starts, 0, ahead)
    h = _expert_hidden(rows, w_gate, w_up, sizes, x.dtype, interpret)
    out = _grouped_product(h, w_down, sizes, x.dtype, interpret)
    # past the held rows the products left `out` unwritten: such a slot, as one past `ahead`, adds
    # nothing whatever its row holds (the sum selects it to zero)
    return sum_counted_rows(out, back, jnp.minimum(ends[-1], ahead), gates, interpret=interpret)


@jax.jit  # one trace and one lowering a process, not one an expert layer: k gathers are slow to trace
def gated_row_sum(out, order, gates):
    """The expert layer's way back: ``out [T*k, D]`` (rows in the order
    ``order [T*k]`` gave the token slots), ``gates [T, k]`` float32 ->
    ``y [T, D]`` in ``out``'s type, ``y[t] = sum_j gates[t, j] * out[row
    of slot (t, j)]`` in float32, ``j = 0 .. k-1`` in that order, rounded
    once.

    One sort inverts the permutation; ``k`` gathers of ``[T, D]`` with
    indices promised in bounds (no fill pass) feed ONE fused pass. What
    this replaced gathered ``[T*k, D]`` under a fill mask, relaid it as
    ``[T, k, D]`` (half-filled tiles at ``k`` 4) and reduced over ``k``:
    8.09 ms at 34,816 x 4 and 11.55 at 34,304 x 8 against 5.97 and 9.35
    (one gather of all ``T*k`` rows in slot-major order: 5.93 and 11.46;
    my chip runs, PR 39)."""
    t, k = gates.shape
    back = jnp.argsort(order).reshape(t, k)  # slot (t, j) -> its row among the sorted
    y = None
    for j in range(k):
        rows = out.at[back[:, j]].get(mode="promise_in_bounds", unique_indices=True)
        term = rows.astype(jnp.float32) * gates[:, j, None]
        y = term if y is None else y + term
    return y.astype(out.dtype)
