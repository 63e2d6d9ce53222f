"""Expert parallelism: switch-routing MoE with capacity-bounded dispatch.

The behavior bar for parallel/moe.py: routing semantics (top-1, FIFO
capacity, drop-to-residual), dense equivalence in the degenerate case,
the Switch load-balance loss, and sharded-vs-single-device agreement on a
('data', 'expert') mesh. The reference has no EP (SURVEY.md §2); these
tests define it."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from flax.core import meta as nn_meta

from psana_ray_tpu.models import ViTHitClassifier
from psana_ray_tpu.models.losses import masked_softmax_xent
from psana_ray_tpu.parallel import SwitchMoEMlp, create_mesh, total_aux_loss
from psana_ray_tpu.parallel.steps import create_train_state, make_train_step


@pytest.fixture(scope="module")
def ep_mesh():
    return create_mesh(("data", "expert"), (2, 4))


def _moe(e=4, d=8, cap=2.0):
    return SwitchMoEMlp(
        embed_dim=d, num_experts=e, mlp_ratio=2, capacity_factor=cap,
        dtype=jnp.float32,
    )


class TestRouting:
    def test_single_expert_equals_gated_dense(self, rng):
        # E=1 with ample capacity: every token routes to expert 0 at
        # gate 1.0 (softmax over one logit), so the layer IS its FFN
        x = jnp.asarray(rng.normal(size=(2, 6, 8)).astype(np.float32))
        moe = _moe(e=1, cap=8.0)
        v = moe.init(jax.random.key(0), x)
        y = moe.apply(v, x)
        p = nn_meta.unbox(v)["params"]
        dense = (
            jax.nn.gelu(x @ p["w_up"][0] + p["b_up"][0]) @ p["w_dn"][0] + p["b_dn"][0]
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(dense), rtol=1e-5, atol=1e-6)

    def test_overflow_tokens_drop_to_zero(self, rng):
        # capacity 1 per expert, all tokens forced to one expert by a
        # biased router: only the FIRST token per batch row survives
        x = jnp.asarray(rng.normal(size=(1, 5, 8)).astype(np.float32))
        moe = _moe(e=4, cap=0.2)  # cap = ceil(5*0.2/4) = 1
        v = nn_meta.unbox(moe.init(jax.random.key(0), x))
        # bias the router hard toward expert 2
        v = jax.tree.map(lambda a: a, v)
        router_b = np.zeros((4,), np.float32)
        router_b[2] = 1e4
        v["params"]["router"]["bias"] = jnp.asarray(router_b)
        y = moe.apply(v, x)
        row_norms = np.linalg.norm(np.asarray(y[0]), axis=-1)
        assert row_norms[0] > 0  # token 0 won the single capacity slot
        np.testing.assert_allclose(row_norms[1:], 0.0, atol=1e-6)  # rest dropped

    def test_aux_loss_balanced_is_one(self, rng):
        # perfectly uniform routing makes E * sum(f*p) -> 1 (Switch eq. 4
        # lower bound); a hard-collapsed router scores ~E
        x = jnp.asarray(rng.normal(size=(2, 64, 8)).astype(np.float32))
        moe = _moe(e=4)
        v = nn_meta.unbox(moe.init(jax.random.key(0), x))
        _, inter = moe.apply(v, x, mutable=["intermediates"])
        balanced = float(total_aux_loss(inter["intermediates"]))
        assert 0.9 < balanced < 2.5  # near-uniform at random init

        router_b = np.zeros((4,), np.float32)
        router_b[1] = 1e4
        v["params"]["router"]["bias"] = jnp.asarray(router_b)
        _, inter = moe.apply(v, x, mutable=["intermediates"])
        collapsed = float(total_aux_loss(inter["intermediates"]))
        assert collapsed > 3.5  # ~E when all tokens hit one expert
        assert collapsed > balanced

    def test_aux_loss_ignores_other_sown_intermediates(self, rng):
        """Only leaves under an 'aux_loss' key count (ADVICE r4): a debug
        stat sown into the same collection must not change the total."""
        x = jnp.asarray(rng.normal(size=(2, 64, 8)).astype(np.float32))
        moe = _moe(e=4)
        v = nn_meta.unbox(moe.init(jax.random.key(0), x))
        _, inter = moe.apply(v, x, mutable=["intermediates"])
        want = float(total_aux_loss(inter["intermediates"]))
        polluted = dict(inter["intermediates"])
        polluted["debug_stat"] = (jnp.full((), 1e6, jnp.float32),)
        assert float(total_aux_loss(polluted)) == want

    def test_capacity_is_static(self):
        # same module, two token counts -> two capacities, no recompile
        # errors (capacity derives from shapes at trace time)
        moe = _moe(e=2, cap=1.0)
        x8 = jnp.zeros((1, 8, 8), jnp.float32)
        x16 = jnp.zeros((1, 16, 8), jnp.float32)
        v = moe.init(jax.random.key(0), x8)
        assert moe.apply(v, x8).shape == (1, 8, 8)
        assert moe.apply(v, x16).shape == (1, 16, 8)


class TestGroupedDispatch:
    """Token-axis chunking (VERDICT r4 weak #4): the dispatch tensor at
    detector scale must be [B·T/G, G, E, C_g], not the ~1.1 GB/layer
    monolithic [B, T, E, C]."""

    def test_pick_group_size(self):
        from psana_ray_tpu.parallel.moe import pick_group_size

        assert pick_group_size(8448, 512) == 384  # ViT serving shape
        assert pick_group_size(64, 512) == 64  # small seqs stay monolithic
        assert pick_group_size(1056, 512) == 352
        assert pick_group_size(8448, 512) * (8448 // 384) == 8448
        assert pick_group_size(7, 4) == 1  # prime beyond cap: degenerate

    def test_grouped_equals_monolithic_when_nothing_drops(self, rng):
        # with capacity_factor >= E no token can overflow in EITHER
        # grouping (worst case: a whole group on one expert), so grouped
        # and monolithic dispatch are numerically identical
        x = jnp.asarray(rng.normal(size=(2, 64, 8)).astype(np.float32))
        kw = dict(embed_dim=8, num_experts=4, mlp_ratio=2,
                  capacity_factor=4.0, dtype=jnp.float32)
        mono = SwitchMoEMlp(**kw, group_size=64)
        grouped = SwitchMoEMlp(**kw, group_size=16)
        v = mono.init(jax.random.key(0), x)
        np.testing.assert_allclose(
            np.asarray(mono.apply(v, x)),
            np.asarray(grouped.apply(v, x)),
            rtol=1e-5, atol=1e-6,
        )

    def test_group_must_divide_tokens(self, rng):
        x = jnp.zeros((1, 10, 8), jnp.float32)
        moe = SwitchMoEMlp(embed_dim=8, num_experts=2, group_size=4,
                           dtype=jnp.float32)
        with pytest.raises(ValueError, match="does not divide"):
            moe.init(jax.random.key(0), x)

    def test_grouped_dispatch_tensor_is_bounded(self):
        # trace-level proof for the serving scale: no intermediate in the
        # jaxpr may reach the monolithic dispatch size (T*E*C elements).
        # T=8448, E=4, cf=2: monolithic C=4224 -> 285M elems at B=1;
        # grouped G=384, C_g=192 -> the largest dispatch-shaped tensor is
        # 8448*4*192 = 6.5M elems per batch row
        t, e, d = 8448, 4, 64
        moe = SwitchMoEMlp(embed_dim=d, num_experts=e, mlp_ratio=2,
                           capacity_factor=2.0, dtype=jnp.bfloat16)
        x = jax.ShapeDtypeStruct((1, t, d), jnp.bfloat16)
        v = jax.eval_shape(
            lambda: moe.init(jax.random.key(0), jnp.zeros((1, 64, d), jnp.bfloat16))
        )
        jaxpr = jax.make_jaxpr(
            lambda vv, xx: moe.apply(vv, xx), static_argnums=()
        )(v, x)
        monolithic = t * e * math.ceil(t * 2.0 / e)
        biggest = max(
            int(np.prod(eqn_var.aval.shape))
            for eqn in jaxpr.eqns
            for eqn_var in eqn.outvars
            if hasattr(eqn_var.aval, "shape")
        )
        assert biggest < monolithic / 10, (
            f"largest traced intermediate {biggest} elems — grouping not "
            f"effective (monolithic dispatch would be {monolithic})"
        )

    def test_sharded_matches_single_device_at_1k_tokens(self, rng, ep_mesh):
        # VERDICT r4 do #5: the sharded==single assertion at >=1k tokens,
        # where grouping is active (auto G=352 for T=1056)
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = jnp.asarray(rng.normal(size=(2, 1056, 8)).astype(np.float32))
        moe = _moe(e=4, cap=2.0)
        v = nn_meta.unbox(moe.init(jax.random.key(0), x))
        want = moe.apply(v, x)
        xs = jax.device_put(x, NamedSharding(ep_mesh, P("data")))
        got = jax.jit(moe.apply)(v, xs)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
        )


class TestExpertParallel:
    def test_sharded_matches_single_device(self, rng, ep_mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        model = ViTHitClassifier(
            patch=8, embed_dim=64, depth=2, num_heads=4, num_classes=2,
            dtype=jnp.float32, moe_experts=4,
        )
        frames = jnp.asarray(rng.normal(size=(4, 2, 16, 32)).astype(np.float32))
        variables = model.init(jax.random.key(0), frames)
        want = model.apply(variables, frames)

        unboxed = nn_meta.unbox(variables)
        xs = jax.device_put(frames, NamedSharding(ep_mesh, P("data")))
        got = jax.jit(model.apply)(unboxed, xs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)

    def test_expert_weights_shard_on_expert_axis(self, rng, ep_mesh):
        # init_sharded (via create_train_state) places w_up/w_dn on the
        # expert axis — each device holds E/4 experts, not all of them
        model = ViTHitClassifier(
            patch=8, embed_dim=64, depth=2, num_heads=4, num_classes=2,
            dtype=jnp.float32, moe_experts=4, scan_trunk=True,
        )
        frames = jnp.asarray(rng.normal(size=(8, 2, 16, 32)).astype(np.float32))
        state = create_train_state(
            model, optax.adamw(1e-3), jax.random.key(1), frames, ep_mesh
        )
        w_up = state.variables["params"]["trunk"]["blocks"]["block"]["moe"]["w_up"]
        # scanned trunk: [layers, expert, d, f]; expert axis sharded
        assert w_up.shape[:2] == (2, 4)
        spec = w_up.sharding.spec
        assert spec[1] == "expert", spec

    def test_moe_vit_train_step_with_aux_loss(self, rng, ep_mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        model = ViTHitClassifier(
            patch=8, embed_dim=64, depth=2, num_heads=4, num_classes=2,
            dtype=jnp.float32, moe_experts=4, scan_trunk=True,
        )
        frames = jnp.asarray(rng.normal(size=(8, 2, 16, 32)).astype(np.float32))
        state = create_train_state(
            model, optax.adamw(1e-3), jax.random.key(1), frames, ep_mesh
        )
        step = make_train_step(
            model, optax.adamw(1e-3),
            lambda lg, aux: masked_softmax_xent(lg, aux[0], aux[1]),
            aux_loss_weight=0.01,
        )
        xs = jax.device_put(frames, NamedSharding(ep_mesh, P("data")))
        labels = jnp.asarray(np.arange(8) % 2)
        valid = jnp.ones((8,), jnp.uint8)
        state, loss = step(state, xs, (labels, valid))
        assert np.isfinite(float(loss))
        assert int(jax.device_get(state.step)) == 1
        # intermediates were consumed by the step, not folded into state
        assert "intermediates" not in state.variables

    def test_degrades_to_replication_without_expert_axis(self, rng):
        # the same MoE model must still initialize on a mesh with no
        # 'expert' axis (weights replicate) — rules degrade, not raise
        mesh = create_mesh(("data", "model"), (4, 2))
        model = ViTHitClassifier(
            patch=8, embed_dim=64, depth=2, num_heads=4, num_classes=2,
            dtype=jnp.float32, moe_experts=2,
        )
        frames = jnp.asarray(rng.normal(size=(8, 2, 16, 32)).astype(np.float32))
        state = create_train_state(
            model, optax.adamw(1e-3), jax.random.key(0), frames, mesh
        )
        w_up = jax.tree.leaves(
            {k: v for k, v in state.variables["params"].items()}
        )
        assert all(np.isfinite(np.asarray(jax.device_get(l))).all() for l in w_up)


def test_serving_capacity_factor_is_trace_time_only():
    """The serving-side capacity trick (train at cf=2.0, serve at
    cf=1.25): expert capacity is a trace-time constant, so
    one trained tree must apply unchanged under ANY capacity factor, and
    with capacity >= tokens/expert-worst-case the outputs must agree
    exactly (no token ever dropped at either setting)."""
    rng = np.random.default_rng(3)
    kw = dict(patch=8, embed_dim=64, depth=2, num_heads=4, num_classes=2,
              dtype=jnp.float32, moe_experts=2)
    train_model = ViTHitClassifier(moe_capacity_factor=2.0, **kw)
    frames = jnp.asarray(rng.normal(size=(2, 2, 16, 32)).astype(np.float32))
    variables = nn_meta.unbox(train_model.init(jax.random.key(0), frames))

    # two NO-DROP capacities (cap=t vs cap=2t — cf=E and cf=2E): different
    # dispatch-tensor shapes, same routing outcome, so outputs must agree
    # exactly — proves capacity changes only the trace, and the padded
    # capacity slots' garbage never leaks into the combine. With E=2 the
    # first config equals train_model's cf=2.0, so it doubles as the
    # train-setting output
    e = float(kw["moe_experts"])
    out_nd1 = train_model.apply(variables, frames)  # cf=2.0 == cf=E here
    out_nd2 = ViTHitClassifier(moe_capacity_factor=2 * e, **kw).apply(variables, frames)
    np.testing.assert_allclose(
        np.asarray(out_nd1), np.asarray(out_nd2), rtol=1e-5, atol=1e-5
    )
    # the shipped train/serve settings: the cf=2.0 tree applies unchanged
    # at cf=1.25, right shape, finite (drops fall back to the residual)
    serve = ViTHitClassifier(moe_capacity_factor=1.25, **kw)
    out_lo = serve.apply(variables, frames)
    assert out_lo.shape == out_nd1.shape
    assert np.isfinite(np.asarray(out_lo)).all()


# ---------------------------------------------------------------------------
# The dropless layer's router: dense passes held to the indexed form they replaced
# ---------------------------------------------------------------------------


def _chip_order_sum(top_p):
    """``jnp.sum(top_p, axis=-1, keepdims=True)`` in the order the TPU gave it
    until PR 51 (column i meets column i + n/2 first, n the next power of
    two), spelled out here on its own: the order ``route_top_k`` now writes."""
    cols = [top_p[:, j] for j in range(top_p.shape[1])]
    while len(cols) & (len(cols) - 1):
        cols.append(None)
    while len(cols) > 1:
        half = len(cols) // 2
        cols = [lo if hi is None else lo + hi for lo, hi in zip(cols[:half], cols[half:])]
    return cols[0][:, None]


def _indexed_route(probs, k, renormalise=True, *, select_bias=None, gate_eps=0.0, gate_scale=1.0,
                   groups=1, groups_kept=1, total_of=lambda top_p: jnp.sum(top_p, axis=-1, keepdims=True)):
    """``moe.route_top_k`` as it stood until PR 51, line for line: ``lax.top_k``
    for the choice (three with a group limit), ``take_along_axis`` for the gates
    (``total_of``: its ``jnp.sum`` over the k, or that sum in the chip's order)."""
    if groups > 1:
        scores = probs if select_bias is None else probs + select_bias.astype(probs.dtype)
        t, e = scores.shape
        best_two = jax.lax.top_k(scores.reshape(t, groups, e // groups), 2)[0]
        _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), groups_kept)
        stays = jnp.any(kept[:, :, None] == jnp.arange(groups, dtype=kept.dtype), axis=1)
        _, ids = jax.lax.top_k(
            jnp.where(jnp.repeat(stays, e // groups, axis=1), scores, -jnp.inf), k)
        top_p = jnp.take_along_axis(probs, ids, axis=-1)
    elif select_bias is None:
        top_p, ids = jax.lax.top_k(probs, k)
    else:
        _, ids = jax.lax.top_k(probs + select_bias.astype(probs.dtype), k)
        top_p = jnp.take_along_axis(probs, ids, axis=-1)
    if renormalise:
        total = total_of(top_p)
        top_p = top_p / (total + gate_eps if gate_eps else total)
    if gate_scale != 1.0:
        top_p = top_p * gate_scale
    return ids.astype(jnp.int32), top_p


def _indexed_counts(ids, first, count, num_experts):
    """Each held expert's token slots as both paths of the layer counted
    them until PR 51: ``bincount`` over all experts, or over the held ones
    with every other slot sent to one bin past them."""
    if count == num_experts:
        return jnp.bincount(ids.reshape(-1), length=num_experts).astype(jnp.int32)
    here = (ids >= first) & (ids < first + count)
    local = jnp.where(here, ids - first, count).reshape(-1)
    return jnp.bincount(local, length=count + 1)[:count].astype(jnp.int32)


def _cell(e, k, scoring, bias, **kw):
    return dict(e=e, k=k, scoring=scoring, bias=bias, **kw)


# (E, k, groups, groups_kept, scoring, bias, the gate's constants) of the five decoder cells,
# then what a router's ties, bias and gate options can do
ROUTINGS = {
    "ling3": _cell(512, 8, "sigmoid", "random", groups=8, groups_kept=4, gate_eps=1e-20, gate_scale=2.5),
    "lfm2": _cell(32, 4, "sigmoid", "random", gate_eps=1e-6),
    "kimi": _cell(384, 8, "sigmoid", "random", gate_eps=1e-20, gate_scale=2.827),
    "dsv32": _cell(256, 8, "sigmoid", "random", groups=8, groups_kept=4, gate_eps=1e-20, gate_scale=2.5),
    "keye": _cell(128, 8, "softmax", None),
    "all_scores_equal": _cell(64, 8, "sigmoid", None, logits="zero"),
    "all_scores_equal_under_a_group_limit": _cell(64, 8, "sigmoid", "zero", groups=8, groups_kept=4,
                                                  logits="zero"),
    "few_distinct_scores": _cell(128, 8, "sigmoid", "coarse", logits="coarse", gate_eps=1e-20),
    "few_distinct_scores_under_a_group_limit": _cell(128, 8, "sigmoid", "coarse", groups=8, groups_kept=4,
                                                     logits="coarse", gate_eps=1e-20),
    "ties_across_a_group_boundary": _cell(32, 6, "sigmoid", None, groups=4, groups_kept=2,
                                          logits="boundary"),
    "groups_tie": _cell(32, 4, "sigmoid", None, groups=8, groups_kept=3, logits="coarse"),
    "a_bias_that_reorders": _cell(64, 6, "softmax", "large"),
    "a_bias_that_reorders_under_a_group_limit": _cell(64, 6, "sigmoid", "large", groups=4, groups_kept=2),
    "renormalise_off": _cell(64, 6, "sigmoid", "random", renormalise=False),
    "renormalise_off_scaled": _cell(64, 6, "softmax", None, renormalise=False, gate_scale=2.5),
    "gate_eps": _cell(64, 6, "sigmoid", "random", gate_eps=1e-6),
    "gate_scale": _cell(64, 6, "sigmoid", "random", gate_scale=2.827),
    "k_is_one": _cell(16, 1, "softmax", None),
    "every_expert_chosen": _cell(8, 8, "sigmoid", "coarse", logits="coarse"),
}


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_the_router_s_dense_passes_are_the_indexed_form_bit_for_bit(name):
    """``route_top_k`` and the per-expert counts (``lax.argmax`` passes, a
    compare and a row sum, column sums of a compare) against ``lax.top_k`` +
    ``take_along_axis`` + ``bincount``: ``ids``, ``gates`` and ``per_expert``
    equal BIT FOR BIT, ties included (the lower index first, in descending
    order of the choosing score), jitted as the layer runs them. The
    renormalising sum over the k is the one float32 sum whose ORDER a backend
    chose (the CPU by index, the TPU's lane reduce by halves): the gates are
    held bit for bit to the indexed form summing in the chip's order, which
    the router now writes out, and to the indexed form's own ``jnp.sum`` as
    the CPU runs it within 4 ulps, bit for bit where nothing is renormalised."""
    from psana_ray_tpu.parallel import moe

    case = dict(ROUTINGS[name])
    e, k, scoring, bias, logits = (case.pop(key, None) for key in ("e", "k", "scoring", "bias", "logits"))
    rng = np.random.default_rng(sorted(ROUTINGS).index(name))
    t = 160
    raw = rng.normal(size=(t, e)).astype(np.float32)
    if logits == "zero":
        raw = np.zeros_like(raw)
    elif logits == "coarse":  # three values: every row full of ties
        raw = np.round(raw)
        raw[::7] = 0.0
    elif logits == "boundary":  # the last expert of a group equals the first of the next, in every row
        width = e // case["groups"]
        raw = np.round(raw * 2) / 2
        raw[:, width] = raw[:, width - 1]
        raw[:, 2 * width - 1] = raw[:, 2 * width]
    select_bias = {None: None, "zero": np.zeros(e), "random": rng.normal(size=e) * 0.02,
                   "coarse": np.round(rng.normal(size=e)) / 4, "large": rng.normal(size=e) * 3}[bias]
    if select_bias is not None:
        select_bias = jnp.asarray(select_bias, jnp.float32)
    probs = moe.SCORINGS[scoring](jnp.asarray(raw))

    want = jax.jit(lambda p: _indexed_route(p, k, select_bias=select_bias, total_of=_chip_order_sum,
                                            **case))(probs)
    got = jax.jit(lambda p: moe.route_top_k(p, k, select_bias=select_bias, **case))(probs)
    for a, b in zip(want, got):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    ids, gates = (np.asarray(a) for a in got)
    ids_cpu, gates_cpu = (np.asarray(a) for a in jax.jit(
        lambda p: _indexed_route(p, k, select_bias=select_bias, **case))(probs))
    np.testing.assert_array_equal(ids, ids_cpu)
    ulps = np.abs(gates.view(np.int32).astype(np.int64) - gates_cpu.view(np.int32).astype(np.int64))
    assert ulps.max() <= (4 if case.get("renormalise", True) else 0), ulps.max()
    ids = got[0]
    if logits == "zero":
        np.testing.assert_array_equal(np.asarray(ids), np.tile(np.arange(k), (t, 1)))
    for first, count in ((0, e), (0, max(e // 4, 1)), (e // 2, max(e // 8, 1))):
        want_n = jax.jit(lambda i: _indexed_counts(i, first, count, e))(ids)
        got_n = jax.jit(lambda i: moe._slots_per_expert(i, first, count))(ids)
        assert want_n.dtype == got_n.dtype
        np.testing.assert_array_equal(np.asarray(want_n), np.asarray(got_n))


def test_a_group_limit_that_leaves_fewer_than_k_experts_is_refused():
    from psana_ray_tpu.parallel import moe

    with pytest.raises(ValueError, match="fewer than k"):
        moe.route_top_k(jnp.zeros((4, 16)), 6, groups=4, groups_kept=1)


# ---------------------------------------------------------------------------
# A share-holder's way back: the counted rows' kernel held to the k gathers it replaced
# ---------------------------------------------------------------------------


def _for_j_form(out, back, limit, gates):
    """The pass's way back until PR 52 (``moe._held_rows_ahead``'s lines): ``k``
    gathers of ``[T, D]``, each under a ``where`` that throws away what it
    fetched for a slot that does not count."""
    counted = back < limit
    y = jnp.zeros((back.shape[0], out.shape[1]), jnp.float32)
    for j in range(back.shape[1]):
        at = jnp.where(counted[:, j], back[:, j], 0)
        got = out.at[at].get(mode="promise_in_bounds")
        y = y + jnp.where(counted[:, j, None], got.astype(jnp.float32) * gates[:, j, None], 0.0)
    return y


# tokens, k, rows of `out`, limit (None: every row), dtype: the shapes' corners
WAYS_BACK = {
    "a_quarter_counted": (48, 8, 104, 50, jnp.bfloat16),  # most tokens: none, one or two of eight
    "every_row_counted": (48, 8, 384, None, jnp.bfloat16),  # all eight of every token
    "nothing_held": (48, 8, 104, 0, jnp.bfloat16),
    "rows_past_the_limit_unwritten": (40, 8, 104, 77, jnp.bfloat16),  # NaN there: never read
    "tokens_not_a_tile": (21, 4, 40, 33, jnp.bfloat16),  # 21 tokens, 40 rows: both padded
    "two_steps_of_the_grid": (512, 4, 1000, 900, jnp.bfloat16),  # 2,048 slots: 2 tiles of 1,024
    "float32_rows": (48, 8, 104, 60, jnp.float32),
}


@pytest.mark.parametrize("gates_are", ["powers_of_two", "with_zeros", "any"])
@pytest.mark.parametrize("d", [2560, 2048, 384, 64])  # 384: an odd count of 128-column chunks
@pytest.mark.parametrize("name", sorted(WAYS_BACK))
def test_the_counted_rows_sum_is_the_k_gathers_it_replaced(name, d, gates_are):
    """``ops/row_gather.sum_counted_rows`` (interpret mode here) against the
    ``for j`` form: float32 sums in the order of a token's choices, a slot
    past the limit adding nothing. Under gates that are powers of two every
    product is exact, so the two are equal BIT FOR BIT whatever a backend
    does with ``a * b + c`` and only the rows, the masks and the ORDER of the
    sum decide; under any gates XLA's CPU backend contracts the interpreted
    kernel's product and sum into one rounding where it keeps the gathers'
    apart (on the TPU the two forms are equal to the bit: PERF.md section 5, PR 52),
    so there the two may differ in the last place."""
    from psana_ray_tpu.ops.row_gather import sum_counted_rows

    t, k, n, limit, dtype = WAYS_BACK[name]
    if d == 2560 and name == "two_steps_of_the_grid":
        d = 512  # the same two steps, a fifth of the interpreter's time
    rng = np.random.default_rng(len(name) * 1000 + d)
    out = rng.standard_normal((n, d)).astype(np.float32)
    limit = n if limit is None else limit
    out[limit:] = np.nan  # what the grouped products leave past the held rows: anything
    back = jnp.asarray(rng.permutation(max(t * k, n))[:t * k].reshape(t, k), jnp.int32)
    if name == "a_quarter_counted":
        back = back.at[3].set(jnp.arange(k) + limit)  # a token with no counted slot ...
        back = back.at[5].set(jnp.arange(k))  # ... and one whose eight all count
    gates = 2.0 ** -rng.integers(0, 7, (t, k)) if gates_are != "any" else rng.random((t, k)) + 0.1
    if gates_are == "with_zeros":
        gates = np.where(rng.random((t, k)) < 0.3, 0.0, gates)
    out, gates = jnp.asarray(out, dtype), jnp.asarray(gates, jnp.float32)
    got = np.asarray(sum_counted_rows(out, back, limit, gates))
    want = np.asarray(jax.jit(_for_j_form)(out, back, jnp.int32(limit), gates))
    assert got.shape == (t, d) and got.dtype == np.float32 and np.isfinite(got).all()
    counted = np.asarray(back) < limit
    assert (counted.sum(1) == 0).any() or limit == n
    np.testing.assert_array_equal(got[counted.sum(1) == 0], 0.0)
    if gates_are == "any":
        np.testing.assert_allclose(got, want, rtol=0, atol=4 * np.spacing(np.abs(want).max()))
    else:
        np.testing.assert_array_equal(got, want)  # -0.0 == 0.0


@pytest.mark.parametrize("case", ["within_the_pass", "more_held_than_the_pass_takes", "nothing_held"])
def test_a_large_share_s_layer_is_the_whole_layer_restricted_to_the_held(case):
    """8 of 32 experts held (a quarter: a pass of 96 rows goes ahead of the
    loop) against ``dropless_moe`` on ALL experts with the others' down
    weights zeroed: the pass's way back and, where a bias sends every
    choice to the held eight, the loop's 160 rows add up to the same layer."""
    from psana_ray_tpu.parallel import moe

    t, d, f, e, k = 64, 64, 32, 32, 4
    keys = jax.random.split(jax.random.key(52), 6)
    x = jax.random.normal(keys[0], (t, d), jnp.float32)
    router = 0.3 * jax.random.normal(keys[1], (d, e), jnp.float32)
    w_gate, w_up = (0.2 * jax.random.normal(key, (e, d, f), jnp.float32) for key in keys[2:4])
    w_down = 0.2 * jax.random.normal(keys[4], (e, f, d), jnp.float32)
    bias = 0.1 * jax.random.normal(keys[5], (e,), jnp.float32)
    bias = bias.at[8:16].add({"within_the_pass": 0.0, "more_held_than_the_pass_takes": 5.0,
                              "nothing_held": -5.0}[case])
    kw = dict(k=k, num_experts=e, scoring="sigmoid", select_bias=bias, gate_eps=1e-20, gate_scale=2.5)
    assert moe.rows_ahead(t * k, 8, e) == 96
    with jax.default_matmul_precision("highest"):
        y, tokens = moe.dropless_moe(x, router, w_gate[8:16], w_up[8:16], w_down[8:16],
                                     experts_held=(8, 8), **kw)
        held_only = w_down.at[:8].set(0.0).at[16:].set(0.0)
        want, all_tokens = moe.dropless_moe(x, router, w_gate, w_up, held_only, **kw)
    rows = int(np.asarray(tokens).sum())
    assert {"within_the_pass": 0 < rows <= 96, "more_held_than_the_pass_takes": rows == t * k,
            "nothing_held": rows == 0}[case], rows
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(all_tokens)[8:16])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert (rows == 0) == (np.abs(np.asarray(y)).max() == 0)


# the grouped product's served shapes (PR 54): name -> (m, groups, k, n, the output's item size,
# rows a group on an even load, the tiles the rule gives, the tiles until PR 54, the even-load
# fill under those and under the rule's). Where the two tiles are equal the shape was LEFT ALONE:
# its layer alone did not win 3% of its products' time (PERF.md section 5, PR 54). A "turn" is
# one of the held rows' loop (2,048 rows); ling3's and laguna's loops are idle on an even load
SERVED_PRODUCTS = {
    "ling3_pass_gate_up": (104448, 128, 2560, 768, 4, 544, (128, 2560, 768), (256, 1280, 768), 0.708, 0.850),
    "ling3_pass_down": (104448, 128, 768, 2560, 2, 544, (128, 768, 2560), (512, 768, 2048), 0.332, 0.850),
    "ling3_turn_gate_up": (2048, 128, 2560, 768, 4, 544, (128, 2560, 768), (256, 1280, 768), 0.727, 0.842),
    "ling3_turn_down": (2048, 128, 768, 2560, 4, 544, (256, 768, 2560), (512, 768, 2048), 0.357, 0.727),
    "laguna_pass_gate_up": (65280, 64, 3072, 1024, 4, 680, (128, 3072, 512), (256, 1536, 1024), 0.733, 0.850),
    "laguna_pass_down": (65280, 64, 1024, 3072, 2, 680, (128, 1024, 1536), (256, 1024, 2048), 0.550, 0.850),
    "laguna_turn_gate_up": (2048, 64, 3072, 1024, 4, 680, (128, 3072, 512), (256, 1536, 1024), 0.727, 0.842),
    "laguna_turn_down": (2048, 64, 1024, 3072, 4, 680, (512, 1024, 1536), (256, 1024, 2048), 0.545, 0.571),
    "kimi_turn_gate_up": (2048, 12, 7168, 2048, 4, 362, (256, 1792, 1024), (256, 1792, 1024), 0.615, 0.615),
    "kimi_turn_down": (2048, 12, 2048, 7168, 4, 362, (256, 2048, 1024), (256, 2048, 1024), 0.615, 0.615),
    "dsv32_turn_gate_up": (2048, 8, 7168, 2048, 4, 272, (256, 1792, 1024), (256, 1792, 1024), 0.533, 0.533),
    "dsv32_turn_down": (2048, 8, 2048, 7168, 4, 272, (256, 2048, 1024), (256, 2048, 1024), 0.533, 0.533),
    "keye_gate_up": (274432, 128, 2048, 768, 4, 2144, (256, 2048, 768), (256, 2048, 768), 0.905, 0.905),
    "keye_down": (274432, 128, 768, 2048, 2, 2144, (512, 768, 2048), (512, 768, 2048), 0.817, 0.817),
    "lfm2_gate_up": (139264, 32, 2048, 1792, 4, 4352, (256, 2048, 896), (256, 2048, 896), 1.000, 1.000),
    "lfm2_down": (139264, 32, 1792, 2048, 2, 4352, (256, 1792, 1024), (256, 1792, 1024), 1.000, 1.000),
}


def _even_fill(m, groups, n, rows_a_group, tiles):
    """Needed rows x columns over what the kernel's grid passes over, every
    group ``rows_a_group`` rows from row 0 on and cut at ``m``: a visit is one
    (row tile, group) pair and multiplies a whole ``tm x tn`` block of every
    output tile, the last one whole too where ``tn`` does not divide ``n``."""
    tm, _, tn = tiles
    ends = np.minimum(np.arange(1, groups + 1) * rows_a_group, m)
    starts = np.minimum(np.arange(groups) * rows_a_group, m)
    live = ends > starts
    visits = int((-(-ends[live] // tm) - starts[live] // tm).sum())
    return int((ends - starts).sum()) * n / (visits * tm * -(-n // tn) * tn)


@pytest.mark.parametrize("name", sorted(SERVED_PRODUCTS))
def test_the_grouped_product_s_tiles_at_every_served_shape(name):
    """``moe.grouped_tiles`` at the shapes the benchmark's six decoders serve:
    the output tile is whole 128-lane tiles that DIVIDE the width (ling3's
    down product computed 4,096 columns for 2,560), the contraction tile
    divides the contraction, the row tile the rows; the kernel's tiles stay
    within the 21 MiB reckoning and a weight tile within 4 MiB; a shape whose
    layer did not win keeps its tiles to the letter."""
    from psana_ray_tpu.parallel import moe

    m, groups, k, n, out_bytes, rows, want, before, fill_before, fill = SERVED_PRODUCTS[name]
    tm, tk, tn = tiles = moe.grouped_tiles(m, groups, k, n, out_bytes)
    assert tiles == want
    assert tn % 128 == 0 and n % tn == 0 and tk % 128 == 0 and k % tk == 0 and m % tm == 0
    assert tm >= 128
    assert moe._gmm_vmem(tm, tk, tn, out_bytes) <= moe.GMM_VMEM_BYTES
    assert 2 * tk * tn <= moe.WEIGHT_TILE_BYTES
    assert _even_fill(m, groups, n, rows, before) == pytest.approx(fill_before, abs=1e-3)
    assert _even_fill(m, groups, n, rows, tiles) == pytest.approx(fill, abs=1e-3) and fill >= fill_before


# m, the groups' sizes, k, n, the output's type: each meets a branch of the rule or of the kernel
GROUPED_PRODUCTS = {
    "uneven_groups_that_end_inside_a_tile": (512, [200, 56, 130, 126], 256, 384, jnp.float32),
    "an_empty_group_and_one_smaller_than_the_tile": (512, [0, 40, 300, 0, 172], 256, 384, jnp.bfloat16),
    "rows_past_the_held": (512, [150, 0, 90, 60], 256, 384, jnp.float32),
    "nothing_held": (256, [0, 0, 0], 128, 128, jnp.float32),
    "a_contraction_cut_in_four": (256, [100, 0, 156], 7168, 384, jnp.float32),
    "a_group_of_many_row_tiles": (4096, [2500, 1596], 128, 256, jnp.bfloat16),
    "a_row_tile_of_a_quarter_of_a_group": (2048, [700, 648, 700], 128, 384, jnp.float32),
    "an_output_cut_to_a_divisor": (256, [131, 125], 1024, 3072, jnp.bfloat16),
    "widths_of_no_whole_lane_tile": (48, [7, 0, 30, 11], 64, 32, jnp.float32),
    # columns of no whole lane tile beside a contraction of whole ones: the weight is read
    # TRANSPOSED, as the device lays it out (PR 64: the ungated experts' [2688, 1856])
    "a_weight_read_transposed": (256, [100, 0, 90, 66], 256, 96, jnp.float32),
    "a_weight_read_transposed_in_tiles_that_cover_its_width": (512, [200, 312], 4096, 800, jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(GROUPED_PRODUCTS))
def test_the_grouped_product_is_each_group_s_dense_product(name):
    """``moe._grouped_product`` (interpret mode here) under
    :func:`moe.grouped_tiles` against ``x[group] @ w[e]`` in float32 from the
    bf16 operands: groups that end inside a row tile, an empty group, a group
    smaller than the tile, a width of 3 x 128, the contraction whole and cut,
    a row tile of today's and of a quarter of a group. Rows past the held ones
    are no group's: the kernel leaves them unwritten and nothing is asked of
    them here (``_held_rows_ahead``'s sum ignores them: the NaN test above)."""
    from psana_ray_tpu.parallel import moe

    m, sizes, k, n, out_dtype = GROUPED_PRODUCTS[name]
    tm, tk, tn = moe.grouped_tiles(m, len(sizes), k, n, jnp.dtype(out_dtype).itemsize)
    assert {"a_contraction_cut_in_four": tk == 1792, "an_output_cut_to_a_divisor": tn == 1536,
            "a_group_of_many_row_tiles": tm == 512, "a_row_tile_of_a_quarter_of_a_group": tm == 128,
            "a_weight_read_transposed_in_tiles_that_cover_its_width": (tk, tn) == (4096, 128),  # 7 x 128 = 896
            }.get(name, (tk, tn) == (k, n)), (tm, tk, tn)
    rng = np.random.default_rng(len(name))
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)) * k ** -0.5, jnp.bfloat16)
    got = np.asarray(moe._grouped_product(x, w, jnp.asarray(sizes, jnp.int32), out_dtype,
                                          interpret=True).astype(jnp.float32))
    assert got.shape == (m, n)
    held, lo = sum(sizes), 0
    for e, rows in enumerate(sizes):
        want = np.asarray(x[lo:lo + rows], np.float32) @ np.asarray(w[e], np.float32)
        tol = 2e-5 if out_dtype == jnp.float32 else 2 ** -7  # bf16 rounds the stored sum once
        np.testing.assert_allclose(got[lo:lo + rows], want, rtol=tol, atol=tol)
        lo += rows
    assert lo == held and np.isfinite(got[:held]).all()


@pytest.mark.parametrize("name", sorted(n for n in SERVED_PRODUCTS if n.endswith("_gate_up")) + ["nemotron3_pass_up"])
def test_the_up_product_that_ends_in_the_activation_keeps_the_tiles_of_the_product_it_was(name):
    """PR 65: an expert's up product writes its hidden rows in the activations'
    type (2 bytes) and, where the expert is gated, reads a float32 tile of the
    gate's product beside its output's: 12 bytes of VMEM an output element in
    two buffers where the float32 product took 8. No served shape's row tile
    halves for it (lfm2's (256, 2048, 896) takes 13.1 MB of the 21 allowed),
    so gate and up run in the same tiles, the ones PR 54 measured."""
    from psana_ray_tpu.parallel import moe

    m, groups, k, n, _, _, want, *_ = SERVED_PRODUCTS.get(
        name, (156672, 64, 2688, 1856, 4, 1632, (128, 2688, 640)))  # PR 64's ungated experts: no gate
    gated = name != "nemotron3_pass_up"
    assert moe.grouped_tiles(m, groups, k, n, 2, gated) == want == moe.grouped_tiles(m, groups, k, n, 4)
    assert moe._gmm_vmem(*want, 2, gated) <= moe.GMM_VMEM_BYTES
    assert moe._gmm_vmem(*want, 2, True) - moe._gmm_vmem(*want, 2) == 2 * 4 * want[0] * want[2]
    if name == "lfm2_gate_up":
        assert round(moe._gmm_vmem(*want, 2, True) / 1e6, 1) == 13.1


# m, the groups' sizes, k, n, gated, the operands' type, the hidden rows' type: each meets a branch of
# the kernel's last step or of what stands around it
HIDDEN_PRODUCTS = {
    "gated_groups_that_end_inside_a_tile": (512, [200, 56, 130, 126], 256, 384, True, jnp.bfloat16, jnp.bfloat16),
    "ungated_groups_that_end_inside_a_tile": (512, [200, 56, 130, 126], 256, 384, False, jnp.bfloat16, jnp.bfloat16),
    # tm 128: the second tile holds the end of group 0, all of group 1 and the start of group 2
    "a_row_tile_three_groups_share": (2048, [700, 20, 648, 680], 128, 384, True, jnp.bfloat16, jnp.bfloat16),
    "an_empty_group_first_and_between": (512, [0, 40, 300, 0, 172], 256, 384, True, jnp.bfloat16, jnp.bfloat16),
    "rows_past_the_last_group": (512, [150, 0, 90, 60], 256, 384, True, jnp.bfloat16, jnp.bfloat16),
    "ungated_rows_past_the_last_group": (512, [150, 0, 90, 60], 256, 384, False, jnp.bfloat16, jnp.bfloat16),
    "nothing_held": (256, [0, 0, 0], 128, 128, True, jnp.bfloat16, jnp.bfloat16),
    # columns of no whole lane tile beside a contraction of whole ones: the weight read TRANSPOSED
    "gated_a_weight_read_transposed": (256, [100, 0, 90, 66], 256, 96, True, jnp.bfloat16, jnp.bfloat16),
    "ungated_a_weight_read_transposed": (256, [100, 0, 90, 66], 256, 96, False, jnp.bfloat16, jnp.bfloat16),
    # ... in tiles of 128 that cover 800 columns with 896: the last output tile is cut
    "ungated_transposed_in_tiles_that_cover_its_width": (512, [200, 312], 4096, 800, False, jnp.bfloat16, jnp.bfloat16),
    "gated_a_width_of_no_whole_lane_tile": (48, [7, 0, 30, 11], 64, 32, True, jnp.bfloat16, jnp.bfloat16),
    "a_contraction_cut_in_four": (256, [100, 0, 156], 7168, 384, True, jnp.bfloat16, jnp.bfloat16),
    # 4,100 columns in tiles of 2,048: the third holds four of them and whatever lies past the array
    "a_contraction_whose_last_tile_is_cut": (64, [30, 34], 4100, 512, True, jnp.bfloat16, jnp.bfloat16),
    "float32_operands": (512, [200, 56, 130, 126], 256, 384, True, jnp.float32, jnp.bfloat16),
    "ungated_float32_operands": (512, [200, 56, 130, 126], 256, 384, False, jnp.float32, jnp.bfloat16),
    "float32_operands_and_hidden_rows": (512, [150, 0, 90, 60], 256, 384, True, jnp.float32, jnp.float32),
}


@pytest.mark.parametrize("name", sorted(HIDDEN_PRODUCTS))
def test_the_up_product_s_last_step_is_hidden_rows_over_each_group_s_dense_products(name):
    """``moe._expert_hidden`` (PR 65; interpret mode here): the gate's grouped
    product float32, then ``moe.gmm``, the up product whose last contraction
    step stores ``silu(gate) * acc`` (``relu(acc)^2`` where the experts have no
    gate) rounded once — against :func:`moe.hidden_rows`, the one statement of
    the form, over ``x[group] @ w[e]`` in plain ``jnp``. Operands of small
    integers times a power of two: every product is exact in float32 whatever
    the order of its sum, so kernel and reference round the SAME float32
    number and the hidden rows are equal to the bit. Rows past the last group
    are no group's: left unwritten, and nothing is asked of them."""
    from psana_ray_tpu.parallel import moe

    m, sizes, k, n, gated, operand, hidden = HIDDEN_PRODUCTS[name]
    rng = np.random.default_rng(len(name))
    x = jnp.asarray(rng.integers(-3, 4, (m, k)), operand)
    w_gate, w_up = (jnp.asarray(rng.integers(-2, 3, (len(sizes), k, n)) * 2.0 ** -5, operand) for _ in "gu")
    tm, tk, tn = moe._tiled(x, w_up, hidden, gated)[2]
    assert {"a_row_tile_three_groups_share": tm == 128, "a_contraction_cut_in_four": tk == 1792,
            "a_contraction_whose_last_tile_is_cut": (tk, k % tk) == (2048, 4),
            "ungated_transposed_in_tiles_that_cover_its_width": (tn, n % tn) == (128, 32)}.get(name, True), (tm, tk, tn)
    assert moe._tiled(x, w_up, hidden, gated)[1] == ("transposed" in name)
    got = moe._expert_hidden(x, w_gate if gated else None, w_up, jnp.asarray(sizes, jnp.int32), hidden,
                             interpret=True)
    assert got.shape == (m, n) and got.dtype == hidden
    lo = 0
    for e, rows in enumerate(sizes):
        group = x[lo:lo + rows].astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = moe.hidden_rows(lambda w: group @ w[e].astype(jnp.float32),
                                   w_gate if gated else None, w_up).astype(hidden)
        np.testing.assert_array_equal(np.asarray(got[lo:lo + rows].astype(jnp.float32)),
                                      np.asarray(want.astype(jnp.float32)))
        lo += rows
    assert lo == sum(sizes) and np.isfinite(np.asarray(got[:lo].astype(jnp.float32))).all()
    if lo:  # something was computed: the form is not the plain product
        assert np.abs(np.asarray(got[:lo].astype(jnp.float32))).max() > 0
