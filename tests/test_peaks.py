"""Peak extraction + CXI writer: synthetic frames with known peak positions
round-trip through find_peaks -> CXI (VERDICT r1 next-round item #10; the
reference names this mission in its packaging, setup.py:11, but ships none
of it)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dense_peaks import dense_find_peaks, dense_local_maxima
from psana_ray_tpu.models.peaks import (
    CxiWriter,
    _local_maxima,
    find_peaks,
    read_cxi_peaks,
    unpad_peaks,
)


def _logits_with_peaks(h, w, centers, hot=8.0, cold=-8.0):
    """Logit map: `cold` everywhere, `hot` bumps at the given centers with
    a slightly dimmer ring so the local-max rule is actually exercised."""
    z = np.full((h, w), cold, np.float32)
    for (cy, cx) in centers:
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                z[cy + dy, cx + dx] = hot - 2.0 * (abs(dy) + abs(dx))
    return z


class TestFindPeaks:
    def test_recovers_known_positions(self):
        centers = [(5, 7), (20, 33), (40, 12)]
        z = _logits_with_peaks(48, 48, centers)
        yx, score, n = jax.jit(find_peaks, static_argnums=(1,))(z[None], 16)
        assert int(n[0]) == 3
        got = {tuple(map(int, p)) for p in np.asarray(yx[0][: int(n[0])])}
        assert got == set(centers)
        assert np.all(np.asarray(score[0][:3]) > 0.9)

    def test_padded_fixed_shapes(self):
        z = _logits_with_peaks(32, 32, [(10, 10)])
        yx, score, n = find_peaks(z[None], max_peaks=8)
        assert yx.shape == (1, 8, 2) and score.shape == (1, 8)
        assert int(n[0]) == 1
        assert np.all(np.asarray(yx[0][1:]) == -1)  # padding marked

    def test_threshold_suppresses_background(self):
        z = np.zeros((1, 16, 16), np.float32)  # sigmoid=0.5 everywhere
        _, _, n = find_peaks(z, max_peaks=8, threshold=0.6)
        assert int(n[0]) == 0

    def test_plateau_yields_single_peak(self):
        z = np.full((1, 16, 16), -8.0, np.float32)
        z[0, 4:6, 4:6] = 6.0  # 2x2 plateau — tie-broken to ONE peak
        _, _, n = find_peaks(z, max_peaks=8)
        assert int(n[0]) == 1


_JIT_FIND = jax.jit(find_peaks, static_argnums=(1, 2, 3))
_JIT_DENSE = jax.jit(dense_find_peaks, static_argnums=(1, 2, 3))


def _assert_same_as_dense(z, max_peaks, threshold=0.5, min_distance=1):
    """``find_peaks`` against the dense oracle: yx, score and n equal element
    for element, so the ORDER of equal scores is held too."""
    z = jnp.asarray(z, jnp.float32)
    got = _JIT_FIND(z, max_peaks, threshold, min_distance)
    want = _JIT_DENSE(z, max_peaks, threshold, min_distance)
    for name, g, w_ in zip(("yx", "score", "n"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_), err_msg=name)
    return tuple(np.asarray(g) for g in got)


def _flat(h, w, centers, hot=40.0, cold=-40.0):
    """One row: ``hot`` single pixels on ``cold``. sigmoid(40) is exactly 1.0
    in f32, so every center ties with every other."""
    z = np.full((1, h, w), cold, np.float32)
    for cy, cx in centers:
        z[0, cy, cx] = hot
    return z


# a row of blocks at min_distance 2 (3x3 blocks): A sits in the EARLIER
# block but later in raster order than B (a lower in-block row wins)
_A, _B, _C = (2, 1), (0, 5), (1, 10)


class TestBlockCandidates:
    """TopK runs over one candidate per (min_distance+1)^2 block; the result
    is the dense form's, ties and their order included (ISSUE 26)."""

    @pytest.mark.parametrize("hw", [(352, 384), (32, 128), (33, 47), (16, 16), (7, 5)])
    @pytest.mark.parametrize("d", [0, 1, 2, 3])
    def test_random_logits_agree_with_dense(self, d, hw):
        rng = np.random.default_rng(1000 * d + hw[0])
        z = rng.normal(size=(2, *hw)).astype(np.float32) * 3.0
        cap = 128 if hw[0] > 30 else 8
        _, _, n = _assert_same_as_dense(z, cap, 0.5, d)
        assert n.max() > 0

    @pytest.mark.parametrize("hw", [(32, 128), (33, 47), (20, 22)])
    @pytest.mark.parametrize("d", [0, 1, 2, 3])
    @pytest.mark.parametrize("levels", ["integers", "saturated"])
    def test_tied_logits_agree_with_dense(self, levels, d, hw):
        """Few distinct values: plateaus everywhere, equal peaks in every row
        of blocks, and with the cap at 8 the cut falls among equals."""
        rng = np.random.default_rng(7 * d + hw[1])
        z = rng.normal(size=(3, *hw)).astype(np.float32) * 2.0
        z = np.round(z) if levels == "integers" else np.where(z > 1.0, 40.0, -40.0)
        for cap in (8, 128):
            _assert_same_as_dense(z.astype(np.float32), cap, 0.5, d)

    def test_plateaus_of_equal_logits(self):
        z = np.full((1, 30, 40), -8.0, np.float32)
        z[0, 4:9, 4:7] = 6.0  # each plateau elects its first pixel alone
        z[0, 4:6, 20:31] = 6.0
        z[0, 20:23, 10:12] = 6.0
        yx, score, n = _assert_same_as_dense(z, 16, 0.5, 2)
        assert [tuple(p) for p in yx[0, : n[0]]] == [(4, 4), (4, 20), (20, 10)]  # raster order
        assert len(set(score[0, :3])) == 1

    def test_probability_exactly_one_everywhere(self):
        """A saturated map is ONE plateau: each pixel but the first is beaten
        by an earlier equal neighbour."""
        z = np.full((2, 32, 128), 40.0, np.float32)
        yx, score, n = _assert_same_as_dense(z, 8, 0.5, 2)
        assert (n == 1).all() and (score[:, 0] == 1.0).all()
        assert (yx[:, 0] == 0).all()

    def test_later_raster_peak_in_the_earlier_block_comes_second(self):
        yx, _, n = _assert_same_as_dense(_flat(12, 18, [_A, _B]), 8, 0.5, 2)
        assert n[0] == 2
        assert [tuple(p) for p in yx[0, :2]] == [_B, _A]

    @pytest.mark.parametrize("cap,kept", [(1, [_B]), (2, [_B, _C]), (3, [_B, _C, _A])])
    def test_equal_scores_straddling_the_cap(self, cap, kept):
        """Row over its cap, the K-th place among equals: the cut keeps the
        lowest raster indices, not the lowest block indices."""
        yx, score, n = _assert_same_as_dense(_flat(12, 18, [_A, _B, _C]), cap, 0.5, 2)
        assert n[0] == cap and (score[0] == 1.0).all()
        assert [tuple(p) for p in yx[0]] == kept

    def test_cut_among_equals_below_a_brighter_peak_and_across_block_rows(self):
        z = _flat(12, 18, [_A, _B, _C, (7, 2), (6, 16)], hot=3.0, cold=-8.0)
        z[0, 10, 9] = 5.0  # the one brighter peak, in the LAST row of blocks
        for cap in range(1, 8):
            yx, _, n = _assert_same_as_dense(z, cap, 0.5, 2)
            want = [(10, 9), _B, _C, _A, (6, 16), (7, 2)][:cap]
            assert [tuple(p) for p in yx[0, : n[0]]] == want

    @pytest.mark.parametrize("hw", [(12, 18), (13, 17), (14, 19)])
    @pytest.mark.parametrize("d", [1, 2, 3])
    def test_peaks_on_every_border_and_corner(self, d, hw):
        h, w = hw
        centers = [(0, 0), (0, w - 1), (h - 1, 0), (h - 1, w - 1),
                   (0, w // 2), (h - 1, w // 2), (h // 2, 0), (h // 2, w - 1)]
        z = np.full((1, h, w), -8.0, np.float32)
        for i, (cy, cx) in enumerate(centers):
            z[0, cy, cx] = 8.0 - 0.25 * i  # distinct: the order is by score
        yx, _, n = _assert_same_as_dense(z, 16, 0.5, d)
        assert [tuple(p) for p in yx[0, : n[0]]] == centers

    def test_row_below_threshold_is_all_padding(self):
        rng = np.random.default_rng(3)
        z = rng.normal(size=(3, 32, 128)).astype(np.float32)
        z[1] = -6.0 - np.abs(z[1])  # nothing in row 1 reaches 0.5
        yx, score, n = _assert_same_as_dense(z, 8, 0.5, 2)
        assert n[1] == 0 and n[0] == 8 and n[2] == 8
        assert (yx[1] == -1).all() and (score[1] == 0.0).all()

    @pytest.mark.parametrize("hw,d", [((3, 3), 2), ((7, 5), 2), ((7, 5), 1), ((1, 1), 3), ((2, 9), 0)])
    def test_max_peaks_above_the_candidate_count(self, hw, d):
        rng = np.random.default_rng(11)
        z = rng.normal(size=(2, *hw)).astype(np.float32) * 3.0
        yx, score, n = _assert_same_as_dense(z, 32, 0.5, d)
        assert yx.shape == (2, 32, 2) and score.shape == (2, 32)
        assert (n <= -(-hw[0] // (d + 1)) * -(-hw[1] // (d + 1))).all()

    @pytest.mark.parametrize("d", [1, 2, 3])
    def test_no_block_holds_two_survivors(self, d):
        """What lets TopK run on one candidate per block: the full-resolution
        mask (block 1) never has two survivors within Chebyshev distance d,
        so none of the (d+1)^2 blocks of any grid alignment holds two."""
        rng = np.random.default_rng(d)
        z = np.round(rng.normal(size=(4, 31, 46)) * 2.0).astype(np.float32)
        score, where = _local_maxima(jnp.asarray(z), 0.5, d, 1)
        mask = np.asarray(score) > 0.0
        is_peak, _ = dense_local_maxima(jnp.asarray(z), 0.5, d)
        np.testing.assert_array_equal(mask, np.asarray(is_peak))
        raster = np.broadcast_to(np.arange(31 * 46).reshape(31, 46), mask.shape)
        np.testing.assert_array_equal(np.asarray(where)[mask], raster[mask])
        assert mask.sum() > 40
        b = d + 1
        for oy in range(b):
            for ox in range(b):
                m = np.pad(mask, ((0, 0), (oy, 2 * b), (ox, 2 * b)))
                m = m[:, : m.shape[1] // b * b, : m.shape[2] // b * b]
                per_block = m.reshape(4, m.shape[1] // b, b, m.shape[2] // b, b).sum(axis=(2, 4))
                assert per_block.max() == 1

    def test_top_k_operand_is_one_candidate_per_block(self):
        """The cell's own step (benchmark/configs/peaknet_sfx_epix10k2m.json:
        epix10k2M panels, min_distance 2) hands ``top_k`` 118 x 128 = 15,104
        scores a row — a refactor cannot put the whole 135,168-pixel map back."""
        import json
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "benchmark", "configs", "peaknet_sfx_epix10k2m.json")) as f:
            cfg = json.load(f)
        det = cfg["detector"]
        step = jax.jit(lambda lg: find_peaks(
            lg, max_peaks=int(cfg["panel_max_peaks"]),
            threshold=float(cfg["peak_threshold"]), min_distance=int(cfg["min_distance"])))
        logits = jax.ShapeDtypeStruct(
            (int(det["panels"]), int(det["height"]), int(det["width"]), 1), jnp.float32)

        def top_k_widths(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "top_k":
                    yield eqn.invars[0].aval.shape[-1]
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from top_k_widths(sub)

        assert list(top_k_widths(jax.make_jaxpr(step)(logits).jaxpr)) == [15104]


def _scenes(h, w, rng):
    """Six rows of one ``[6, h, w]`` logit batch, each a case the packed
    form could get wrong: random; few distinct values (plateaus over every
    packed-pixel and super-block border); probability exactly 1.0 in
    plateaus; distinct peaks on every frame edge and corner plus plateaus
    ACROSS rows/columns 5|6|7 and 11|12|13 (packed-pixel borders for r 2
    and 4, block borders for d 1-3, the super-block border at 12); nothing
    over the threshold; and more equal single pixels than any cap."""
    z = np.full((6, h, w), -8.0, np.float32)
    z[0] = rng.normal(size=(h, w)) * 3.0
    z[1] = np.round(rng.normal(size=(h, w)) * 2.0)
    z[2] = np.where(rng.normal(size=(h, w)) > 1.0, 40.0, -40.0)
    edges = [(0, 0), (0, w - 1), (h - 1, 0), (h - 1, w - 1),
             (0, w // 2), (h - 1, w // 2), (h // 2, 0), (h // 2, w - 1)]
    for i, (cy, cx) in enumerate(edges):
        z[3, cy, cx] = 8.0 - 0.25 * i
    z[3, 5:8, 11:14] = 6.0
    z[3, 11:14, 5:8] = 6.0
    z[3, 12, 20:28] = 6.0
    z[4] = -6.0 - np.abs(rng.normal(size=(h, w)))
    z[5] = -40.0
    z[5, 1::5, 2::7] = 40.0
    return z


class TestPackedLogits:
    """``find_peaks(..., s2d=r)`` reads the head's packed ``[N, H/r, W/r,
    r*r]`` logits by static slices and never forms the full-resolution
    map; its result is the dense form's, element for element (ISSUE 41)."""

    @pytest.mark.parametrize("hw", [(352, 384), (32, 128), (44, 52)])
    @pytest.mark.parametrize("d", [0, 1, 2, 3])
    @pytest.mark.parametrize("r", [1, 2, 4])
    def test_packed_form_agrees_with_dense(self, r, d, hw):
        """Sizes the super-block lcm(r, d+1) divides (32 x 128 at d 1, 3)
        and does not (352 x 384 at d 2; 44 x 52: 11 x 13 packed pixels at
        r 4, odd in blocks at every d > 0)."""
        from psana_ray_tpu.models.unet_tpu import space_to_depth

        z = _scenes(*hw, np.random.default_rng(100 * r + 10 * d + hw[0]))
        cap = 128 if hw[0] > 100 else 8
        packed = space_to_depth(jnp.asarray(z)[..., None], r)
        got = jax.jit(lambda y: find_peaks(y, cap, 0.5, d, s2d=r))(packed)
        want = _JIT_DENSE(jnp.asarray(z), cap, 0.5, d)
        for name, g, w_ in zip(("yx", "score", "n"), got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_), err_msg=name)
        n = np.asarray(got[2])
        assert n[4] == 0 and n[0] == cap and n[5] == cap  # empty row; rows over the cap
        assert (np.asarray(got[1])[5] == 1.0).all()  # the cut falls among equals
        assert n[3] >= 8  # every edge and corner peak is there

    @pytest.mark.parametrize("r", [2, 4])
    def test_packed_call_equals_the_call_on_the_unshuffled_map(self, r):
        """``find_peaks(depth_to_space(y, r))`` and the packed call on ``y``
        give the same bits: one algorithm, ``r`` a property of the model."""
        from psana_ray_tpu.models.unet_tpu import depth_to_space

        rng = np.random.default_rng(r)
        y = jnp.asarray(np.round(rng.normal(size=(3, 24, 20, r * r)) * 4.0) / 2.0, jnp.float32)
        got = jax.jit(lambda a: find_peaks(a, 16, 0.5, 2, s2d=r))(y)
        want = jax.jit(lambda a: find_peaks(depth_to_space(a, r), 16, 0.5, 2))(y)
        for g, w_ in zip(got, want):
            assert np.asarray(g).tobytes() == np.asarray(w_).tobytes()
        assert int(np.asarray(got[2]).min()) == 16

    def test_packed_logits_must_carry_one_class(self):
        with pytest.raises(ValueError, match="packed logits"):
            find_peaks(jnp.zeros((1, 8, 8, 8)), s2d=2)
        with pytest.raises(ValueError, match="packed logits"):
            find_peaks(jnp.zeros((1, 8, 8)), s2d=2)


class TestPackedKernel:
    """``ops/peak_nms.packed_local_maxima``, the one-pass form the TPU runs
    on packed probabilities, in Pallas interpret mode: the plain form's
    candidates, and through ``find_peaks`` the dense form's peaks."""

    @pytest.mark.parametrize("hw", [(48, 128), (44, 52)])
    @pytest.mark.parametrize("d", [0, 1, 2, 3])
    @pytest.mark.parametrize("r", [2, 4])
    def test_kernel_candidates_and_peaks(self, r, d, hw, monkeypatch):
        import psana_ray_tpu.models.peaks as peaks
        from psana_ray_tpu.models.unet_tpu import space_to_depth
        from psana_ray_tpu.ops.peak_nms import packed_local_maxima

        def kernel(logits, threshold, d_, b, r_=1):
            assert (b, r_) == (d + 1, r)
            return packed_local_maxima(
                jax.nn.sigmoid(logits.astype(jnp.float32)), threshold=threshold, d=d_, r=r_,
                interpret=True)

        z = _scenes(*hw, np.random.default_rng(7 * r + d))
        packed = space_to_depth(jnp.asarray(z)[..., None], r)
        plain = _local_maxima(packed, 0.5, d, d + 1, r)
        for name, g, w_ in zip(("score", "where"), kernel(packed, 0.5, d, d + 1, r), plain):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_), err_msg=name)
        monkeypatch.setattr(peaks, "_local_maxima", kernel)
        got = find_peaks(packed, 8, 0.5, d, s2d=r)
        for name, g, w_ in zip(("yx", "score", "n"), got, _JIT_DENSE(jnp.asarray(z), 8, 0.5, d)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_), err_msg=name)

    def test_kernel_takes_whole_tiles_only(self):
        from psana_ray_tpu.ops.peak_nms import takes

        assert takes((256, 176, 192, 4), 2, 2) and takes((128, 88, 96, 16), 4, 2)
        assert not takes((16, 176, 192, 4), 2, 2)  # a sixteenth of a lane tile
        assert not takes((128, 22, 26, 4), 2, 2)  # 26 packed columns: not whole sublane tiles
        assert not takes((128, 352, 384, 1), 1, 2) and not takes((128, 352, 384), 1, 2)  # nothing packed


class TestCxiRoundtrip:
    def test_roundtrip(self, tmp_path):
        centers = [(5, 7), (20, 33)]
        z = jnp.asarray(_logits_with_peaks(48, 48, centers)[None])
        yx, score, n = find_peaks(z, max_peaks=16)
        peaks = unpad_peaks(
            yx, score, n,
            event_idx=np.array([42]), shard_rank=np.array([3]),
            photon_energy=np.array([9.5]),
        )
        path = str(tmp_path / "peaks.cxi")
        with CxiWriter(path, max_peaks=16) as wtr:
            wtr.append(peaks)
            assert wtr.n_events == 1
        n_back, x, y, inten, ev = read_cxi_peaks(path)
        assert n_back[0] == 2
        got = {(int(yy), int(xx)) for yy, xx in zip(y[0][:2], x[0][:2])}
        assert got == set(centers)
        assert ev[0] == 42
        assert np.all(inten[0][:2] > 0.9)

    def test_append_batches(self, tmp_path):
        path = str(tmp_path / "multi.cxi")
        z = jnp.asarray(
            np.stack([_logits_with_peaks(32, 32, [(8, 8)]),
                      _logits_with_peaks(32, 32, [(4, 4), (20, 20)])])
        )
        yx, score, n = find_peaks(z, max_peaks=8)
        with CxiWriter(path, max_peaks=8) as wtr:
            wtr.append(unpad_peaks(yx, score, n))
            wtr.append(unpad_peaks(yx, score, n))
            assert wtr.n_events == 4
        n_back, *_ = read_cxi_peaks(path)
        assert list(n_back) == [1, 2, 1, 2]


class TestPeakMetrics:
    """peak_metrics + the synthetic source's planted ground truth
    (VERDICT r3 #5: the s2d quality numbers need an oracle)."""

    def test_event_with_truth_matches_event(self):
        from psana_ray_tpu.sources import SyntheticSource

        src = SyntheticSource(num_events=2, detector_name="smoke_a", seed=7)
        d1, e1 = src.event(1)
        d2, e2, truth = src.event_with_truth(1)
        np.testing.assert_array_equal(d1, d2)  # identical rng consumption
        assert e1 == e2
        assert truth.shape[1] == 4
        assert len(truth) >= 1
        p, h, w = src.spec.frame_shape
        assert (truth[:, 0] < p).all()
        assert (truth[:, 1] < h).all() and (truth[:, 2] < w).all()

    def test_truth_peaks_are_in_the_frame(self):
        from psana_ray_tpu.sources import SyntheticSource

        src = SyntheticSource(num_events=1, detector_name="smoke_a", seed=3)
        data, _, truth = src.event_with_truth(0)
        # a bright plant must actually be bright at its center
        bright = truth[truth[:, 3] > 200]
        for pi, cy, cx, amp in bright:
            v = data[int(pi), int(round(cy)), int(round(cx))]
            assert v > 50, (pi, cy, cx, amp, v)

    def test_metrics_exact_match(self):
        from psana_ray_tpu.models.peaks import peak_metrics

        pred_yx = np.full((1, 4, 2), -1, np.int32)
        pred_yx[0, :2] = [[10, 20], [30, 40]]
        truth = [np.asarray([[0, 10.4, 19.8, 100.0], [0, 29.9, 40.2, 100.0]])]
        m = peak_metrics(pred_yx, np.asarray([2]), truth, tolerance=2.0)
        assert m["recall"] == 1.0 and m["precision"] == 1.0

    def test_metrics_miss_and_false_positive(self):
        from psana_ray_tpu.models.peaks import peak_metrics

        pred_yx = np.full((1, 4, 2), -1, np.int32)
        pred_yx[0, :2] = [[10, 20], [90, 90]]  # second is spurious
        truth = [np.asarray([[0, 10, 20, 100.0], [0, 50, 50, 100.0]])]  # second missed
        m = peak_metrics(pred_yx, np.asarray([2]), truth, tolerance=2.0)
        assert m["recall"] == 0.5 and m["precision"] == 0.5

    def test_metrics_one_to_one_matching(self):
        from psana_ray_tpu.models.peaks import peak_metrics

        # two truth peaks near ONE prediction: only one may claim it
        pred_yx = np.full((1, 4, 2), -1, np.int32)
        pred_yx[0, :1] = [[10, 10]]
        truth = [np.asarray([[0, 10, 10, 100.0], [0, 11, 10, 100.0]])]
        m = peak_metrics(pred_yx, np.asarray([1]), truth, tolerance=3.0)
        assert m["n_matched"] == 1
        assert m["recall"] == 0.5 and m["precision"] == 1.0

    def test_min_amplitude_drops_subthreshold_truth(self):
        from psana_ray_tpu.models.peaks import peak_metrics

        pred_yx = np.full((1, 2, 2), -1, np.int32)
        truth = [np.asarray([[0, 10, 10, 20.0]])]  # weak plant, no prediction
        m = peak_metrics(pred_yx, np.asarray([0]), truth, min_amplitude=50.0)
        assert m["n_truth"] == 0 and m["recall"] == 0.0

    def test_split_truth_by_panel(self):
        from psana_ray_tpu.models.peaks import split_truth_by_panel

        truth = np.asarray([[0, 1, 2, 9.0], [2, 3, 4, 9.0], [0, 5, 6, 9.0]])
        parts = split_truth_by_panel(truth, 3)
        assert [len(p) for p in parts] == [2, 0, 1]

    def test_find_peaks_recovers_planted_truth(self):
        """End-to-end oracle check WITHOUT a model: sigmoid-space logits
        built directly from the calibrated frame must recover the bright
        planted peaks — validates the truth/metric plumbing itself."""
        import jax.numpy as jnp

        from psana_ray_tpu.models.peaks import (
            find_peaks,
            peak_metrics,
            split_truth_by_panel,
        )
        from psana_ray_tpu.sources import SyntheticSource

        # sparse plants: on the tiny smoke panels a dense field overlaps
        # into merged maxima, which tests geometry, not the plumbing
        src = SyntheticSource(
            num_events=1, detector_name="smoke_a", seed=11, peak_count=4
        )
        data, _, truth = src.event_with_truth(0)
        p = src.spec.frame_shape[0]
        # "perfect segmentation": logit rises with intensity, threshold at
        # 50 ADU. Scaled so sigmoid cannot saturate to exactly 1.0 in f32
        # — a saturated plateau ties every pixel and the raster tie-break
        # elects the plateau's corner, not the peak center
        logits = jnp.asarray((data - 50.0) * 0.01)[..., None]
        yx, score, n = find_peaks(logits, max_peaks=64, min_distance=2)
        m = peak_metrics(
            np.asarray(yx), np.asarray(n), split_truth_by_panel(truth, p),
            tolerance=3.0, min_amplitude=100.0,
        )
        assert m["recall"] >= 0.9, m

    def test_detection_of_ignored_truth_is_not_a_false_positive(self):
        from psana_ray_tpu.models.peaks import peak_metrics

        # one strong plant (matched) + one correctly-detected WEAK plant:
        # the weak detection must not count against precision
        pred_yx = np.full((1, 4, 2), -1, np.int32)
        pred_yx[0, :2] = [[10, 10], [40, 40]]
        truth = [np.asarray([[0, 10, 10, 500.0], [0, 40, 40, 60.0]])]
        m = peak_metrics(pred_yx, np.asarray([2]), truth, min_amplitude=100.0)
        assert m["n_truth"] == 1 and m["n_matched"] == 1
        assert m["precision"] == 1.0 and m["recall"] == 1.0
