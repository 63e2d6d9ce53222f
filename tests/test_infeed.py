"""Infeed: fixed-shape batching, pad+mask tails, device prefetch, end-to-end
queue->mesh flow on the 8-device CPU mesh."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from psana_ray_tpu.infeed import DevicePrefetcher, FrameBatcher, InfeedPipeline
from psana_ray_tpu.infeed.batcher import batches_from_queue
from psana_ray_tpu.infeed.multihost import batch_sharding, make_global_batch
from psana_ray_tpu.parallel import create_mesh
from psana_ray_tpu.records import EndOfStream, FrameRecord
from psana_ray_tpu.transport import RingBuffer


def _rec(i, shape=(2, 8, 16), rank=0):
    return FrameRecord(rank, i, np.full(shape, float(i), np.float32), 9.0 + i)


class TestBatcher:
    def test_emits_full_batches(self):
        b = FrameBatcher(batch_size=4)
        outs = [b.push(_rec(i)) for i in range(9)]
        batches = [o for o in outs if o is not None]
        assert len(batches) == 2
        assert batches[0].frames.shape == (4, 2, 8, 16)
        assert batches[0].valid.tolist() == [1, 1, 1, 1]
        assert batches[1].event_idx.tolist() == [4, 5, 6, 7]
        assert b.pending == 1

    def test_flush_pads_tail(self):
        b = FrameBatcher(batch_size=4)
        for i in range(2):
            b.push(_rec(i))
        tail = b.flush()
        assert tail.frames.shape == (4, 2, 8, 16)
        assert tail.valid.tolist() == [1, 1, 0, 0]
        assert tail.num_valid == 2
        np.testing.assert_array_equal(tail.frames[2:], 0)  # padding rows zero
        assert b.flush() is None

    def test_metadata_alignment(self):
        b = FrameBatcher(batch_size=3)
        b.push(_rec(10, rank=5))
        b.push(_rec(11, rank=6))
        out = b.push(_rec(12, rank=7))
        assert out.shard_rank.tolist() == [5, 6, 7]
        assert out.photon_energy.tolist() == pytest.approx([19.0, 20.0, 21.0])

    def test_shape_lock(self):
        b = FrameBatcher(batch_size=2)
        b.push(_rec(0))
        with pytest.raises(ValueError, match="locked shape"):
            b.push(_rec(1, shape=(2, 8, 17)))

    def test_rejects_bad_batch_size(self):
        with pytest.raises(ValueError):
            FrameBatcher(batch_size=0)


class TestBatchesFromQueue:
    def test_drains_until_eos(self):
        q = RingBuffer(maxsize=64)
        for i in range(10):
            q.put(_rec(i))
        q.put(EndOfStream(total_events=10))
        batches = list(batches_from_queue(q, batch_size=4, poll_interval_s=0.001))
        assert [b.num_valid for b in batches] == [4, 4, 2]
        all_idx = np.concatenate([b.event_idx[b.valid.astype(bool)] for b in batches])
        assert all_idx.tolist() == list(range(10))

    def test_max_wait_stops_starved_stream(self):
        q = RingBuffer(maxsize=4)
        q.put(_rec(0))
        batches = list(
            batches_from_queue(q, batch_size=4, poll_interval_s=0.005, max_wait_s=0.02)
        )
        # tail flushed on starvation timeout even without EOS
        assert len(batches) == 1 and batches[0].num_valid == 1

    def test_concurrent_producer(self):
        q = RingBuffer(maxsize=8)

        def produce():
            for i in range(20):
                while not q.put(_rec(i)):
                    pass
            q.put(EndOfStream())

        t = threading.Thread(target=produce)
        t.start()
        batches = list(batches_from_queue(q, batch_size=8, poll_interval_s=0.001))
        t.join()
        assert sum(b.num_valid for b in batches) == 20


class _Pops:
    """A transport whose every pop is scripted: an int is that many fresh
    frames, 0 a starved poll; past the script, the end of the stream."""

    def __init__(self, script):
        self.script = list(script)
        self.sent = 0

    def get_batch(self, n, timeout=None):
        if not self.script:
            return [EndOfStream(total_events=self.sent)]
        k = self.script.pop(0)
        assert k <= n
        out = [_rec(self.sent + j) for j in range(k)]
        self.sent += k
        return out


class TestBetweenTurns:
    """``between_turns``: the consumer's own work on the batcher's thread,
    at the end of every turn that emitted no batch (ISSUE 35)."""

    @pytest.mark.parametrize(
        "script,want_calls,want_batches",
        [
            ([4, 4, 4], 0, 3),  # a batch every turn: never asked
            ([0, 0, 4, 0], 3, 1),  # every starved poll
            ([2, 2, 1, 3], 2, 2),  # frames that did not fill the arena
            ([3, 4, 1], 1, 2),  # a pop that completes a batch AND starts one emits: not asked
            ([4, 2], 1, 2),  # the turn that ends the stream flushes the tail: not asked again
        ],
        ids=["full-pops", "starved", "partial", "straddling", "eos-tail"],
    )
    def test_called_once_per_turn_without_a_batch(self, script, want_calls, want_batches):
        calls = []
        got = list(batches_from_queue(
            _Pops(script), 4, poll_interval_s=0.001, between_turns=lambda: calls.append(1),
        ))
        assert len(calls) == want_calls
        assert len(got) == want_batches
        idx = np.concatenate([b.event_idx[b.valid.astype(bool)] for b in got])
        assert idx.tolist() == list(range(sum(script)))  # and the stream is what it was

    def test_it_runs_on_the_loops_thread_outside_every_phase(self):
        from psana_ray_tpu.obs.profiling.stagetag import TAG_UNTAGGED, current_tag

        seen = []
        list(batches_from_queue(
            _Pops([0, 2, 2]), 4, poll_interval_s=0.001,
            between_turns=lambda: seen.append((threading.get_ident(), current_tag())),
        ))
        assert seen == [(threading.get_ident(), TAG_UNTAGGED)] * 2

    @pytest.mark.parametrize("script", [[0, 0, 4], [2, 2, 4]], ids=["starved", "partial"])
    def test_a_truthy_return_ends_iteration_as_stop_does(self, script):
        q = _Pops(script)
        got = list(batches_from_queue(q, 4, poll_interval_s=0.001, between_turns=lambda: True))
        assert got == []  # frames held are abandoned, not flushed: a cancellation
        assert len(q.script) == 2  # and nothing more was popped

    @pytest.mark.parametrize("answer", [None, False], ids=["found-nothing", "worked"])
    def test_a_wait_over_several_polls_starts_anew_after_its_work(self, answer):
        # its time is the consumer's, like time suspended at a yield: the
        # ``queue_wait`` that spans the empty polls must not cover it
        from psana_ray_tpu.utils.metrics import PipelineMetrics

        def hook():
            time.sleep(0.03)
            return answer

        m = PipelineMetrics()
        list(batches_from_queue(_Pops([0, 0, 4]), 4, poll_interval_s=0.001, metrics=m,
                                between_turns=hook))
        waits = m.stages.stat("queue_wait")._samples  # the frames' pop, then the end's
        assert len(waits) == 2
        assert (waits[0] < 0.03) == (answer is False), waits

    def test_what_it_raises_surfaces_from_the_iterator(self):
        def boom():
            raise OSError("disk full")

        it = batches_from_queue(_Pops([4, 1]), 4, poll_interval_s=0.001, between_turns=boom)
        assert next(it).num_valid == 4
        with pytest.raises(OSError, match="disk full"):
            next(it)

    def test_other_loops_pass_no_hook(self, monkeypatch):
        # InfeedPipeline runs what it ran: its call names no between_turns
        import psana_ray_tpu.infeed.pipeline as pipeline_mod

        seen = {}

        def spy(*a, **kw):
            seen.update(kw)
            return batches_from_queue(*a, **kw)

        monkeypatch.setattr(pipeline_mod, "batches_from_queue", spy)
        q = RingBuffer(maxsize=8)
        for i in range(4):
            q.put(_rec(i))
        q.put(EndOfStream(total_events=4))
        pipe = InfeedPipeline(q, batch_size=4, poll_interval_s=0.001)
        assert pipe.run(lambda b: b.frames) == 4
        assert seen and "between_turns" not in seen and "rows_landed" not in seen

    def test_without_a_hook_a_turn_is_what_it_was(self, monkeypatch):
        # nobody asks the batcher what landed, and the phases observed and
        # the batches yielded are those of a loop whose hooks do nothing
        from psana_ray_tpu.utils.metrics import PipelineMetrics

        script = [2, 2, 0, 1, 3, 3, 4, 0, 2]

        def turns(**hooks):
            m = PipelineMetrics()
            got = list(batches_from_queue(_Pops(script), 4, poll_interval_s=0.001, metrics=m, **hooks))
            phases = {name: m.stages.stat(name).count for name in m.stages.stages()}
            return phases, [(b.num_valid, b.event_idx.tolist(), b.valid.tolist()) for b in got]

        hooked = turns(between_turns=lambda: None, rows_landed=lambda *rows: None)
        monkeypatch.setattr(FrameBatcher, "landed", lambda self: pytest.fail("asked what landed"))
        bare = turns()
        assert bare == hooked
        pops = sum(1 for k in script if k) + 1  # the pops that brought something, and the end's
        assert bare[0] == {"queue_wait": pops, "decode": pops, "copy": pops}
        assert [n for n, _, _ in bare[1]] == [4, 4, 4, 4, 1]


class TestRowsLanded:
    """``rows_landed``: the rows of the current arena that were copied in
    since the consumer was last told, at the end of every turn that
    emitted no batch (ISSUE 43)."""

    @pytest.mark.parametrize(
        "script,want",
        [
            ([4, 4, 4], [[], [], []]),  # an arena filled in one turn is never named
            ([1, 1, 1, 1], [[(0, 1), (1, 2), (2, 3)]]),  # a trickle: all but the one that fills
            ([2, 2, 1, 3], [[(0, 2)], [(0, 1)]]),
            ([3, 4, 1], [[(0, 3)], []]),  # rows behind an emitted batch wait for a turn without one
            ([3, 4, 0, 0, 1], [[(0, 3)], [(0, 3)]]),  # which a starved poll is; told once
            ([4, 1, 0, 1], [[], [(0, 1), (1, 2)]]),  # the tail: its real rows, never its padding
        ],
        ids=["full-pops", "trickle", "partial", "straddling", "straddling-then-starved", "eos-tail"],
    )
    def test_each_row_below_the_fill_is_named_once_in_order(self, script, want):
        told, per_batch = [], []
        it = batches_from_queue(_Pops(script), 4, poll_interval_s=0.001,
                                rows_landed=lambda arena, lo, hi: told.append((arena, lo, hi)))
        for batch in it:
            # what was named since the previous batch is this batch's own arena
            assert all(arena is batch.frames for arena, _, _ in told)
            assert all(hi <= batch.num_valid for _, _, hi in told)
            per_batch.append([(lo, hi) for _, lo, hi in told])
            told.clear()
        assert per_batch == want and not told

    def test_it_follows_between_turns_on_the_loops_thread_outside_every_phase(self):
        from psana_ray_tpu.obs.profiling.stagetag import TAG_UNTAGGED, current_tag

        seen = []
        list(batches_from_queue(
            _Pops([0, 2, 0, 2]), 4, poll_interval_s=0.001,
            between_turns=lambda: seen.append("between"),
            rows_landed=lambda *rows: seen.append((threading.get_ident(), current_tag())),
        ))
        assert seen == ["between", "between", (threading.get_ident(), TAG_UNTAGGED), "between"]

    def test_a_between_turns_that_ends_the_iteration_is_not_followed(self):
        told = []
        got = list(batches_from_queue(_Pops([2, 2]), 4, poll_interval_s=0.001,
                                      between_turns=lambda: True, rows_landed=told.append))
        assert got == [] and told == []

    def test_a_wait_over_several_polls_starts_anew_after_rows_were_named(self):
        from psana_ray_tpu.utils.metrics import PipelineMetrics

        m = PipelineMetrics()
        # 3 + 2 straddle a batch; the first starved poll names the row behind it
        list(batches_from_queue(_Pops([3, 2, 0, 0, 3]), 4, poll_interval_s=0.001, metrics=m,
                                rows_landed=lambda *rows: time.sleep(0.03)))
        waits = m.stages.stat("queue_wait")._samples
        assert len(waits) == 4 and max(waits) < 0.03, waits

    def test_what_it_raises_surfaces_from_the_iterator(self):
        def boom(*rows):
            raise OSError("no device")

        it = batches_from_queue(_Pops([4, 1]), 4, poll_interval_s=0.001, rows_landed=boom)
        assert next(it).num_valid == 4
        with pytest.raises(OSError, match="no device"):
            next(it)


class TestDevicePrefetch:
    def test_batches_land_on_device(self):
        q = RingBuffer(maxsize=32)
        for i in range(8):
            q.put(_rec(i))
        q.put(EndOfStream())
        pf = DevicePrefetcher(batches_from_queue(q, 4, poll_interval_s=0.001))
        out = list(pf)
        assert len(out) == 2
        assert isinstance(out[0].frames, jax.Array)
        np.testing.assert_array_equal(np.asarray(out[0].valid), 1)

    def test_error_propagates(self):
        def gen():
            raise RuntimeError("source died")
            yield  # noqa

        pf = DevicePrefetcher(gen())
        with pytest.raises(RuntimeError, match="source died"):
            list(pf)

    def test_exhausted_iterator_keeps_raising(self):
        q = RingBuffer(maxsize=8)
        q.put(_rec(0))
        q.put(EndOfStream())
        pf = DevicePrefetcher(batches_from_queue(q, 1, poll_interval_s=0.001))
        assert len(list(pf)) == 1
        with pytest.raises(StopIteration):  # not a deadlock
            next(pf)

    def test_close_releases_thread_on_early_exit(self):
        q = RingBuffer(maxsize=64)
        for i in range(32):
            q.put(_rec(i))
        q.put(EndOfStream())
        pf = DevicePrefetcher(batches_from_queue(q, 4, poll_interval_s=0.001), prefetch_depth=2)
        next(pf)  # consume one, then abandon
        pf.close()
        assert not pf._thread.is_alive()
        with pytest.raises(StopIteration):
            next(pf)

    def test_num_valid_is_host_int_after_transfer(self):
        q = RingBuffer(maxsize=8)
        for i in range(3):
            q.put(_rec(i))
        q.put(EndOfStream())
        pf = DevicePrefetcher(batches_from_queue(q, 4, poll_interval_s=0.001))
        (batch,) = list(pf)
        assert isinstance(batch.num_valid, int) and batch.num_valid == 3

    def test_sharded_prefetch_on_mesh(self):
        mesh = create_mesh(("data", "model"), (8, 1))
        sharding = batch_sharding(mesh)
        q = RingBuffer(maxsize=32)
        for i in range(8):
            q.put(_rec(i))
        q.put(EndOfStream())
        pf = DevicePrefetcher(batches_from_queue(q, 8, poll_interval_s=0.001), sharding=sharding)
        (batch,) = list(pf)
        # rows split over the 8 data-axis devices
        assert len(batch.frames.sharding.device_set) == 8
        assert batch.frames.shape == (8, 2, 8, 16)


class TestPipelineEndToEnd:
    def test_jitted_consumer_over_mesh(self):
        mesh = create_mesh(("data", "model"), (4, 2))
        sharding = batch_sharding(mesh)
        q = RingBuffer(maxsize=64)
        for i in range(19):  # deliberately not a multiple of 8 -> padded tail
            q.put(_rec(i))
        q.put(EndOfStream(total_events=19))

        pipe = InfeedPipeline(q, batch_size=8, sharding=sharding, poll_interval_s=0.001)

        @jax.jit
        def step(frames, valid):
            # masked per-frame mean: padding rows contribute 0
            per = jnp.mean(frames, axis=(1, 2, 3)) * valid
            return jnp.sum(per)

        totals = []
        seen = pipe.run(lambda b: totals.append(step(b.frames, b.valid)))
        assert seen == 19
        # frames are constant = idx, so sum of per-frame means = sum(range(19))
        assert float(jnp.sum(jnp.stack(totals))) == pytest.approx(sum(range(19)))


class TestMultihostHelpers:
    def test_make_global_batch_single_process(self):
        mesh = create_mesh(("data", "model"), (8, 1))
        local = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        arr = make_global_batch(local, mesh)
        assert arr.shape == (8, 4)
        assert len(arr.sharding.device_set) == 8
        np.testing.assert_array_equal(np.asarray(arr), local)


class TestPipelineMetrics:
    def test_run_records_latency_and_counts(self):
        """VERDICT r1 weak #8: observe_batch was never called — the p50
        half of the north-star target was unmeasured."""
        q = RingBuffer(maxsize=64)
        for i in range(16):
            q.put(_rec(i))
        q.put(EndOfStream())
        pipe = InfeedPipeline(q, batch_size=8, poll_interval_s=0.001)
        seen = pipe.run(lambda b: jnp.sum(b.frames), block_until_ready=True)
        assert seen == 16
        assert pipe.metrics.batches.count == 2
        assert pipe.metrics.frames.count == 16
        assert pipe.metrics.step_latency.count == 2
        p50 = pipe.metrics.step_latency.quantile(0.5)
        assert np.isfinite(p50) and p50 > 0
        assert "p50" in pipe.metrics.status_line()


class TestTrailingEosInSameBatch:
    def test_sibling_eos_after_completing_marker_survives(self):
        """Two EOS copies popped in ONE get_batch: the copy after the
        tally-completing marker must go back for the sibling consumer
        (code-review r2 finding)."""
        q = RingBuffer(maxsize=16)
        for i in range(3):
            q.put(_rec(i))
        q.put(EndOfStream())  # completes the (single-producer) tally
        q.put(EndOfStream())  # sibling consumer's copy — same get_batch
        batches = list(batches_from_queue(q, 8, poll_interval_s=0.001))
        assert sum(b.num_valid for b in batches) == 3
        leftover = q.get()
        assert isinstance(leftover, EndOfStream)  # survived for the sibling


class TestUint16Stream:
    """Detector-native uint16 ADUs end to end: half the transport and
    host->device bytes of f32; calibration upcasts on device."""

    def test_u16_stream_through_pipeline_and_calib(self):
        import threading

        import jax
        import numpy as np

        from psana_ray_tpu.config import RetrievalMode
        from psana_ray_tpu.infeed import InfeedPipeline
        from psana_ray_tpu.ops import fused_calibrate
        from psana_ray_tpu.records import EndOfStream, FrameRecord
        from psana_ray_tpu.sources import SyntheticSource
        from psana_ray_tpu.transport import RingBuffer

        n = 10
        src = SyntheticSource(
            num_events=n, detector_name="epix100", seed=0, dtype=np.uint16
        )
        ped = np.asarray(src.pedestal())
        gain = np.asarray(src.gain_map())
        mask = np.asarray(src.create_bad_pixel_mask())
        q = RingBuffer(maxsize=16)

        def produce():
            for i in range(n):
                data, e = src.event(i, RetrievalMode.RAW)
                assert data.dtype == np.uint16
                assert q.put_wait(FrameRecord(0, i, data, e), timeout=10)
            assert q.put_wait(EndOfStream(total_events=n), timeout=10)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        pipe = InfeedPipeline(q, batch_size=4, poll_interval_s=0.001)
        outs = []
        seen = pipe.run(
            lambda b: fused_calibrate(b.frames, ped, gain, mask, threshold=10.0),
            on_result=lambda out, b: outs.append((out, b)),
            block_until_ready=True,
        )
        t.join(timeout=10)
        assert seen == n
        for out, b in outs:
            assert b.frames.dtype == np.uint16  # stream stays u16 to the device
            assert out.dtype == np.float32  # calibration upcasts on device
            assert bool(jax.numpy.isfinite(out).all())


class TestPooledBatcher:
    """FrameBatcher(n_buffers=K): recycled batch-buffer arena (round-3
    fan-in profiling: fresh 100+ MB allocations were re-page-faulted every
    batch — see utils/hostmem.py)."""

    def test_pool_reuses_buffers_round_robin(self):
        b = FrameBatcher(batch_size=2, n_buffers=2)
        batches = []
        for i in range(8):
            out = b.push(_rec(i))
            if out is not None:
                batches.append(out)
        assert len(batches) == 4
        # buffer identity cycles with period n_buffers
        ids = [id(x.frames) for x in batches]
        assert ids[0] == ids[2] and ids[1] == ids[3] and ids[0] != ids[1]
        # the most recent n_buffers batches hold correct (un-clobbered) data
        np.testing.assert_array_equal(batches[2].frames[0, 0, 0, 0], 4.0)
        np.testing.assert_array_equal(batches[3].frames[1, 0, 0, 0], 7.0)

    def test_pooled_tail_padding_zeroes_stale_rows(self):
        b = FrameBatcher(batch_size=4, n_buffers=1)
        for i in range(4):
            assert b.push(_rec(i)) is not None or i < 3  # first batch full
        # second, partial fill of the SAME recycled buffer
        b.push(_rec(10))
        tail = b.flush()
        assert tail.num_valid == 1
        np.testing.assert_array_equal(tail.valid, [1, 0, 0, 0])
        # stale rows from the previous batch must be zeroed, not leaked
        np.testing.assert_array_equal(tail.frames[1:], 0.0)
        assert float(tail.frames[0, 0, 0, 0]) == 10.0
        np.testing.assert_array_equal(tail.event_idx[1:], 0)

    def test_eager_copy_releases_source(self):
        # push copies immediately: mutating the source after push must not
        # change the emitted batch
        b = FrameBatcher(batch_size=2)
        r = _rec(1)
        b.push(r)
        r.panels[:] = -1.0
        out = b.push(_rec(2))
        assert float(out.frames[0, 0, 0, 0]) == 1.0


class TestHostOnlyPipeline:
    def test_place_on_device_false_yields_numpy(self):
        q = RingBuffer(maxsize=8)
        for i in range(4):
            q.put(_rec(i))
        q.put(EndOfStream(total_events=4))
        pipe = InfeedPipeline(q, batch_size=4, place_on_device=False)
        got = list(pipe)
        assert len(got) == 1
        assert isinstance(got[0].frames, np.ndarray)  # no device_put copy
        assert got[0].num_valid == 4

    def test_pipeline_rejects_undersized_pool(self):
        q = RingBuffer(maxsize=4)
        with pytest.raises(ValueError, match="batcher_buffers"):
            InfeedPipeline(q, batch_size=2, prefetch_depth=2, batcher_buffers=2)

    def test_fanin_rejects_undersized_pool(self):
        from psana_ray_tpu.infeed import DetectorStream, FanInPipeline

        q = RingBuffer(maxsize=4)
        with pytest.raises(ValueError, match="batcher_buffers"):
            FanInPipeline(
                [DetectorStream("d", q, batch_size=2, batcher_buffers=3)]
            )


def test_stop_stream_ends_run_early_and_closes():
    """A step callback raising StopStream ends run() cleanly: no further
    batches are processed, the pipeline closes, the count so far returns."""
    from psana_ray_tpu.infeed import InfeedPipeline, StopStream
    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.transport import RingBuffer

    q = RingBuffer(maxsize=64)
    for i in range(32):
        q.put(FrameRecord(0, i, np.zeros((1, 4, 4), np.float32), 1.0))
    q.put(EndOfStream(total_events=32))

    seen = []

    def step(batch):
        seen.append(batch.num_valid)
        if len(seen) == 2:
            raise StopStream

    pipe = InfeedPipeline(q, batch_size=4, place_on_device=False)
    n = pipe.run(step)
    assert len(seen) == 2  # stopped right at the quota
    assert n == 4  # frames counted before the stopping batch
