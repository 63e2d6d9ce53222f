"""One host timeline for the serving loops (ISSUE 24): every phase of a
serving thread is marked once, by ``utils.trace.phase``, and that one
mark feeds the sampler's tags, the stage histograms and the span spool;
the shm ring stamps its slots, so ``queue_dwell`` and an enqueue ->
result ``e2e`` exist behind a process hop; the tracer keeps spans in
memory and serializes nothing on the emitting thread; named scopes in
the device programs are metadata only."""

import contextlib
import gc
import json
import os
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from psana_ray_tpu.infeed import InfeedPipeline
from psana_ray_tpu.infeed.batcher import Batch, FrameBatcher, batches_from_queue
from psana_ray_tpu.obs import tracing
from psana_ray_tpu.obs.profiling.stagetag import TAG_NAMES, TAG_UNTAGGED, current_tag
from psana_ray_tpu.obs.stages import (
    HOP_BATCH,
    HOP_DEQ,
    HOP_ENQ,
    HOP_PUSH,
    PHASES,
    STAGE_E2E,
    StageTimes,
    observe_batch_done,
)
from psana_ray_tpu.obs.tracing import TRACE_KEY, TRACER, TraceContext, Tracer
from psana_ray_tpu.records import EndOfStream, FrameRecord
from psana_ray_tpu.transport.shm_ring import ShmRingBuffer, native_available
from psana_ray_tpu.utils.metrics import PipelineMetrics
from psana_ray_tpu.utils.trace import phase

needs_ring = pytest.mark.skipif(not native_available(), reason="no native shm ring here")

SHAPE = (2, 32, 128)  # panels, height, width: the benchmark's rehearsal detector
BATCH = 4
BATCH_PHASES_SFX = ("launch", "device_wait", "fold", "append")
BATCH_PHASES_INFEED = ("device_put", "prefetch_full", "infeed_wait", "launch", "device_wait")
TURN_PHASES = ("queue_wait", "decode", "copy")


@pytest.fixture(autouse=True)
def _global_tracer_off():
    yield
    TRACER.close()


@pytest.fixture
def ring():
    r = ShmRingBuffer.create(f"phases_{os.getpid()}_{time.monotonic_ns()}", maxsize=16,
                             slot_bytes=64 * 1024)
    yield r
    r.destroy()


def _frame(i, trace=None):
    return FrameRecord(0, i, np.full(SHAPE, i % 7, np.uint16), 9.5, trace=trace)


def _feed(ring, n, traced=False, gap_s=0.0):
    """Put ``n`` frames and the end of stream from a thread; returns the
    thread and the list it fills with each put's (before, after)."""
    stamps = []

    def run():
        for i in range(n):
            ctx = TraceContext(trace_id=1000 + i, origin_host="t", origin_pid=1) if traced else None
            t0 = time.monotonic()
            assert ring.put_wait(_frame(i, ctx), timeout=30)
            stamps.append((t0, time.monotonic()))
            if gap_s:
                time.sleep(gap_s)
        assert ring.put_wait(EndOfStream(total_events=n), timeout=30)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, stamps


def _spool(tracer):
    path = tracer.spool_path
    tracer.close()
    return [json.loads(s) for s in open(path) if s.strip()]


def _phase_spans(rows, name):
    return [r for r in rows if r["t"] == "s" and r["n"] == "stage." + name]


def _assert_consecutive(rows, names):
    """Spans of the named phases, all of one thread: none overlaps."""
    spans = sorted(
        (r for r in rows if r["t"] == "s" and r["n"] in {"stage." + n for n in names}),
        key=lambda r: r["a"],
    )
    assert spans
    for prev, cur in zip(spans, spans[1:]):
        assert cur["a"] >= prev["b"], (prev, cur)


# ---------------------------------------------------------------------------
# the helper
# ---------------------------------------------------------------------------

class TestPhaseHelper:
    @pytest.mark.parametrize("name", PHASES)
    def test_tag_is_set_inside_and_restored_after(self, name):
        assert current_tag() == TAG_UNTAGGED
        with phase("copy"):
            outer = current_tag()
            with phase(name):  # (loops never nest; the unwinding still holds)
                assert TAG_NAMES[current_tag()] == name
            assert current_tag() == outer
        assert current_tag() == TAG_UNTAGGED

    def test_tag_is_restored_when_the_body_raises(self):
        with pytest.raises(KeyError):
            with phase("launch"):
                raise KeyError("boom")
        assert current_tag() == TAG_UNTAGGED

    def test_one_mark_one_observation_one_span(self, tmp_path):
        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        m = PipelineMetrics()
        with phase("launch", m, batch_id=7, frames=3) as ph:
            time.sleep(0.002)
        assert m.stages.stat("launch").count == 1
        assert ph.t1 - ph.t0 >= 0.002
        spans = [r for r in _spool(TRACER) if r["t"] == "s"]
        assert spans == [{"t": "s", "id": 7, "n": "stage.launch", "a": ph.t0, "b": ph.t1, "k": 3}]

    def test_a_turn_with_nothing_to_report_keeps_quiet(self, tmp_path):
        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        m = PipelineMetrics()
        with phase("queue_wait", m) as ph:
            ph.record = False
            assert TAG_NAMES[current_tag()] == "queue_wait"  # the tag is kept
        assert m.stages.stages() == []
        assert [r for r in _spool(TRACER) if r["t"] == "s"] == []

    def test_without_metrics_and_tracer_it_only_tags(self):
        with phase("fold") as ph:
            pass
        assert ph.t1 >= ph.t0 > 0.0

    def test_the_region_is_on_the_profilers_timeline(self, monkeypatch):
        seen = []

        class Ann:
            def __init__(self, name):
                seen.append(name)

            def __enter__(self):
                seen.append("enter")

            def __exit__(self, *exc):
                seen.append("exit")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
        with phase("device_wait"):
            pass
        assert seen == ["stage.device_wait", "enter", "exit"]

    def test_samples_taken_inside_a_phase_bill_to_it(self):
        """Deterministic twin of the live-relay attribution test: the
        sampler's own tick, called from inside a phase, bills the calling
        thread's stack to that phase, and to nothing once it is over."""
        from psana_ray_tpu.obs.profiling import FlameSampler

        sampler = FlameSampler(hz=97.0, process="unit", register=False)
        for name, ticks in (("queue_wait", 3), ("copy", 2), ("append", 1)):
            with phase(name):
                for _ in range(ticks):
                    sampler._sample_once()
        totals = sampler.trie.stage_totals()
        for name, ticks in (("queue_wait", 3), ("copy", 2), ("append", 1)):
            assert totals[name]["on"] + totals[name]["off"] == ticks
        before = dict(totals)
        sampler._sample_once()  # outside any phase
        after = sampler.trie.stage_totals()
        assert {k: v for k, v in after.items() if k != "untagged"} == {
            k: v for k, v in before.items() if k != "untagged"
        }


# ---------------------------------------------------------------------------
# the tracer: nothing serialized on the emitting thread
# ---------------------------------------------------------------------------

class TestTracerInMemory:
    def test_no_dumps_and_no_write_between_configure_and_close(self, tmp_path, monkeypatch):
        t = Tracer().configure(str(tmp_path), sample_every=1, process="t")
        calls = {"dumps": 0, "write": 0}
        real_dumps = json.dumps

        def counting_dumps(*a, **kw):
            calls["dumps"] += 1
            return real_dumps(*a, **kw)

        class CountingFile:
            def __init__(self, f):
                self._f = f

            def write(self, s):
                calls["write"] += 1
                return self._f.write(s)

            def __getattr__(self, name):
                return getattr(self._f, name)

        monkeypatch.setattr(tracing.json, "dumps", counting_dumps)
        t._f = CountingFile(t._f)
        for i in range(500):
            t.span(i, "batch", 1.0, 2.0)
            t.instant(i, "produce", 1.0)
        t.extend([(i, "dequeue", 1.0, 2.0, 9, 0) for i in range(500)])
        with phase("launch"):
            pass
        assert calls == {"dumps": 0, "write": 0}
        assert t.snapshot()["spans_total"] == 1500
        t.close()
        assert calls["write"] >= 1 and calls["dumps"] >= 1500

    def test_drops_beyond_its_bound_and_counts_them(self, tmp_path):
        t = Tracer().configure(str(tmp_path), sample_every=1, process="t", max_spans=5)
        t.span(1, "s", 0.0, 1.0)
        t.extend([(i, "batch", 0.0, 1.0, 3, 0) for i in range(10)])  # 4 fit
        t.span(2, "s", 0.0, 1.0)
        t.extend([(1, "batch", 0.0, 1.0, 3, 0)])
        snap = t.snapshot()
        assert snap["spans_total"] == 5 and snap["spans_dropped_total"] == 8
        assert len([r for r in _spool(t) if r["t"] == "s"]) == 5

    def test_flush_writes_what_is_held_and_keeps_going(self, tmp_path):
        t = Tracer().configure(str(tmp_path), sample_every=1, process="t")
        t.span(1, "batch", 1.0, 2.0)
        t.flush()
        held = [json.loads(s) for s in open(t.spool_path) if s.strip()]
        assert [r["n"] for r in held if r["t"] == "s"] == ["batch"]
        t.span(2, "batch", 2.0, 3.0)
        assert [r["id"] for r in _spool(t) if r["t"] == "s"] == [1, 2]

    @pytest.mark.parametrize("span,q,want_ms", [
        ("batch", 0.5, 2000.0),  # a frame's span
        ("stage.launch", 0.5, 500.0),  # a loop phase's span
    ])
    def test_spool_lines_parse_with_the_benchmarks_reader(self, tmp_path, span, q, want_ms):
        from benchmark.readers import span_quantile

        t = Tracer().configure(str(tmp_path), sample_every=1, process="t")
        t.extend([(5, "batch", 10.0, 12.0, 3, 0)])
        t.phase_span(3, "stage.launch", 12.0, 12.5, 4)
        t.instant(5, "produce", 9.0)
        path = t.spool_path
        t.close()
        ctx = types.SimpleNamespace(spool_path=path, window=(0.0, 100.0))
        assert span_quantile.read(ctx, span=span, q=q) == pytest.approx(want_ms)

    def test_a_full_collection_is_a_span_and_a_counter(self, tmp_path):
        t = Tracer().configure(str(tmp_path), sample_every=1, process="t")
        gc.collect(0)  # a young collection: not counted
        assert t.snapshot()["gc_collections_total"] == 0
        with phase("fold") as ph:
            gc.collect()
        snap = t.snapshot()
        assert snap["gc_collections_total"] == 1 and snap["gc_seconds_total"] > 0.0
        (span,) = [r for r in _spool(t) if r["t"] == "s" and r["n"] == tracing.GC_SPAN]
        assert ph.t0 <= span["a"] <= span["b"] <= ph.t1  # inside the open phase
        assert t._on_gc not in gc.callbacks  # the hook goes with the tracer
        gc.collect()
        assert t.snapshot()["gc_collections_total"] == 1

    def test_a_flight_dump_flushes_the_spans_held(self, tmp_path):
        from psana_ray_tpu.obs.flight import FlightRecorder

        TRACER.configure(str(tmp_path / "spans"), sample_every=1, process="t")
        TRACER.span(1, "batch", 1.0, 2.0)
        fl = FlightRecorder()
        assert fl.dump("test", path=str(tmp_path / "flight.json"), force=True)
        rows = [json.loads(s) for s in open(TRACER.spool_path) if s.strip()]
        assert [r["n"] for r in rows if r["t"] == "s"] == ["batch"]


# ---------------------------------------------------------------------------
# the stamp that crosses shm://
# ---------------------------------------------------------------------------

@needs_ring
class TestRingStamp:
    def test_stats_carry_the_dwell_of_every_item(self, ring):
        for i in range(3):
            assert ring.put(_frame(i))
        time.sleep(0.03)
        assert ring.stats()["dwell_count"] == 0  # nothing popped yet
        for _ in range(3):
            ring.get()
        s = ring.stats()
        assert s["dwell_count"] == 3
        assert 30.0 <= s["dwell_ms_mean"] <= s["dwell_ms_max"] < 5000.0

    def test_a_second_handle_reads_the_same_counters(self, ring):
        other = ShmRingBuffer.attach(ring.name, retries=0)
        try:
            ring.put(_frame(0))
            ring.get()
            assert other.stats()["dwell_count"] == 1
        finally:
            other.disconnect()

    @pytest.mark.parametrize("pop", ["get", "get_view", "get_batch", "get_batch_view"])
    def test_every_pop_hands_the_enqueue_stamp_to_the_record(self, ring, pop):
        t0 = time.monotonic()
        ring.put(_frame(0))
        t1 = time.monotonic()
        out = getattr(ring, pop)() if pop.startswith("get") and "batch" not in pop else (
            getattr(ring, pop)(4, timeout=1.0)[0])
        assert t0 <= out.t_enq <= t1  # one clock for every process of the host
        out.release()

    def test_the_stamp_is_not_on_the_wire(self):
        rec = _frame(0)
        object.__setattr__(rec, "t_enq", 12.5)
        assert FrameRecord.from_bytes(rec.to_bytes()).t_enq == 0.0

    def test_the_batch_keeps_its_oldest_stamp_and_no_per_frame_object(self, ring):
        for i in range(BATCH):
            ring.put(_frame(i))
            time.sleep(0.002)
        recs = ring.get_batch_view(BATCH, timeout=1.0)
        oldest = min(r.t_enq for r in recs)
        batcher = FrameBatcher(BATCH)
        out = [batcher.push_view(r) for r in reversed(recs)][-1]  # order does not matter
        assert out.t_enq == oldest and out.hops is None and out.batch_id > 0
        assert batcher.push(_frame(9)) is None and batcher.flush().t_enq == 0.0

    def test_traced_frames_get_enq_seeded_beside_deq(self, ring, tmp_path):
        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        feeder, stamps = _feed(ring, BATCH, traced=True)
        (batch,) = list(batches_from_queue(ring, BATCH, poll_interval_s=0.001))
        feeder.join(timeout=30)
        assert len(batch.hops) == BATCH
        for hops, (t0, t1) in zip(batch.hops, stamps):
            assert t0 <= hops[HOP_ENQ] <= t1 <= hops[HOP_DEQ] + 1e-3
            assert hops[HOP_ENQ] <= hops[HOP_DEQ] <= hops[HOP_PUSH] <= hops[HOP_BATCH]
            assert TRACE_KEY in hops

    def test_untraced_frames_get_no_hops_behind_the_ring(self, ring):
        feeder, _ = _feed(ring, BATCH)
        (batch,) = list(batches_from_queue(ring, BATCH, poll_interval_s=0.001))
        feeder.join(timeout=30)
        assert batch.hops is None and batch.t_enq > 0.0


class TestBatchDone:
    def _batch(self, hops=None, t_enq=0.0, t_staged=0.0):
        z = np.zeros(2)
        return Batch(np.zeros((2, 1, 1, 1)), z, z, z, z, num_valid=2, hops=hops,
                     t_enq=t_enq, t_staged=t_staged)

    def test_untimed_batch_is_observed_once_by_its_scalar(self):
        st = StageTimes()
        observe_batch_done(st, self._batch(t_enq=10.0), 10.5)
        assert st.stat(STAGE_E2E).count == 1 and st.stat(STAGE_E2E).mean == pytest.approx(0.5)
        assert st.stages() == [STAGE_E2E]

    def test_timed_batch_is_observed_per_frame_instead_never_both(self):
        st = StageTimes()
        hops = [{HOP_ENQ: 10.0, HOP_BATCH: 11.0}, {"src": 9.0, HOP_ENQ: 10.2, HOP_BATCH: 11.0}]
        observe_batch_done(st, self._batch(hops=hops, t_enq=10.0, t_staged=11.5), 12.0)
        assert st.stat(STAGE_E2E).count == 2  # from enq behind a hop, from src in process
        assert st.stat(STAGE_E2E).mean == pytest.approx((2.0 + 3.0) / 2)
        assert st.stat("dispatch").count == 1  # the same for all its frames: once
        assert st.stat("dispatch").mean == pytest.approx(0.5)  # staged -> done

    def test_a_batch_without_any_stamp_observes_nothing(self):
        st = StageTimes()
        observe_batch_done(st, self._batch(), 1.0)
        assert st.stages() == []


# ---------------------------------------------------------------------------
# the loops, over an in-process shm ring
# ---------------------------------------------------------------------------

class _Writer:
    max_peaks = 64

    def __init__(self):
        self.done_t = []
        self.rows = 0

    def append(self, sets):
        time.sleep(0.003)
        self.rows += len(sets)
        self.done_t.append(time.monotonic())


@pytest.fixture(scope="module")
def sfx_variables():
    from flax.core import meta

    from psana_ray_tpu.models import PeakNetUNetTPU, host_init

    model = PeakNetUNetTPU(features=(8, 16), norm="frozen", s2d=2)
    return meta.unbox(host_init(model, (1, SHAPE[1], SHAPE[2], 1)))


def _sfx(sfx_variables):
    from psana_ray_tpu.sfx import SfxConfig, SfxPipeline

    calib = (np.zeros(SHAPE, np.float32), np.ones(SHAPE, np.float32), np.ones(SHAPE, np.uint8))
    return SfxPipeline(sfx_variables, _Writer(), calib=calib,
                       config=SfxConfig(batch_size=BATCH, max_peaks=8))


@needs_ring
class TestSfxLoop:
    N = 3 * BATCH

    @pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
    def test_every_phase_once_per_batch_or_turn_and_none_overlaps(
        self, ring, sfx_variables, tmp_path, traced
    ):
        pipe = _sfx(sfx_variables)
        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        feeder, _ = _feed(ring, self.N, traced=traced, gap_s=0.001)
        assert pipe.run(ring, poll_interval_s=0.001) == self.N
        feeder.join(timeout=30)
        rows = _spool(TRACER)
        n_batches = self.N // BATCH
        stages = pipe.metrics.stages
        for name in BATCH_PHASES_SFX:
            spans = _phase_spans(rows, name)
            assert len(spans) == n_batches, name
            assert sorted(r["id"] for r in spans) == sorted({r["id"] for r in spans})
            assert all(r["k"] == BATCH for r in spans)
            assert stages.stat(name).count == n_batches, name
        turns = len(_phase_spans(rows, "queue_wait"))
        assert turns >= n_batches
        for name in TURN_PHASES:
            assert len(_phase_spans(rows, name)) == turns, name
        # the batcher thread's phases: one observation a turn that popped
        # something, tracer or no tracer
        for name in TURN_PHASES:
            assert stages.stat(name).count == turns, name
        # ``dequeue`` and ``batch`` are a frame's hop stages and nothing else
        for name in ("dequeue", "batch"):
            stat = stages.stat(name)
            assert (stat.count if stat else 0) == (self.N if traced else 0), name
        _assert_consecutive(rows, TURN_PHASES + BATCH_PHASES_SFX)
        assert pipe.metrics.step_latency.count == n_batches

    def test_untraced_frames_get_one_e2e_per_batch_ending_at_append_done(
        self, ring, sfx_variables
    ):
        pipe = _sfx(sfx_variables)
        feeder, stamps = _feed(ring, self.N, gap_s=0.001)
        assert pipe.run(ring, poll_interval_s=0.001) == self.N
        feeder.join(timeout=30)
        e2e = pipe.metrics.stages.stat(STAGE_E2E)
        assert e2e.count == self.N // BATCH
        assert pipe.metrics.stages.stat("queue_dwell") is None  # per frame: traced only
        # each batch's oldest frame is its first: enqueue -> append done
        want = [done - stamps[i * BATCH][1] for i, done in enumerate(pipe.writer.done_t)]
        got = sorted(e2e._samples)
        for w, g in zip(sorted(want), got):
            assert w <= g + 1e-4 and g <= w + 0.05, (want, got)
        assert ring.stats()["dwell_count"] >= self.N

    def test_the_drain_reads_its_results_back_in_one_call(
        self, ring, sfx_variables, monkeypatch
    ):
        # three readbacks in a row are three blocking round trips to the
        # device on a paced frame's path (PERF.md, PR 24 finding 9)
        import jax

        calls = []
        real = jax.device_get
        monkeypatch.setattr(jax, "device_get", lambda x: calls.append(len(x)) or real(x))
        pipe = _sfx(sfx_variables)
        feeder, _ = _feed(ring, self.N, gap_s=0.001)
        assert pipe.run(ring, poll_interval_s=0.001) == self.N
        feeder.join(timeout=30)
        assert calls == [3] * (self.N // BATCH)  # (yx, score, n), once per batch

    def test_traced_frames_get_queue_dwell_and_e2e_per_frame(
        self, ring, sfx_variables, tmp_path
    ):
        pipe = _sfx(sfx_variables)
        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        feeder, stamps = _feed(ring, self.N, traced=True, gap_s=0.001)
        assert pipe.run(ring, poll_interval_s=0.001) == self.N
        feeder.join(timeout=30)
        stages = pipe.metrics.stages
        assert stages.stat("queue_dwell").count == self.N
        assert stages.stat(STAGE_E2E).count == self.N  # per frame INSTEAD of per batch
        assert stages.stat("dispatch").count == self.N // BATCH
        # e2e ends at append-done: no frame's is shorter than its append's wait
        first_done = pipe.writer.done_t[0]
        assert max(stages.stat(STAGE_E2E)._samples) >= first_done - stamps[0][1] - 1e-4
        rows = _spool(TRACER)
        frame_spans = [r for r in rows if r["t"] == "s" and "j" in r]
        assert sorted({r["n"] for r in frame_spans}) == ["batch", "dequeue", "queue_dwell"]
        assert len(frame_spans) == 3 * self.N
        batch_ids = {r["id"] for r in _phase_spans(rows, "launch")}
        assert {r["j"] for r in frame_spans} == batch_ids  # joined on the batch id


@needs_ring
class TestSfxEarlyDrain:
    """``SfxPipeline.run`` drains a batch whose step has ended between
    two turns of the batcher (ISSUE 35): its ``device_wait`` / ``fold`` /
    ``append`` lie after one turn's ``copy`` (or the ``launch``, over
    empty polls, which leave no span) and before the next turn's
    ``queue_wait`` — never inside a turn."""

    N = 4 * BATCH

    @pytest.mark.parametrize(
        "poll_s", [0.001, 0.5], ids=["seen-at-an-empty-poll", "seen-at-a-frame"]
    )
    def test_an_early_drains_phases_lie_between_two_turns(
        self, ring, sfx_variables, tmp_path, poll_s
    ):
        pipe = _sfx(sfx_variables)
        z = np.zeros(BATCH)
        pipe.process_batch(Batch(  # compile before the stream: its steps then end within a gap
            np.zeros((BATCH,) + SHAPE, np.uint16), np.ones(BATCH, np.uint8), z.astype(np.int32),
            np.arange(BATCH, dtype=np.int64), z.astype(np.float32),
        ))
        pipe.writer, pipe.metrics, pipe.n_events = _Writer(), PipelineMetrics(), 0
        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        feeder, _ = _feed(ring, self.N, gap_s=0.05)  # a batch every 200 ms
        assert pipe.run(ring, poll_interval_s=poll_s) == self.N
        feeder.join(timeout=30)
        rows = _spool(TRACER)
        n_batches = self.N // BATCH
        names = TURN_PHASES + ("put_ahead",) + BATCH_PHASES_SFX
        # frames that landed without filling a batch went to the device in
        # a phase of their own, which reports the frames it started
        ahead = _phase_spans(rows, "put_ahead")
        staged = pipe.metrics.frames_staged_ahead.count
        assert 0 < staged == sum(r["k"] for r in ahead) <= self.N - n_batches
        assert pipe.metrics.stages.stat("put_ahead").count == len(ahead)
        for name in BATCH_PHASES_SFX:  # still once a batch, under the batch's id
            spans = _phase_spans(rows, name)
            assert len(spans) == n_batches, name
            assert len({r["id"] for r in spans}) == n_batches
        _assert_consecutive(rows, names)
        spans = sorted(
            (r for r in rows if r["t"] == "s" and r["n"] in {"stage." + n for n in names}),
            key=lambda r: r["a"],
        )
        order = [r["n"][len("stage."):] for r in spans]
        early = 0
        for i, name in enumerate(order):
            if name == "device_wait":
                # a drain begins where a turn has ended or a launch has (a turn that filled
                # nothing ends with its frame's `put_ahead`: on a loaded machine the step
                # ends only in the silence after one), and its three phases follow one another
                assert order[i - 1] in ("copy", "launch", "put_ahead"), order[max(0, i - 3): i + 4]
                assert order[i + 1: i + 3] == ["fold", "append"]
                assert spans[i]["id"] == spans[i + 1]["id"] == spans[i + 2]["id"]
                if i + 3 < len(order):  # then the turn's own frame, if it filled nothing
                    assert order[i + 3] in ("queue_wait", "put_ahead")
                launches = [r for r in spans if r["n"] == "stage.launch"]
                own = next(r for r in launches if r["id"] == spans[i]["id"])
                later = [r for r in launches if r["a"] > own["a"]]
                if later and spans[i + 2]["b"] <= later[0]["a"]:
                    early += 1  # appended before the next launch began
        assert early == n_batches - 1
        # the last batch has no next launch: it counts too when the stream
        # fell silent long enough before its end for the hook to see it done
        assert pipe.metrics.drained_ahead.count in (n_batches - 1, n_batches)
        assert pipe.metrics.step_latency.count == n_batches


@needs_ring
class TestInfeedLoop:
    N = 3 * BATCH

    @pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
    def test_every_phase_once_per_batch_and_no_thread_overlaps_itself(
        self, ring, tmp_path, traced
    ):
        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        feeder, _ = _feed(ring, self.N, traced=traced, gap_s=0.001)
        pipe = InfeedPipeline(ring, batch_size=BATCH, poll_interval_s=0.001)
        step = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
        results = []
        seen = pipe.run(lambda b: step(b.frames), on_result=lambda out, b: results.append(out),
                        block_until_ready=True)
        feeder.join(timeout=30)
        assert seen == self.N and len(results) == self.N // BATCH
        rows = _spool(TRACER)
        n_batches = self.N // BATCH
        stages = pipe.metrics.stages
        for name in BATCH_PHASES_INFEED:
            assert len(_phase_spans(rows, name)) == n_batches, name
            assert stages.stat(name).count == n_batches, name
        turns = len(_phase_spans(rows, "queue_wait"))
        for name in TURN_PHASES:
            assert len(_phase_spans(rows, name)) == turns, name
        # the prefetch thread runs the batcher's phases and its own two;
        # the serving thread the other three
        _assert_consecutive(rows, TURN_PHASES + ("device_put", "prefetch_full"))
        _assert_consecutive(rows, ("infeed_wait", "launch", "device_wait"))
        assert stages.stat(STAGE_E2E).count == (self.N if traced else n_batches)
        assert pipe.metrics.step_latency.count == n_batches
        if traced:
            assert stages.stat("queue_dwell").count == self.N
            assert stages.stat("dispatch").count == n_batches

    def test_after_the_step_the_instrument_makes_a_handful_of_calls(self, monkeypatch):
        """At batch 192 with every frame traced: once the step is done
        the serving thread observes ``dispatch`` once and ``e2e`` per
        frame, and hands the tracer nothing per frame (the per-frame fold
        ran before the host blocked)."""
        from psana_ray_tpu.infeed import pipeline as pipeline_mod

        n = 192
        hops = [{TRACE_KEY: i, HOP_ENQ: 1.0, HOP_DEQ: 2.0, HOP_PUSH: 3.0, HOP_BATCH: 4.0}
                for i in range(n)]
        z = np.zeros(n)
        batch = Batch(np.zeros((n, 1, 1, 1)), z, z, z, z, num_valid=n, hops=hops, batch_id=3)
        metrics = PipelineMetrics()
        calls = {"observe": 0, "span": 0, "extend": 0, "after": False}
        real_observe = StageTimes.observe

        def observe(self, *a, **kw):
            calls["observe"] += calls["after"]
            return real_observe(self, *a, **kw)

        monkeypatch.setattr(StageTimes, "observe", observe)
        monkeypatch.setattr(TRACER, "phase_span", lambda *a, **kw: calls.__setitem__(
            "span", calls["span"] + calls["after"]))
        monkeypatch.setattr(TRACER, "extend", lambda *a, **kw: calls.__setitem__(
            "extend", calls["extend"] + calls["after"]))
        monkeypatch.setattr(TRACER, "enabled", True)

        def block(out):
            calls["after"] = True  # the step is done: count from here
            return out

        monkeypatch.setattr(pipeline_mod.jax, "block_until_ready", block)
        pipeline_mod.drive_step(metrics, lambda b: 1, batch, block_until_ready=True)
        # device_wait's own mark (1 observation, 1 span) + dispatch + e2e per frame
        assert calls["observe"] == 1 + 1 + n
        assert calls["span"] == 1 and calls["extend"] == 0
        assert metrics.stages.stat("queue_dwell").count == n  # folded before the wait


# ---------------------------------------------------------------------------
# the hand-off (ISSUE 40): the transfer's true end, the batcher's two
# phases under names of their own, a bound of their own for the phases
# ---------------------------------------------------------------------------

def _host_batches(ids):
    z = np.zeros(BATCH)
    return [
        Batch(np.zeros((BATCH,) + SHAPE, np.uint16), np.ones(BATCH, np.uint8),
              z.astype(np.int32), np.arange(BATCH, dtype=np.int64), z.astype(np.float32),
              batch_id=i)
        for i in ids
    ]


class _Late:
    """What a ``to_device`` hands back: on the device only once ``arrived``
    is set (``jax.block_until_ready`` calls this on a leaf that has it)."""

    def __init__(self, batch_id, arrived):
        self.batch_id, self._arrived = batch_id, arrived

    def block_until_ready(self):
        assert self._arrived.wait(timeout=30)
        return self


class TestTransferEnd:
    IDS = (11, 12, 13)

    def test_tracer_on_one_h2d_span_a_batch_and_the_hand_off_does_not_wait(self, tmp_path):
        from psana_ray_tpu.infeed.pipeline import DevicePrefetcher

        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        arrived = threading.Event()
        pf = DevicePrefetcher(
            iter(_host_batches(self.IDS)), prefetch_depth=1,
            to_device=lambda b: _Late(b.batch_id, arrived),
        )
        # all three come through, in order, while no transfer has ended
        assert [item.batch_id for item in pf] == list(self.IDS)
        assert pf._watcher.is_alive()
        time.sleep(0.02)
        arrived.set()
        pf.close()
        assert not pf._watcher.is_alive()
        rows = _spool(TRACER)
        h2d = [r for r in rows if r["t"] == "s" and r["n"] == "h2d"]
        put = {r["id"]: r for r in _phase_spans(rows, "device_put")}
        assert [r["id"] for r in h2d] == list(self.IDS)
        for r in h2d:
            assert r["a"] == put[r["id"]]["a"] and r["b"] >= put[r["id"]]["b"]
            assert r["k"] == BATCH
        assert h2d[0]["b"] - put[11]["b"] >= 0.02  # the tail, not the call
        tails = _phase_spans(rows, "h2d_tail")
        assert [r["id"] for r in tails] == list(self.IDS)
        assert [r["b"] for r in tails] == [r["b"] for r in h2d]

    def test_a_real_device_put_is_waited_for(self, tmp_path):
        from psana_ray_tpu.infeed.pipeline import DevicePrefetcher

        TRACER.configure(str(tmp_path), sample_every=1, process="t")
        with DevicePrefetcher(iter(_host_batches(self.IDS))) as pf:
            staged = list(pf)
        assert all(isinstance(b.frames, jax.Array) for b in staged)
        rows = _spool(TRACER)
        assert [r["id"] for r in rows if r["t"] == "s" and r["n"] == "h2d"] == list(self.IDS)

    def test_tracer_off_no_thread_beyond_todays_and_no_reference(self):
        from psana_ray_tpu.infeed.pipeline import DevicePrefetcher

        before = threading.active_count()
        release = threading.Event()

        def src():
            yield from _host_batches(self.IDS)
            assert release.wait(timeout=30)  # hold the prefetch thread alive

        pf = DevicePrefetcher(src(), prefetch_depth=4, to_device=lambda b: b)
        try:
            deadline = time.monotonic() + 10
            while pf._buf.qsize() < len(self.IDS) and time.monotonic() < deadline:
                time.sleep(0.001)
            assert pf._buf.qsize() == len(self.IDS)  # all three were staged
            assert threading.active_count() == before + 1  # the prefetch thread alone
            assert pf._watcher is None and pf._watched is None
        finally:
            release.set()
            pf.close()


class TestBatcherPhasesHaveNamesOfTheirOwn:
    N = 6

    @pytest.mark.parametrize("traced", [False, True], ids=["tracer-off", "tracer-on"])
    def test_decode_and_copy_once_a_turn_with_frames_none_on_an_empty_poll(
        self, tmp_path, traced
    ):
        from psana_ray_tpu.transport import RingBuffer

        if traced:
            TRACER.configure(str(tmp_path), sample_every=1, process="t")
        q = RingBuffer(maxsize=16)
        pops = {"empty": 0, "held": 0}
        real = q.get_batch

        def counting(n, timeout=None):
            items = real(n, timeout=timeout)
            pops["held" if items else "empty"] += 1
            return items

        q.get_batch = counting

        def feed():
            for i in range(self.N):
                q.put_wait(_frame(i), timeout=30)
                time.sleep(0.01)  # several empty polls between two frames
            q.put_wait(EndOfStream(total_events=self.N), timeout=30)

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        m = PipelineMetrics()
        batches = list(batches_from_queue(q, BATCH, poll_interval_s=0.001, metrics=m))
        t.join(timeout=30)
        assert sum(b.num_valid for b in batches) == self.N
        assert pops["empty"] > 0
        for name in TURN_PHASES:
            assert m.stages.stat(name).count == pops["held"], name
        # the hop histograms are the frames' and are left alone: an untimed
        # stream has none (the serving loop folds them, obs.stages)
        assert m.stages.stat("dequeue") is None and m.stages.stat("batch") is None
        if traced:
            rows = _spool(TRACER)
            copies = _phase_spans(rows, "copy")
            assert len(copies) == len(_phase_spans(rows, "decode")) == pops["held"]
            assert sum(r.get("k", 0) for r in copies) == self.N
            assert sum(r.get("y", 0) for r in copies) == self.N * _frame(0).nbytes


class TestPhaseSpansHaveABoundOfTheirOwn:
    def test_a_full_frame_budget_drops_frames_and_keeps_every_phase(self, tmp_path):
        t = Tracer().configure(str(tmp_path), sample_every=1, process="t", max_spans=5)
        t.extend([(i, "batch", 0.0, 1.0, 3, 0) for i in range(8)])  # 5 fit
        t.span(9, "queue_dwell", 0.0, 1.0)  # none fits
        for i in range(5):
            t.phase_span(i, "stage.copy", float(i), i + 0.5, 2, 4096)
        snap = t.snapshot()
        assert snap["spans_total"] == 5 and snap["spans_dropped_total"] == 4
        assert snap["phase_spans_total"] == 5 and snap["phase_spans_dropped_total"] == 0
        t.phase_span(5, "h2d", 5.0, 5.5, 2)  # the phases' own bound, and its count
        assert t.snapshot()["phase_spans_dropped_total"] == 1
        assert "drops=5" in t.status_suffix()
        rows = _spool(t)
        assert len(_phase_spans(rows, "copy")) == 5
        assert all(r["k"] == 2 and r["y"] == 4096 for r in _phase_spans(rows, "copy"))
        assert [r for r in rows if r["t"] == "d"][-1] == {
            "t": "d", "spans": 5, "dropped": 4, "phase_spans": 5, "phase_dropped": 1}
        from psana_ray_tpu.obs import trace_merge

        (track,) = trace_merge.merge([str(tmp_path)])["otherData"]["tracks"]
        assert (track["spans_dropped"], track["phase_spans_dropped"]) == (4, 1)

    def test_the_benchmarks_reader_refuses_a_spool_that_dropped_a_phase(self, tmp_path, capsys):
        from benchmark.readers import span_time_share

        t = Tracer().configure(str(tmp_path), sample_every=1, process="t", max_spans=2)
        for i in range(3):
            t.phase_span(i, "stage.copy", float(i), i + 0.5)
        path = t.spool_path
        t.close()
        ctx = types.SimpleNamespace(spool_path=path, window=(0.0, 10.0))
        assert span_time_share.read(ctx, spans=["stage.copy"]) is None
        assert "dropped 1 phase spans" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# names in the device program: metadata only
# ---------------------------------------------------------------------------

def _without_scopes(monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())


class TestScopesAreMetadataOnly:
    def test_sfx_device_step_is_bit_identical_without_them(self, sfx_variables, monkeypatch):
        frames = np.random.default_rng(0).integers(0, 200, (BATCH, *SHAPE)).astype(np.uint16)
        with_scopes = [np.asarray(a) for a in _sfx(sfx_variables)._step(frames)]
        _without_scopes(monkeypatch)
        jax.clear_caches()
        without = [np.asarray(a) for a in _sfx(sfx_variables)._step(frames)]
        for a, b in zip(with_scopes, without):
            np.testing.assert_array_equal(a, b)

    def test_sfx_device_step_names_its_parts(self, sfx_variables):
        pipe = _sfx(sfx_variables)
        text = jax.jit(pipe._device_step).lower(
            pipe._variables, pipe._calib, jnp.zeros((BATCH, *SHAPE), jnp.uint16)
        ).as_text(debug_info=True)
        for scope in ("calib", "peaknet", "find_peaks", "nms", "topk",
                      "enc0", "bottleneck", "dec0", "head"):
            assert scope in text, scope

    def test_fused_resnet_step_is_bit_identical_without_them(self, monkeypatch):
        from flax.core import meta

        from psana_ray_tpu.models import ResNetClassifier, host_init
        from psana_ray_tpu.models.pallas_resnet import resnet_fused_infer

        sizes = (1, 1, 1, 1)
        model = ResNetClassifier(stage_sizes=sizes, num_classes=2, width=8, norm="frozen")
        variables = meta.unbox(host_init(model, (1, 64, 64, 2)))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64, 64, 2)), jnp.float32)

        def run():
            return np.asarray(jax.jit(
                lambda v, a: resnet_fused_infer(v, a, stage_sizes=sizes))(variables, x))

        text = jax.jit(lambda v, a: resnet_fused_infer(v, a, stage_sizes=sizes)).lower(
            variables, x).as_text(debug_info=True)
        for scope in ("stem", "stage1", "stage4", "head"):
            assert scope in text, scope
        with_scopes = run()
        _without_scopes(monkeypatch)
        jax.clear_caches()
        np.testing.assert_array_equal(with_scopes, run())


# ---------------------------------------------------------------------------
# the benchmark's own checks still stand with what this PR added to it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("script", ["check_scope.py", "check_reduce.py", "check_manifest.py"])
def test_benchmark_check_scripts_pass(script):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmark", script)],
        capture_output=True, text=True, timeout=300, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
