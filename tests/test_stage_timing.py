"""Per-record stage timing: hop stamps through the record envelope
decompose e2e latency into named stages (enqueue, queue_dwell, dequeue,
batch, device_put, dispatch).

Acceptance (ISSUE 1): on a synthetic-source e2e run the per-stage sum is
within 20% of the measured e2e latency. The decomposition is telescoping
(consecutive differences of one record's timeline), so the per-record sum
is EXACT; the 20% tolerance covers the reservoir/mean estimators only."""

import threading
import time

import numpy as np
import pytest

from psana_ray_tpu.infeed import InfeedPipeline
from psana_ray_tpu.obs.stages import (
    HOP_ENQ,
    HOP_SRC,
    STAGE_E2E,
    STAGES,
    observe_record_stages,
)
from psana_ray_tpu.records import EndOfStream, FrameRecord, mark_hop
from psana_ray_tpu.transport.ring import RingBuffer
from psana_ray_tpu.utils.metrics import StageTimes


def _make_record(i, shape=(1, 8, 8)):
    return FrameRecord(0, i, np.full(shape, float(i), np.float32), 9.0)


class TestHopStamps:
    def test_mark_hop_lazy_allocation(self):
        rec = _make_record(0)
        assert rec.hops is None  # zero cost until someone times the stream
        mark_hop(rec, HOP_SRC)
        assert HOP_SRC in rec.hops
        mark_hop(rec, HOP_ENQ, t=123.0)
        assert rec.hops[HOP_ENQ] == 123.0

    def test_mark_hop_ignores_non_frames(self):
        eos = EndOfStream(total_events=4)
        mark_hop(eos, HOP_SRC)  # no-op, no crash

    def test_hops_never_cross_the_wire(self):
        rec = _make_record(1)
        mark_hop(rec, HOP_SRC)
        back = FrameRecord.from_bytes(rec.to_bytes())
        assert back.hops is None  # monotonic stamps are process-local

    def test_telescoping_with_missing_boundary(self):
        st = StageTimes()
        # 'deq' missing: the stage ending at the next boundary ('push' ->
        # dequeue) absorbs the gap; stages still sum to last-first
        hops = {"src": 0.0, "enq": 1.0, "push": 4.0, "batch": 5.0, "device_put": 6.0}
        observe_record_stages(st, hops, t_end=8.0)
        snap = st.snapshot()
        total = sum(
            snap[s]["mean_ms"] for s in STAGES if s in snap
        )
        assert total == pytest.approx(8.0 * 1e3)
        assert snap[STAGE_E2E]["mean_ms"] == pytest.approx(8.0 * 1e3)


class TestE2EDecomposition:
    @pytest.mark.parametrize("batch_size", [4])
    def test_stage_sum_matches_e2e(self, batch_size):
        """Synthetic source -> ring -> batcher -> device_put -> step, with
        every record stamped; per-stage means must sum to the e2e mean
        (exactly, modulo estimator noise — assert the 20% criterion)."""
        n = 32
        queue = RingBuffer(maxsize=8)

        def produce():
            for i in range(n):
                rec = _make_record(i)
                mark_hop(rec, HOP_SRC)
                while not queue.put(rec):
                    time.sleep(0.0005)
                mark_hop(rec, HOP_ENQ)
                if i % 8 == 3:
                    time.sleep(0.002)  # visible queue-dwell variation
            assert queue.put_wait(EndOfStream(total_events=n), timeout=30.0)

        t_prod = threading.Thread(target=produce, daemon=True)
        pipe = InfeedPipeline(
            queue, batch_size=batch_size, prefetch_depth=2, poll_interval_s=0.001
        )
        t_prod.start()
        seen = pipe.run(lambda b: b.frames.sum(), block_until_ready=True)
        t_prod.join()
        assert seen == n

        snap = pipe.metrics.stages.snapshot()
        # every named stage observed: what differs by frame once per
        # record, what is the same for a whole batch once per batch
        n_batches = n // batch_size
        per_batch = ("device_put", "dispatch")
        for stage in STAGES:
            assert stage in snap, f"stage {stage!r} missing from {sorted(snap)}"
            assert snap[stage]["count"] == (n_batches if stage in per_batch else n)
        assert snap[STAGE_E2E]["count"] == n

        # every batch is full, so a mean over batches is a mean over frames
        stage_sum = sum(snap[s]["mean_ms"] for s in STAGES)
        e2e = snap[STAGE_E2E]["mean_ms"]
        assert e2e > 0
        # acceptance: decomposition within 20% of measured e2e
        assert stage_sum == pytest.approx(e2e, rel=0.20)
        # queue-dwell must have picked up the injected producer sleeps
        assert snap["queue_dwell"]["mean_ms"] > 0

    def test_untimed_stream_records_no_frame_stages(self):
        """Zero-cost-when-disabled: without mark_hop the same pipeline
        run observes nothing per frame (batch.hops stays None end to
        end) — only its loops' own phases, once per batch."""
        n = 8
        queue = RingBuffer(maxsize=8)

        def produce():
            for i in range(n):
                while not queue.put(_make_record(i)):
                    time.sleep(0.0005)
            assert queue.put_wait(EndOfStream(total_events=n), timeout=30.0)

        t_prod = threading.Thread(target=produce, daemon=True)
        pipe = InfeedPipeline(queue, batch_size=4, poll_interval_s=0.001)
        t_prod.start()
        seen = pipe.run(lambda b: b.frames.sum(), block_until_ready=True)
        t_prod.join()
        assert seen == n
        snap = pipe.metrics.stages.snapshot()
        assert not set(snap) & {"enqueue", "queue_dwell", "dequeue", "batch", "dispatch", "e2e"}
        for name in ("device_put", "launch", "device_wait"):
            assert snap[name]["count"] == n // 4

    def test_stages_flow_to_prometheus(self):
        """The same histograms surface as psana_ray_stages_* gauges."""
        import re

        from psana_ray_tpu.obs import MetricsRegistry

        n = 8
        queue = RingBuffer(maxsize=8)

        def produce():
            for i in range(n):
                rec = _make_record(i)
                mark_hop(rec, HOP_SRC)
                while not queue.put(rec):
                    time.sleep(0.0005)
                mark_hop(rec, HOP_ENQ)
            assert queue.put_wait(EndOfStream(total_events=n), timeout=30.0)

        t_prod = threading.Thread(target=produce, daemon=True)
        pipe = InfeedPipeline(queue, batch_size=4, poll_interval_s=0.001)
        t_prod.start()
        pipe.run(lambda b: b.frames.sum(), block_until_ready=True)
        t_prod.join()

        reg = MetricsRegistry()
        reg.register("consumer", pipe.metrics)
        text = reg.render_prometheus()
        for stage in STAGES:
            pat = rf'^psana_ray_stages_{stage}_p50_ms\{{source="consumer"\}} \S+$'
            assert re.search(pat, text, re.M), f"missing {stage} gauge in:\n{text}"

    def test_named_pipeline_registers_and_unregisters(self):
        from psana_ray_tpu.obs import MetricsRegistry

        queue = RingBuffer(maxsize=8)
        queue.put(EndOfStream(total_events=0))
        pipe = InfeedPipeline(queue, batch_size=4, poll_interval_s=0.001, name="epix")
        assert "infeed.epix" in MetricsRegistry.default().sources()
        pipe.run(lambda b: b.frames.sum())
        assert "infeed.epix" not in MetricsRegistry.default().sources()
