"""Multi-detector fan-in tests (BASELINE config 5).

Two detectors with different geometries stream through one FanInPipeline;
each detector's step must compile exactly once (fixed per-detector shapes
— the whole point of per-detector batchers) and every frame from both
streams must be processed before the loop ends.
"""

import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from psana_ray_tpu.infeed import DetectorStream, FanInPipeline
from psana_ray_tpu.records import EndOfStream, FrameRecord
from psana_ray_tpu.transport import RingBuffer, TransportClosed
from psana_ray_tpu.transport.shm_ring import ShmRingBuffer, native_available
from psana_ray_tpu.utils.bufpool import WIRE

EPIX_SHAPE = (2, 16, 24)  # scaled-down epix10k2M (16, 352, 384)
JF_SHAPE = (1, 32, 8)  # scaled-down jungfrau4M (8, 512, 1024)


def _produce(queue, shape, n, delay_s=0.0, base=0.0):
    # a closed transport is a clean producer exit, same as the real
    # ProducerRuntime (producer.py) — keeps early-close tests warning-free
    try:
        for i in range(n):
            frame = np.full(shape, base + i, dtype=np.float32)
            rec = FrameRecord(0, i, frame, 9.5)
            while not queue.put(rec):
                time.sleep(0.0005)
            if delay_s:
                time.sleep(delay_s)
        assert queue.put_wait(EndOfStream(total_events=n), timeout=30.0)
    except TransportClosed:
        return


def _start_producers(specs):
    """specs: [(queue, shape, n, delay_s), ...] -> joined-later threads."""
    threads = [
        threading.Thread(target=_produce, args=spec, daemon=True) for spec in specs
    ]
    for t in threads:
        t.start()
    return threads


# A detector's ingest process, as deployed: its own interpreter, no JAX,
# frame i filled with i so a consumer can tell a torn or doubled one.
_SHM_PRODUCER = """
import sys, time
import numpy as np
from psana_ray_tpu.records import EndOfStream, FrameRecord
from psana_ray_tpu.transport.shm_ring import ShmRingBuffer
name, n, shape = sys.argv[1], int(sys.argv[2]), tuple(int(x) for x in sys.argv[3].split(","))
ring = ShmRingBuffer.attach(name, retries=40, interval_s=0.25)
for i in range(n):
    rec = FrameRecord(0, i, np.full(shape, i % 4096, np.uint16), 9.5)
    while not ring.put(rec):
        time.sleep(0.001)
assert ring.put_wait(EndOfStream(total_events=n), timeout=120.0)
ring.disconnect()
"""


@pytest.mark.skipif(not native_available(), reason="native toolchain unavailable")
@pytest.mark.parametrize(
    "shapes,counts,batches",
    [
        # frame-bound: few large frames, tails that pad (37 % 8, 19 % 4)
        pytest.param(((4, 64, 96), (2, 128, 64)), (37, 19), (8, 4), id="frame_bound"),
        # record-bound: many small frames, the rings wrap dozens of times
        pytest.param(((1, 8, 16), (1, 16, 8)), (600, 300), (64, 32), id="record_bound"),
    ],
)
def test_two_processes_two_shm_rings_no_loss_no_double_one_copy(shapes, counts, batches):
    """Two detectors, each its own producer PROCESS and shm ring, merged
    by one FanInPipeline on the host: every frame arrives exactly once,
    intact, and the consumer side pays ONE payload copy a frame (slot
    view -> batch arena; the producers' copies into their slots are
    theirs, in their processes)."""
    names = ("epix", "jf")
    ring_names = {det: f"fanin_{det}_{os.getpid()}_{time.monotonic_ns()}" for det in names}
    rings, procs = {}, []
    try:
        for det, shape in zip(names, shapes):
            rings[det] = ShmRingBuffer.create(
                ring_names[det], maxsize=8, slot_bytes=int(np.prod(shape)) * 2 + 4096
            )
        fan = FanInPipeline(
            [
                DetectorStream(det, rings[det], batch_size=b, poll_interval_s=0.002,
                               place_on_device=False)
                for det, b in zip(names, batches)
            ]
        )
        c0 = WIRE.stats()
        for det, shape, n in zip(names, shapes, counts):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _SHM_PRODUCER, ring_names[det], str(n),
                 ",".join(map(str, shape))],
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ))
        seen = {det: [] for det in names}

        def on_result(name, out, batch):
            idx = batch.event_idx[: batch.num_valid]
            # frame i is all-i: a torn or recycled slot would not be
            assert (batch.frames[: batch.num_valid].reshape(len(idx), -1)
                    == (idx % 4096)[:, None]).all()
            seen[name].extend(int(i) for i in idx)

        got = fan.run({det: (lambda b: None) for det in names}, on_result=on_result)
        for p in procs:
            assert p.wait(timeout=60) == 0
        c1 = WIRE.stats()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for r in rings.values():
            r.destroy()
    assert got == dict(zip(names, counts))
    for det, n in zip(names, counts):
        assert sorted(seen[det]) == list(range(n)), f"{det}: lost or doubled frames"
    assert c1["copies_total"] - c0["copies_total"] == sum(counts)
    assert c1["bytes_copied_total"] - c0["bytes_copied_total"] == sum(
        n * int(np.prod(s)) * 2 for n, s in zip(counts, shapes)
    )


class TestFanInPipeline:
    def test_two_detectors_all_frames_one_compile_each(self):
        n_epix, n_jf = 10, 25
        q_epix, q_jf = RingBuffer(maxsize=16), RingBuffer(maxsize=16)
        producers = _start_producers(
            [(q_epix, EPIX_SHAPE, n_epix, 0.0), (q_jf, JF_SHAPE, n_jf, 0.0)]
        )
        fan = FanInPipeline(
            [
                DetectorStream("epix10k2M", q_epix, batch_size=4, poll_interval_s=0.001),
                DetectorStream("jungfrau4M", q_jf, batch_size=8, poll_interval_s=0.001),
            ]
        )
        traces = {"epix10k2M": 0, "jungfrau4M": 0}
        sums = {"epix10k2M": 0.0, "jungfrau4M": 0.0}

        def make_step(name):
            @jax.jit
            def step(frames, valid):
                traces[name] += 1  # python body runs once per (re)trace
                keep = valid.astype(frames.dtype).reshape(-1, 1, 1, 1)
                return jnp.sum(frames * keep)

            return lambda batch: step(batch.frames, batch.valid)

        steps = {name: make_step(name) for name in traces}

        def on_result(name, out, batch):
            sums[name] += float(out)

        counts = fan.run(steps, on_result=on_result, block_until_ready=True)
        for t in producers:
            t.join(timeout=10.0)

        assert counts == {"epix10k2M": n_epix, "jungfrau4M": n_jf}
        # no recompile churn: one trace per detector despite padded tails
        assert traces == {"epix10k2M": 1, "jungfrau4M": 1}
        # every frame's payload arrived intact (frame i is all-i)
        assert sums["epix10k2M"] == pytest.approx(
            sum(range(n_epix)) * np.prod(EPIX_SHAPE)
        )
        assert sums["jungfrau4M"] == pytest.approx(
            sum(range(n_jf)) * np.prod(JF_SHAPE)
        )
        assert fan.metrics["jungfrau4M"].frames.count == n_jf

    def test_fast_stream_not_blocked_by_slow(self):
        """Ready-ordered merge: the fast detector's whole stream completes
        while the slow producer is still trickling (no head-of-line
        blocking behind the slow stream's pending EOS)."""
        q_fast, q_slow = RingBuffer(maxsize=64), RingBuffer(maxsize=64)
        n_fast, n_slow = 32, 4
        producers = _start_producers(
            [(q_fast, JF_SHAPE, n_fast, 0.0), (q_slow, EPIX_SHAPE, n_slow, 0.05)]
        )
        fan = FanInPipeline(
            [
                DetectorStream("fast", q_fast, batch_size=8, poll_interval_s=0.001),
                DetectorStream("slow", q_slow, batch_size=4, poll_interval_s=0.001),
            ]
        )
        order = []
        for name, batch in fan:
            order.append(name)
        fan.close()
        for t in producers:
            t.join(timeout=10.0)
        # all fast batches arrive before the slow stream's final batch
        last_fast = len(order) - 1 - order[::-1].index("fast")
        last_slow = len(order) - 1 - order[::-1].index("slow")
        assert last_fast < last_slow
        assert order.count("fast") == n_fast // 8

    def test_missing_step_raises(self):
        q = RingBuffer(maxsize=4)
        fan = FanInPipeline([DetectorStream("epix10k2M", q, batch_size=2)])
        with pytest.raises(KeyError, match="epix10k2M"):
            fan.run({"jungfrau4M": lambda b: None})
        fan.close()
        q.close()

    def test_duplicate_names_rejected(self):
        q1, q2 = RingBuffer(maxsize=4), RingBuffer(maxsize=4)
        with pytest.raises(ValueError, match="duplicate"):
            FanInPipeline(
                [DetectorStream("d", q1, batch_size=2), DetectorStream("d", q2, batch_size=2)]
            )
        q1.close(), q2.close()

    def test_stream_error_propagates(self):
        """A mis-shaped frame inside one stream surfaces to the consumer
        (after the other stream drains) instead of hanging the loop."""
        q_ok, q_bad = RingBuffer(maxsize=16), RingBuffer(maxsize=16)
        producers = _start_producers([(q_ok, JF_SHAPE, 8, 0.0)])
        q_bad.put(FrameRecord(0, 0, np.zeros(EPIX_SHAPE, np.float32), 9.5))
        q_bad.put(FrameRecord(0, 1, np.zeros(JF_SHAPE, np.float32), 9.5))  # mismatch
        q_bad.put(EndOfStream())
        fan = FanInPipeline(
            [
                DetectorStream("ok", q_ok, batch_size=4, poll_interval_s=0.001),
                DetectorStream("bad", q_bad, batch_size=4, poll_interval_s=0.001),
            ]
        )
        with pytest.raises(ValueError, match="locked shape"):
            fan.run({"ok": lambda b: None, "bad": lambda b: None})
        for t in producers:
            t.join(timeout=10.0)

    def test_dead_stream_surfaces_while_other_still_live(self):
        """A failed leg raises promptly even though the healthy detector
        keeps streaming with no EOS in sight (continuous multi-run mode —
        a dead detector must not stay silent until global EOS)."""
        q_live, q_bad = RingBuffer(maxsize=64), RingBuffer(maxsize=64)
        stop = threading.Event()

        def trickle():
            i = 0
            while not stop.is_set():
                q_live.put(FrameRecord(0, i, np.zeros(JF_SHAPE, np.float32), 9.5))
                i += 1
                time.sleep(0.002)

        live_thread = threading.Thread(target=trickle, daemon=True)
        live_thread.start()
        q_bad.put(FrameRecord(0, 0, np.zeros(EPIX_SHAPE, np.float32), 9.5))
        q_bad.put(FrameRecord(0, 1, np.zeros(JF_SHAPE, np.float32), 9.5))  # mismatch
        fan = FanInPipeline(
            [
                DetectorStream("live", q_live, batch_size=4, poll_interval_s=0.001),
                DetectorStream("bad", q_bad, batch_size=4, poll_interval_s=0.001),
            ]
        )
        t0 = time.monotonic()
        with pytest.raises(ValueError, match="locked shape"):
            fan.run({"live": lambda b: None, "bad": lambda b: None})
        assert time.monotonic() - t0 < 10.0
        stop.set()
        live_thread.join(timeout=5.0)
        q_live.close()

    def test_cross_thread_close_unblocks_starved_consumer(self):
        """close() from a watchdog thread must wake a consumer blocked on
        the merge queue AND stop a leg parked in a starved transport poll
        (neither EOS nor frames ever arrive)."""
        q = RingBuffer(maxsize=8)
        fan = FanInPipeline(
            [DetectorStream("d", q, batch_size=2, poll_interval_s=0.001)]
        )
        seen = []
        consumer = threading.Thread(
            target=lambda: seen.extend(iter(fan)), daemon=True
        )
        consumer.start()
        time.sleep(0.1)
        t0 = time.monotonic()
        fan.close()
        consumer.join(timeout=5.0)
        assert not consumer.is_alive()
        assert time.monotonic() - t0 < 2.0
        for th in fan._threads:
            assert not th.is_alive()
        assert seen == []
        q.close()

    def test_early_close_joins_threads(self):
        q = RingBuffer(maxsize=8)
        producers = _start_producers([(q, JF_SHAPE, 64, 0.0)])
        fan = FanInPipeline([DetectorStream("d", q, batch_size=4, poll_interval_s=0.001)])
        it = iter(fan)
        next(it)
        fan.close()
        for t in fan._threads:
            assert not t.is_alive()
        q.close()
        for t in producers:
            t.join(timeout=10.0)
