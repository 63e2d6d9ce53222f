"""Zero-copy host datapath (ISSUE 2): wire codec buffer-protocol edge
cases, scatter-gather equivalence, pool lease discipline, and the
copy-count pin — consumer-side copies/frame on the TCP path is EXACTLY
one (the batch-arena memcpy), with steady-state recv allocations zero.
"""

import threading
import time

import numpy as np
import pytest

from psana_ray_tpu.infeed.batcher import FrameBatcher, batches_from_queue
from psana_ray_tpu.records import EndOfStream, FrameRecord, decode
from psana_ray_tpu.transport.codec import (
    decode_payload,
    encode_payload,
    encode_payload_parts,
    payload_nbytes,
)
from psana_ray_tpu.transport.ring import RingBuffer
from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
from psana_ray_tpu.utils.bufpool import WIRE, BufferPool


def _rec(idx=0, shape=(2, 4, 8), dtype=np.float32, rank=1, energy=9.5):
    panels = np.arange(int(np.prod(shape)), dtype=dtype).reshape(shape) + idx
    return FrameRecord(rank, idx, panels, energy, timestamp=1.25)


class TestWirePartsEquivalence:
    """wire_parts() must be byte-for-byte to_bytes() — the scatter-gather
    sender and any legacy contiguous consumer read the same stream."""

    def test_contiguous_roundtrip(self):
        rec = _rec()
        header, payload = rec.wire_parts()
        assert isinstance(payload, memoryview)
        assert header + payload.tobytes() == rec.to_bytes()
        assert decode(rec.to_bytes()).equals(rec)

    def test_zero_copy_payload_is_a_view(self):
        rec = _rec()
        _, payload = rec.wire_parts()
        # same memory, not a copy: writing through the record shows in
        # the payload view (contiguous panels only)
        base = np.frombuffer(payload, dtype=rec.panels.dtype)
        assert base[0] == rec.panels.ravel()[0]
        assert np.shares_memory(np.asarray(rec.panels), base)

    @pytest.mark.parametrize("dtype", [np.uint16, np.float64, np.int16, np.uint8])
    def test_dtype_shape_roundtrip(self, dtype):
        rec = _rec(shape=(3, 5, 7), dtype=dtype)
        header, payload = rec.wire_parts()
        out = decode(header + payload.tobytes())
        assert out.equals(rec)
        assert out.panels.dtype == np.dtype(dtype)
        assert out.panels.shape == (3, 5, 7)

    def test_non_contiguous_panels(self):
        # strided slice: wire_parts must emit the contiguous content
        full = np.arange(2 * 4 * 12, dtype=np.float32).reshape(2, 4, 12)
        rec = FrameRecord(0, 3, full[:, :, ::3], 7.5)
        assert not rec.panels.flags.c_contiguous
        header, payload = rec.wire_parts()
        assert header + payload.tobytes() == rec.to_bytes()
        assert decode(rec.to_bytes()).equals(rec)

    def test_encode_parts_matches_encode_payload(self):
        for item in (_rec(), EndOfStream(producer_rank=2, total_events=5), {"x": 1}):
            parts = encode_payload_parts(item)
            flat = b"".join(bytes(p) for p in parts)
            assert flat == encode_payload(item)
            assert payload_nbytes(parts) == len(flat)


class TestLeasedDecode:
    def test_decode_view_into_pooled_buffer(self):
        pool = BufferPool()
        rec = _rec(shape=(2, 8, 8))
        wire = rec.to_bytes()
        lease = pool.lease(len(wire))
        lease.mv[:] = wire
        out = decode(lease.mv, lease=lease)
        assert out.equals(rec)
        assert out.lease is lease
        # zero-copy: the panels view the pooled buffer
        assert np.shares_memory(
            np.asarray(out.panels), np.frombuffer(lease.mv, dtype=np.uint8)
        )
        assert pool.stats()["leases"] == 1
        out.release()
        assert out.lease is None
        assert pool.stats()["leases"] == 0
        out.release()  # idempotent

    def test_memoryview_slice_of_pooled_buffer(self):
        # tagged-payload form: decode_payload sees a SLICE of the lease
        pool = BufferPool()
        rec = _rec(shape=(1, 4, 4), dtype=np.uint16)
        payload = encode_payload(rec)
        lease = pool.lease(len(payload))
        lease.mv[:] = payload
        out = decode_payload(lease.mv, lease=lease)
        assert out.equals(rec) and out.lease is lease
        out.release()
        assert pool.stats()["leases"] == 0

    def test_non_record_payload_releases_lease_after_parse(self):
        pool = BufferPool()
        payload = encode_payload({"k": list(range(100))})
        lease = pool.lease(len(payload))
        lease.mv[:] = payload
        out = decode_payload(lease.mv, lease=lease)
        assert out == {"k": list(range(100))}
        assert pool.stats()["leases"] == 0

    def test_gc_releases_dropped_record(self):
        pool = BufferPool()
        rec = _rec()
        lease = pool.lease(len(rec.to_bytes()))
        lease.mv[:] = rec.to_bytes()
        out = decode(lease.mv, lease=lease)
        del lease
        assert pool.stats()["leases"] == 1
        del out  # CPython refcount drop -> Lease.__del__ -> release
        assert pool.stats()["leases"] == 0

    def test_materialize_detaches_from_lease(self):
        pool = BufferPool()
        rec = _rec(shape=(2, 4, 4))
        lease = pool.lease(len(rec.to_bytes()))
        lease.mv[:] = rec.to_bytes()
        out = decode(lease.mv, lease=lease)
        owned = out.materialize()
        assert pool.stats()["leases"] == 0  # released by materialize
        assert owned.lease is None and owned.equals(rec)
        # buffer reuse cannot corrupt the materialized copy
        lease2 = pool.lease(len(rec.to_bytes()))
        lease2.mv[:] = b"\xff" * len(lease2.mv)
        assert owned.equals(rec)
        lease2.release()

    def test_push_view_releases_after_copy(self):
        pool = BufferPool()
        batcher = FrameBatcher(batch_size=2)
        recs = [_rec(i) for i in range(2)]
        for i, r in enumerate(recs):
            wire = r.to_bytes()
            lease = pool.lease(len(wire))
            lease.mv[:] = wire
            view = decode(lease.mv, lease=lease)
            out = batcher.push_view(view)
            assert pool.stats()["leases"] == 0  # released right after copy
        assert out is not None
        np.testing.assert_array_equal(out.frames[0], recs[0].panels)
        np.testing.assert_array_equal(out.frames[1], recs[1].panels)


class TestBufferPool:
    def test_hit_after_release(self):
        pool = BufferPool()
        a = pool.lease(1000)
        a.release()
        b = pool.lease(900)  # same 4 KB class
        s = pool.stats()
        assert s["hits"] == 1 and s["misses"] == 1
        b.release()

    def test_adaptive_retention_tracks_peak(self):
        pool = BufferPool(min_per_class=1)
        burst = [pool.lease(5000) for _ in range(8)]
        for le in burst:
            le.release()
        # all 8 existed concurrently: all are retained and re-leasable
        again = [pool.lease(5000) for _ in range(8)]
        assert pool.stats()["misses"] == 8  # only the initial burst
        assert pool.stats()["hits"] == 8
        for le in again:
            le.release()

    def test_retention_decays_after_burst(self):
        # a one-time burst must not pin its high-water of memory forever:
        # the per-class peak decays toward the live working set
        pool = BufferPool(min_per_class=1)
        burst = [pool.lease(5000) for _ in range(8)]
        for le in burst:
            le.release()
        assert pool.stats()["bytes_pooled"] == 8 * 8192
        for _ in range(pool.DECAY_EVERY * 8):  # steady state: 1 at a time
            pool.lease(5000).release()
        assert pool.stats()["bytes_pooled"] <= 2 * 8192

    def test_oversized_wire_length_rejected(self):
        # a corrupt/hostile u32 length must not size a pool lease
        import socket as socket_mod

        from psana_ray_tpu.transport.tcp import _MAX_PAYLOAD, _recv_payload

        a, b = socket_mod.socketpair()
        try:
            with pytest.raises(ConnectionError, match="wire maximum"):
                _recv_payload(a, _MAX_PAYLOAD + 1, BufferPool())
        finally:
            a.close()
            b.close()

    def test_leak_tracking_in_debug_mode(self):
        pool = BufferPool(debug=True)
        lease = pool.lease(64)
        assert len(pool.leaks()) == 1
        lease.release()
        assert pool.leaks() == []


class TestTcpCopyCount:
    """THE acceptance pin: over a real TCP server, consumer-side
    copies/frame == 1 (the batch-arena memcpy) and steady-state recv
    allocations come from the pool, not malloc — on BOTH drain modes
    (request/response pull and the ISSUE 5 server-push stream the
    batcher now prefers)."""

    def _run_relay(
        self, n, prefer_stream, pool=None, codec=None, shape=(2, 16, 16), stamp=None
    ):
        """``stamp(rec, port) -> rec`` is the producer's hook on each
        frame (the tracer's sampling gate; an observer of the live server
        on ``port``)."""
        q = RingBuffer(16)
        srv = TcpQueueServer(q, host="127.0.0.1", pool=pool).serve_background()
        prod = TcpQueueClient("127.0.0.1", srv.port, pool=pool, codec=codec)
        cons = TcpQueueClient("127.0.0.1", srv.port, pool=pool, codec=codec)
        try:

            def produce():
                for i in range(n):
                    rec = _rec(i, shape=shape)
                    assert prod.put_wait(stamp(rec, srv.port) if stamp else rec, timeout=30)
                assert prod.put_wait(EndOfStream(total_events=n), timeout=30)

            t = threading.Thread(target=produce, daemon=True)
            c0 = WIRE.stats()
            t.start()
            seen = 0
            for batch in batches_from_queue(
                cons, 8, poll_interval_s=0.002, prefer_stream=prefer_stream
            ):
                seen += batch.num_valid
            t.join()
            assert seen == n
            if prefer_stream:
                assert cons._stream is not None  # the drain actually streamed
            d = WIRE.stats()
            return (
                d["copies_total"] - c0["copies_total"],
                d["bytes_copied_total"] - c0["bytes_copied_total"],
            )
        finally:
            prod.disconnect()
            cons.disconnect()
            srv.shutdown()
            # at-least-once tail: if the server processes the stream
            # conn's death before the disconnect's final cumulative ack
            # (a race a CPU-starved box widens), the tail frames requeue
            # — RETAINED by the queue for redelivery, not leaked. After
            # shutdown every requeue has landed; hand those leases back
            # so the zero-leak pins below measure leaks, not the
            # redelivery guarantee.
            from psana_ray_tpu.transport.ring import EMPTY as _EMPTY

            while True:
                item = q.get()
                if item is _EMPTY:
                    break
                release = getattr(item, "release", None)
                if release is not None:
                    release()

    @staticmethod
    def _assert_no_churn_no_leaks(pool, what):
        """Zero pool-churn allocations on an instrumented private pool
        (working-set growth up to the credit window is not churn), and
        every lease back once the drain's final ack has landed."""
        s = pool.stats()
        assert s["churn_misses"] == 0, (
            f"{what} churned {s['churn_misses']} allocations (pool: {s})"
        )
        # the last pushed window stays leased until the client's final
        # cumulative ack (sent at disconnect) prunes it server-side —
        # that retention IS the redelivery guarantee, so allow the
        # asynchronous prune a moment before calling anything a leak
        # (10 s: under a CPU-share-throttled full tier-1 run the prune
        # + record GC episodically exceeded the old 2 s grace — a leak
        # never clears however long we wait, so the wider window only
        # trades flake for patience)
        deadline = time.monotonic() + 10.0
        while pool.stats()["leases"] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.stats()["leases"] == 0, (
            f"{what} leaked leases after drain+ack: {pool.stats()}"
        )

    def test_consumer_side_exactly_one_copy_per_frame(self):
        n = 24
        copies, nbytes = self._run_relay(n, prefer_stream=False)
        assert copies == n, f"expected exactly 1 copy/frame, got {copies}/{n}"
        assert nbytes == n * _rec(0, shape=(2, 16, 16)).nbytes

    def test_streaming_drain_exactly_one_copy_zero_alloc_per_frame(self):
        """ISSUE 5 acceptance: the server-push stream preserves the
        zero-copy discipline — copies/frame == 1.00 AND zero pool-churn
        allocations (every recv lease recycled; working-set growth up to
        the credit window is not churn), measured on an instrumented
        private pool."""
        from psana_ray_tpu.utils.bufpool import BufferPool

        pool = BufferPool()
        n = 24
        copies, nbytes = self._run_relay(n, prefer_stream=True, pool=pool)
        assert copies == n, f"expected exactly 1 copy/frame, got {copies}/{n}"
        assert nbytes == n * _rec(0, shape=(2, 16, 16)).nbytes
        self._assert_no_churn_no_leaks(pool, "streaming path")

    def test_compressed_streaming_one_copy_zero_alloc_zero_leaks(self):
        """ISSUE 9 acceptance pin: the NEGOTIATED-CODEC streaming path
        keeps the zero-copy discipline — copies/frame == 1.00 (the
        batch-arena memcpy; compress/decompress stage through pool
        leases, never fresh allocations or extra payload memcpys),
        steady-state pool churn == 0, and zero leaked leases after the
        drain's final ack (compressed staging + pass-through cache +
        decompressed-panel leases all recycle)."""
        from psana_ray_tpu.transport.codec import CODEC_STATS

        pool = BufferPool()
        n = 24
        # big enough to clear WIRE_COMPRESS_MIN — the pin must exercise
        # the codec, not the too-small passthrough
        shape = (2, 32, 32)
        s0 = CODEC_STATS.stats()
        copies, nbytes = self._run_relay(
            n, prefer_stream=True, pool=pool, codec="shuffle-rle", shape=shape
        )
        s1 = CODEC_STATS.stats()
        # the pin only means something if the codec actually engaged
        assert s1["frames_compressed_total"] > s0["frames_compressed_total"]
        assert copies == n, f"expected exactly 1 copy/frame, got {copies}/{n}"
        assert nbytes == n * _rec(0, shape=shape).nbytes
        self._assert_no_churn_no_leaks(pool, "compressed streaming")

    @pytest.mark.parametrize("prefer_stream", [False, True], ids=["pull", "stream"])
    @pytest.mark.parametrize("observer", ["tracer_1_in_16", "flame_sampler", "telemetry_plane"])
    def test_observed_relay_still_one_copy_zero_alloc(self, observer, prefer_stream, tmp_path):
        """What watches the relay reads counters and stacks, never
        frames: with the tracer sampling 1 frame in 16, with the 97 Hz
        flame sampler on this process's threads, and with the history
        sampler and a federation collector sweeping the live server
        mid-stream, both drain modes still make exactly one copy a frame
        and churn no allocation. Each observer is made to act WHILE
        frames are in flight (the producer's hook waits for it), so the
        pin cannot pass by finishing first."""
        import dataclasses

        from psana_ray_tpu.obs.collector import ClusterCollector
        from psana_ray_tpu.obs.profiling import FlameSampler
        from psana_ray_tpu.obs.timeseries import HistorySampler
        from psana_ray_tpu.obs.tracing import TRACER

        pool = BufferPool()
        n = 48
        acted = []  # what the observer did while the stream ran

        if observer == "tracer_1_in_16":
            TRACER.configure(str(tmp_path), sample_every=16, process="relay")

            def stamp(rec, port):
                return dataclasses.replace(rec, trace=TRACER.maybe_trace())

            def stop():
                acted.append(TRACER.snapshot()["spans_total"])
                TRACER.close()

        elif observer == "flame_sampler":
            flame = FlameSampler(hz=97.0, process="relay", register=False).start()

            def stamp(rec, port):
                if rec.event_idx % 16 == 8:  # a sample lands between two frames
                    seen, deadline = flame.trie.samples_total, time.monotonic() + 5.0
                    while flame.trie.samples_total == seen and time.monotonic() < deadline:
                        time.sleep(0.002)
                    acted.append(flame.trie.samples_total - seen)
                return rec

            def stop():
                flame.stop(write_spool=False)

        else:
            history = HistorySampler(interval_s=3600.0)  # swept by hand, below
            collector = []  # needs the live server's port: made mid-stream

            def stamp(rec, port):
                if rec.event_idx % 16 == 8:
                    if not collector:
                        collector.append(ClusterCollector(
                            [f"127.0.0.1:{port}"], interval_s=3600.0, register=False
                        ))
                    history.sample_once()
                    acted.append(collector[0].poll_once())
                return rec

            def stop():
                for c in collector:
                    c.stop()
                    assert c.snapshot()["pulls_ok_total"] == len(acted), c.snapshot()

        try:
            copies, nbytes = self._run_relay(n, prefer_stream, pool=pool, stamp=stamp)
        finally:
            stop()
        assert acted and all(acted), acted
        assert copies == n, f"expected exactly 1 copy/frame, got {copies}/{n}"
        assert nbytes == n * _rec(0, shape=(2, 16, 16)).nbytes
        self._assert_no_churn_no_leaks(pool, f"relay under {observer}")

    def test_tcp_roundtrip_content_through_pool(self):
        # recycled buffers must never bleed between frames
        srv = TcpQueueServer(RingBuffer(4), host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            for i in range(12):
                rec = _rec(i, shape=(1, 32, 32), dtype=np.uint16)
                assert c.put(rec)
                out = c.get()
                assert out.equals(rec), f"frame {i} corrupted through pooled path"
        finally:
            c.disconnect()
            srv.shutdown()
