"""Tests for ISSUE 4: sampled per-frame distributed tracing.

Covers the satellite test checklist: trace-context wire round-trips over
TCP and shm (sampled AND unsampled — the unsampled wire stays
byte-identical v2), the zero-allocation pin on the unsampled hot path,
the clock-anchor RPC, span emission through the batching pipeline, and
the trace_merge golden-output test (3 handcrafted spools with known
monotonic offsets -> one valid Chrome trace-event JSON)."""

from __future__ import annotations

import gc
import json
import os
import sys

import numpy as np
import pytest

from psana_ray_tpu.obs.tracing import (
    TRACE_KEY,
    TRACER,
    TraceContext,
    Tracer,
    exchange_anchors,
)
from psana_ray_tpu.records import FrameRecord, decode, encode_into, encoded_size


@pytest.fixture
def tracer(tmp_path):
    t = Tracer()
    t.configure(str(tmp_path), sample_every=1, process="test")
    yield t
    t.close()


@pytest.fixture(autouse=True)
def _global_tracer_off():
    yield
    TRACER.close()


def _frame(trace=None, shape=(2, 8, 8)):
    return FrameRecord(
        0, 7, np.arange(np.prod(shape), dtype=np.uint16).reshape(shape),
        9.5, timestamp=123.5, trace=trace,
    )


CTX = TraceContext(trace_id=0x1234_5678_9ABC, origin_host="hosta", origin_pid=4242)


class TestContextWireFormat:
    def test_pack_unpack_round_trip(self):
        buf = CTX.pack()
        assert len(buf) == TraceContext.WIRE_SIZE == 25
        out = TraceContext.unpack_from(buf, 0)
        assert out == CTX

    def test_long_hostname_truncates_not_raises(self):
        ctx = TraceContext(1, True, "a-very-long-hostname.example.com", 1)
        out = TraceContext.unpack_from(ctx.pack(), 0)
        assert out.origin_host == "a-very-long-"  # 12-byte budget

    def test_sampled_frame_encodes_v3_with_context(self):
        rec = _frame(trace=CTX)
        out = FrameRecord.from_bytes(rec.to_bytes())
        assert out.schema_version == 3
        assert out.trace == CTX
        assert out.equals(rec)

    def test_unsampled_frame_encodes_v2_byte_identical(self):
        # THE zero-cost contract: no trace context -> the wire bytes are
        # exactly the pre-tracing v2 format (no extra bytes, no version
        # bump), so unsampled streams are indistinguishable from before
        rec = _frame()
        wire = rec.to_bytes()
        out = FrameRecord.from_bytes(wire)
        assert out.schema_version == 2 and out.trace is None
        assert encoded_size(rec) == len(wire)
        traced = _frame(trace=CTX)
        assert encoded_size(traced) == len(wire) + TraceContext.WIRE_SIZE

    def test_encode_into_matches_to_bytes_both_ways(self):
        for rec in (_frame(), _frame(trace=CTX)):
            buf = bytearray(encoded_size(rec))
            n = encode_into(rec, buf)
            assert n == len(buf) and buf == rec.to_bytes()
            out = decode(memoryview(buf))
            assert out.trace == rec.trace

    def test_materialize_carries_trace(self):
        rec = _frame(trace=CTX)
        assert rec.materialize().trace == CTX


class TestTcpRoundTrip:
    def test_sampled_and_unsampled_over_tcp(self):
        from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer

        srv = TcpQueueServer(host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            assert c.put(_frame(trace=CTX))
            assert c.put(_frame())
            a, b = c.get(), c.get()
            assert a.trace == CTX and a.equals(_frame(trace=CTX))
            assert b.trace is None
        finally:
            c.disconnect()
            srv.shutdown()

    def test_anchor_rpc(self):
        from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer

        srv = TcpQueueServer(host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            a = c.anchor()
            assert a["rtt_s"] >= 0
            assert a["send_mono"] <= a["recv_mono"]
            assert a["peer_wall"] > 0 and a["peer_mono"] > 0
        finally:
            c.disconnect()
            srv.shutdown()

    def test_exchange_anchors_spools_peer_records(self, tmp_path):
        from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer

        t = Tracer().configure(str(tmp_path), sample_every=1, process="c")
        srv = TcpQueueServer(host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            assert exchange_anchors(c, n=2, tracer=t) == 2
        finally:
            c.disconnect()
            srv.shutdown()
        t.close()
        lines = [json.loads(s) for s in open(t.spool_path) if s.strip()]
        assert sum(1 for r in lines if r["t"] == "p") == 2

    def test_exchange_anchors_noop_without_rpc(self, tracer):
        class Plain:
            pass

        assert exchange_anchors(Plain(), tracer=tracer) == 0


class TestShmRoundTrip:
    @pytest.fixture
    def ring(self):
        from psana_ray_tpu.transport.shm_ring import ShmRingBuffer, native_available

        if not native_available():
            pytest.skip("native shm ring unavailable")
        r = ShmRingBuffer.create(f"trace_rt_{os.getpid()}", maxsize=4)
        yield r
        r.destroy()

    def test_sampled_and_unsampled_over_shm(self, ring):
        assert ring.put(_frame(trace=CTX))
        assert ring.put(_frame())
        a, b = ring.get(), ring.get()
        assert a.trace == CTX and a.equals(_frame(trace=CTX))
        assert b.trace is None

    def test_zero_copy_view_keeps_trace(self, ring):
        assert ring.put(_frame(trace=CTX))
        rec = ring.get_view()
        try:
            assert rec.trace == CTX
        finally:
            rec.release()


class TestSamplingGate:
    def test_disabled_returns_none(self):
        assert Tracer().maybe_trace() is None

    def test_sample_every_n(self, tmp_path):
        t = Tracer().configure(str(tmp_path), sample_every=4, process="p")
        got = [t.maybe_trace() for _ in range(16)]
        assert sum(c is not None for c in got) == 4
        ids = [c.trace_id for c in got if c is not None]
        assert len(set(ids)) == 4  # unique per sampled frame
        t.close()

    def test_unsampled_path_is_allocation_free(self, tmp_path):
        """The zero-alloc pin: with tracing ENABLED, frames that miss the
        sample gate cost counter arithmetic only — no net allocations
        (the PR 1 stage_timing discipline, now pinned for tracing)."""
        t = Tracer().configure(str(tmp_path), sample_every=10_000_000, process="p")
        try:
            for _ in range(64):
                t.maybe_trace()  # warm any int caching
            gc.disable()
            try:
                gc.collect()
                before = sys.getallocatedblocks()
                for _ in range(10_000):
                    t.maybe_trace()
                after = sys.getallocatedblocks()
            finally:
                gc.enable()
            # a handful of blocks of allocator/freelist noise is fine; a
            # real per-frame allocation would show >= 10_000 blocks here
            assert after - before <= 16, (
                f"unsampled maybe_trace leaked {after - before} blocks "
                f"over 10k frames"
            )
        finally:
            t.close()

    def test_disabled_tracer_span_is_noop(self):
        t = Tracer()
        t.span(1, "x", 0.0, 1.0)  # must not raise, must not spool
        t.instant(1, "y", 0.0)
        assert t.snapshot()["spans_total"] == 0


class TestSpool:
    def test_spool_contains_meta_anchor_span(self, tmp_path):
        t = Tracer().configure(str(tmp_path), sample_every=1, process="prod")
        ctx = t.maybe_trace()
        t.span(ctx.trace_id, "enqueue", 1.0, 2.0)
        t.instant(ctx.trace_id, "produce", 1.0)
        t.close()
        lines = [json.loads(s) for s in open(t.spool_path) if s.strip()]
        kinds = [r["t"] for r in lines]
        assert kinds.count("m") == 1 and "a" in kinds
        spans = [r for r in lines if r["t"] == "s"]
        assert spans == [{"t": "s", "id": ctx.trace_id, "n": "enqueue", "a": 1.0, "b": 2.0}]
        meta = next(r for r in lines if r["t"] == "m")
        assert meta["process"] == "prod" and meta["every"] == 1

    def test_bounded_spool_drops_and_counts(self, tmp_path):
        t = Tracer().configure(str(tmp_path), sample_every=1, process="p", max_spans=3)
        for i in range(10):
            t.span(i, "s", 0.0, 1.0)
        snap = t.snapshot()
        t.close()
        assert snap["spans_total"] == 3 and snap["spans_dropped_total"] == 7

    def test_status_suffix_shows_rate_spans_flight(self, tmp_path):
        from psana_ray_tpu.obs.flight import FlightRecorder

        t = Tracer()
        assert t.status_suffix() == ""  # off: heartbeat line unchanged
        t.configure(str(tmp_path), sample_every=100, process="p")
        t.span(1, "s", 0.0, 1.0)
        fl = FlightRecorder()
        fl.record("eos_complete")
        suffix = t.status_suffix(fl)
        t.close()
        assert "trace[1/100 spans=1]" in suffix and "flight=1" in suffix


class TestBatchPathSpans:
    def test_batches_from_queue_stamps_traced_records(self, tracer, monkeypatch):
        import psana_ray_tpu.infeed.batcher as batcher_mod
        from psana_ray_tpu.infeed.batcher import batches_from_queue
        from psana_ray_tpu.records import EndOfStream
        from psana_ray_tpu.transport.ring import RingBuffer

        monkeypatch.setattr(batcher_mod, "TRACER", tracer)
        q = RingBuffer(16)
        ctx = tracer.maybe_trace()
        for i in range(3):
            q.put(_frame(trace=ctx if i == 0 else None))
        q.put(EndOfStream(total_events=3))
        batches = list(batches_from_queue(q, 3))
        assert len(batches) == 1
        hops = batches[0].hops
        assert hops is not None and len(hops) == 1  # only the traced record
        assert hops[0][TRACE_KEY] == ctx.trace_id

    def _spans(self, tracer):
        tracer.close()
        rows = [json.loads(s) for s in open(tracer.spool_path) if s.strip()]
        return [r for r in rows if r["t"] == "s"]

    def test_frame_spans_telescope_hops_and_name_their_batch(self, tracer):
        from psana_ray_tpu.obs.stages import (
            HOP_BATCH, HOP_DEQ, HOP_PUSH, StageTimes, observe_frame_stages,
        )

        class B:
            batch_id = 41
            hops = [{TRACE_KEY: 99, HOP_DEQ: 1.0, HOP_PUSH: 2.0, HOP_BATCH: 3.0}]

        st = StageTimes()
        observe_frame_stages(st, B(), tracer=tracer)
        # deq->push = dequeue, push->batch = batch; what comes after the
        # batch is emitted is the batch's, not the frame's (phase spans)
        assert [(r["n"], r["a"], r["b"], r["id"], r["j"]) for r in self._spans(tracer)] == [
            ("dequeue", 1.0, 2.0, 99, 41), ("batch", 2.0, 3.0, 99, 41),
        ]
        assert st.stages() == ["batch", "dequeue"]

    def test_no_duplicate_enqueue_span_in_process(self, tracer):
        # in-process transports share the hops dict with the producer,
        # whose _Sender.flush already emitted the enqueue span — the
        # batch walk must not replay the src->enq leg (but keeps the
        # enq->deq queue_dwell no server exists to emit)
        from psana_ray_tpu.obs.stages import (
            HOP_BATCH, HOP_DEQ, HOP_ENQ, HOP_PUSH, HOP_SRC, StageTimes,
            observe_frame_stages,
        )

        class B:
            batch_id = 1
            hops = [{
                TRACE_KEY: 7, HOP_SRC: 1.0, HOP_ENQ: 2.0, HOP_DEQ: 3.0,
                HOP_PUSH: 4.0, HOP_BATCH: 5.0,
            }]

        st = StageTimes()
        observe_frame_stages(st, B(), tracer=tracer)
        assert [r["n"] for r in self._spans(tracer)] == ["queue_dwell", "dequeue", "batch"]
        assert "enqueue" in st.stages()  # the histogram still has the leg

    def test_untraced_batch_is_free(self, tracer):
        from psana_ray_tpu.obs.stages import StageTimes, observe_frame_stages

        class B:
            batch_id = 1
            hops = None

        st = StageTimes()
        observe_frame_stages(st, B(), tracer=tracer)
        assert tracer.snapshot()["spans_total"] == 0 and st.stages() == []


def _write_spool(path, process, host, pid, mono_offset, spans, peers=()):
    """A handcrafted spool whose monotonic clock is ``mono_offset`` behind
    wall time (offset = wall - mono)."""
    wall0 = 1_000_000.0
    lines = [
        {"t": "m", "process": process, "host": host, "pid": pid, "every": 1,
         "start_wall": wall0, "start_mono": wall0 - mono_offset},
        {"t": "a", "wall": wall0, "mono": wall0 - mono_offset},
        {"t": "a", "wall": wall0 + 1.0, "mono": wall0 + 1.0 - mono_offset},
    ]
    for p in peers:
        lines.append({"t": "p", **p})
    for tid, name, a, b in spans:
        lines.append({"t": "s", "id": tid, "n": name, "a": a, "b": b})
    with open(path, "w") as f:
        f.write("\n".join(json.dumps(ln) for ln in lines) + "\n")


class TestTraceMergeGolden:
    """3 spool files -> one valid Chrome trace JSON with the per-process
    monotonic offsets applied (the satellite golden-output test)."""

    def _make_spools(self, tmp_path):
        wall = 1_000_000.0
        tid = 0xABC
        # three processes, three DIFFERENT monotonic epochs: producer's
        # mono runs 100s behind wall, server's 200s, consumer's 300s —
        # the same frame's spans only order correctly if each offset is
        # applied per process
        _write_spool(
            tmp_path / "producer-h-1.trace.jsonl", "producer", "h", 1, 100.0,
            [(tid, "enqueue", wall - 100.0 + 0.10, wall - 100.0 + 0.20)],
        )
        _write_spool(
            tmp_path / "queue_server-h-2.trace.jsonl", "queue_server", "h", 2, 200.0,
            [
                (tid, "queue_dwell", wall - 200.0 + 0.25, wall - 200.0 + 0.40),
                (tid, "relay", wall - 200.0 + 0.40, wall - 200.0 + 0.45),
            ],
        )
        _write_spool(
            tmp_path / "consumer-h-3.trace.jsonl", "consumer", "h", 3, 300.0,
            [(tid, "dequeue", wall - 300.0 + 0.50, wall - 300.0 + 0.60)],
        )
        return tid, wall

    def test_merge_applies_offsets_and_links(self, tmp_path):
        from psana_ray_tpu.obs.trace_merge import merge

        tid, wall = self._make_spools(tmp_path)
        doc = merge([str(tmp_path)])
        json.dumps(doc)  # valid JSON document
        evts = doc["traceEvents"]
        names = {e["name"] for e in evts if e["ph"] == "M"}
        assert names == {"process_name"} and len(
            [e for e in evts if e["ph"] == "M"]
        ) == 3  # one track per process
        spans = sorted(
            (e for e in evts if e["ph"] == "X"), key=lambda e: e["ts"]
        )
        assert [s["name"] for s in spans] == [
            "enqueue", "queue_dwell", "relay", "dequeue",
        ]
        # offsets applied: all spans land on the shared wall timeline
        assert spans[0]["ts"] == pytest.approx((wall + 0.10) * 1e6, abs=1.0)
        assert spans[-1]["ts"] == pytest.approx((wall + 0.50) * 1e6, abs=1.0)
        # non-overlapping, monotone stage boundaries across processes
        for a, b in zip(spans, spans[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-6
        # linked by trace id, with a flow chain across the three tracks
        assert all(s["args"]["trace_id"] == f"{tid:#x}" for s in spans)
        flows = [e for e in evts if e["ph"] in ("s", "t", "f")]
        assert [f["ph"] for f in sorted(flows, key=lambda e: e["ts"])] == [
            "s", "t", "t", "f",
        ]
        assert {f["pid"] for f in flows} == {1, 2, 3}

    def test_exemplar_follows_the_frame_into_its_batch(self, tmp_path):
        """A frame's spans name the batch it joined; the batch's phase
        spans carry that id: --exemplar draws both, and no other batch's."""
        from psana_ray_tpu.obs.trace_merge import merge

        path = str(tmp_path / "c.trace.jsonl")
        _write_spool(path, "consumer", "h", 1, 0.0, [])
        with open(path, "a") as f:
            for rec in (
                {"t": "s", "id": 7, "n": "queue_dwell", "a": 1.0, "b": 2.0, "j": 3},
                {"t": "s", "id": 8, "n": "queue_dwell", "a": 1.1, "b": 2.0, "j": 4},
                {"t": "s", "id": 3, "n": "stage.launch", "a": 2.0, "b": 2.1, "k": 16},
                {"t": "s", "id": 4, "n": "stage.launch", "a": 3.0, "b": 3.1, "k": 16},
            ):
                f.write(json.dumps(rec) + "\n")
        doc = merge([path], only_trace=7)
        names = [(e["name"], e["args"]["trace_id"]) for e in doc["traceEvents"] if e["ph"] == "X"]
        assert names == [("queue_dwell", "0x7"), ("stage.launch", "0x3")]

    def test_peer_anchor_skew_correction(self, tmp_path):
        from psana_ray_tpu.obs.trace_merge import merge

        wall = 1_000_000.0
        # consumer's WALL clock runs 5s ahead of the server's; its peer
        # exchange reveals it: local wall mid = offset + mid_mono, server
        # replied peer_wall = local_est - 5
        mono_off = 300.0
        mid_mono = wall - mono_off + 0.5
        _write_spool(
            tmp_path / "queue_server-h-2.trace.jsonl", "queue_server", "h", 2, 200.0,
            [(1, "relay", wall - 200.0 + 0.40, wall - 200.0 + 0.45)],
        )
        _write_spool(
            tmp_path / "consumer-h-3.trace.jsonl", "consumer", "h", 3, mono_off,
            [(1, "dequeue", wall - mono_off + 0.50, wall - mono_off + 0.60)],
            peers=[{
                "send_wall": wall + 0.49, "send_mono": mid_mono - 0.01,
                "recv_wall": wall + 0.51, "recv_mono": mid_mono + 0.01,
                "peer_wall": (mono_off + mid_mono) - 5.0, "peer_mono": 0.0,
            }],
        )
        doc = merge([str(tmp_path)])
        track = next(
            t for t in doc["otherData"]["tracks"] if "consumer" in t["process"]
        )
        assert track["skew_vs_server_s"] == pytest.approx(5.0, abs=1e-6)
        spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        # skew subtracted: ts = (mono + offset - skew) on the unified
        # (server-relative) timeline
        assert spans["dequeue"]["ts"] == pytest.approx(
            (wall + 0.50 - 5.0) * 1e6, abs=1.0
        )

    def test_cli_writes_valid_json(self, tmp_path):
        import subprocess

        self._make_spools(tmp_path)
        out = tmp_path / "merged.json"
        proc = subprocess.run(
            [sys.executable, "-m", "psana_ray_tpu.obs.trace_merge",
             str(tmp_path), "--out", str(out)],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(out.read_text())
        assert doc["traceEvents"] and "3 process track(s)" in proc.stdout

    def test_no_spools_is_an_error(self, tmp_path):
        import subprocess

        proc = subprocess.run(
            [sys.executable, "-m", "psana_ray_tpu.obs.trace_merge",
             str(tmp_path)],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 1 and "no trace spools" in proc.stderr

    def test_torn_tail_line_is_tolerated(self, tmp_path):
        from psana_ray_tpu.obs.trace_merge import load_spool

        p = tmp_path / "x-h-1.trace.jsonl"
        _write_spool(p, "x", "h", 1, 0.0, [(1, "s", 0.0, 1.0)])
        with open(p, "a") as f:
            f.write('{"t":"s","id":2,"n":"trunc')  # crashed mid-write
        spool = load_spool(str(p))
        assert len(spool["spans"]) == 1  # the torn line is skipped


class TestCliWiring:
    def test_shared_trace_flags(self):
        import argparse

        from psana_ray_tpu.obs.tracing import add_trace_args

        p = argparse.ArgumentParser()
        add_trace_args(p)
        a = p.parse_args(
            ["--trace_dir", "/tmp/t", "--trace_sample", "7", "--flight_dir", "/tmp/f"]
        )
        assert (a.trace_dir, a.trace_sample, a.flight_dir) == ("/tmp/t", 7, "/tmp/f")
        assert p.parse_args([]).trace_dir is None  # default off

    def test_configure_from_args_registers_sources(self, tmp_path):
        import argparse

        from psana_ray_tpu.obs.registry import MetricsRegistry
        from psana_ray_tpu.obs.tracing import add_trace_args, configure_from_args

        p = argparse.ArgumentParser()
        add_trace_args(p)
        a = p.parse_args(["--trace_dir", str(tmp_path), "--trace_sample", "3"])
        t = configure_from_args(a, "unit")
        try:
            assert t is TRACER and t.enabled and t.sample_every == 3
            names = MetricsRegistry.default().sources()
            assert "trace" in names and "flight" in names
        finally:
            from psana_ray_tpu.obs.flight import FLIGHT

            FLIGHT.uninstall()

    def test_consumer_heartbeat_appends_obs_suffix(self):
        # the heartbeat line includes sample rate / spans / flight count
        # (satellite: a live run shows tracing is actually on)
        import inspect

        import psana_ray_tpu.consumer as consumer_mod

        src = inspect.getsource(consumer_mod.main)
        assert "obs_status_suffix" in src and "--status_interval" in src

    def test_every_cli_takes_trace_flags(self):
        import inspect

        import psana_ray_tpu.consumer as c
        import psana_ray_tpu.producer as p
        import psana_ray_tpu.queue_server as q

        for mod, fn in ((c, c.main), (p, p.parse_arguments), (q, q.main)):
            assert "add_trace_args" in inspect.getsource(fn), mod.__name__
        # sfx too — source check only (importing psana_ray_tpu.sfx pulls jax)
        import pathlib

        sfx_src = (
            pathlib.Path(p.__file__).resolve().parent / "sfx.py"
        ).read_text()
        assert "add_trace_args" in sfx_src


class TestThreeProcessAcceptance:
    """The ISSUE 4 acceptance run: producer, queue server, and consumer
    as real processes with sampling on; the merged output must show at
    least one sampled frame with linked spans on all three tracks, with
    clock-aligned, non-overlapping stage boundaries."""

    def test_three_process_trace_merges_linked(self, tmp_path):
        import socket
        import subprocess

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        spool = tmp_path / "spool"

        def popen(mod, *args):
            return subprocess.Popen(
                [sys.executable, "-m", mod, *args],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )

        qs = popen(
            "psana_ray_tpu.queue_server", "--host", "127.0.0.1",
            "--port", str(port), "--queue_size", "32",
            "--trace_dir", str(spool), "--drain_s", "1",
        )
        cons = prod = None
        try:
            cons = popen(
                "psana_ray_tpu.consumer",
                "--address", f"tcp://127.0.0.1:{port}",
                "--queue_name", "shared_queue", "--max_frames", "32",
                "--quiet", "--trace_dir", str(spool), "--trace_sample", "4",
            )
            prod = popen(
                "psana_ray_tpu.producer", "--exp", "synthetic",
                "--detector_name", "smoke_a", "--num_events", "32",
                "--address", f"tcp://127.0.0.1:{port}",
                "--queue_name", "shared_queue",
                "--trace_dir", str(spool), "--trace_sample", "4",
            )
            pout, _ = prod.communicate(timeout=120)
            assert prod.returncode == 0, pout
            cout, _ = cons.communicate(timeout=120)
            assert cons.returncode == 0, cout
        finally:
            for p in (cons, prod):
                if p is not None and p.poll() is None:
                    p.kill()
            qs.terminate()
            qs.communicate(timeout=30)

        from psana_ray_tpu.obs.trace_merge import merge

        doc = merge([str(spool)])
        json.dumps(doc)  # valid
        tracks = doc["otherData"]["tracks"]
        assert len(tracks) == 3, tracks
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        by_trace: dict = {}
        for e in spans:
            by_trace.setdefault(e["args"]["trace_id"], []).append(e)
        linked = {
            tid: evs for tid, evs in by_trace.items()
            if len({e["pid"] for e in evs}) == 3
        }
        assert linked, f"no frame linked across all 3 tracks: {by_trace}"
        # clock-aligned, non-overlapping stage boundaries for a linked
        # frame — within the alignment error bound: cross-process span
        # placement is only as good as the anchor/skew estimate (~RTT),
        # so allow a few ms of slack instead of asserting exact ordering
        # (a 1 us bound here is tighter than the physics and flakes)
        SLACK_US = 5000.0
        evs = sorted(next(iter(linked.values())), key=lambda e: e["ts"])
        names = {e["name"] for e in evs}
        assert {"enqueue", "relay", "dequeue"} <= names, names
        for a, b in zip(evs, evs[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + SLACK_US, (a, b)
        # the producer's enqueue genuinely precedes the consumer's
        # dequeue END (read + processing) even under worst-case skew
        enq = min(e["ts"] for e in evs if e["name"] == "enqueue")
        deq_end = max(
            e["ts"] + e["dur"] for e in evs if e["name"] == "dequeue"
        )
        assert enq < deq_end + SLACK_US
