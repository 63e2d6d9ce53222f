"""Negotiated wire compression (ISSUE 9): codec round trips over every
wire dtype, expansion fallback, hostile-payload fail-fast with the
in-flight requeue contract intact, mixed-codec connections on one
server, old-peer degradation, lazy relay pass-through, and
zero-leaked-leases after decode errors.
"""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from faultproxy import ThrottleProxy
from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.records import (
    EndOfStream,
    FrameRecord,
    LazyFrameRecord,
    narrow_panels,
)
from psana_ray_tpu.transport import codec as codec_mod
from psana_ray_tpu.transport.codec import (
    CODEC_NONE,
    TAG_COMPRESSED,
    WIRE_COMPRESS_MIN,
    available_codecs,
    compress_encoded_parts,
    decode_payload,
    encode_payload,
    encode_payload_parts,
    get_codec,
    negotiate_codec,
    payload_nbytes,
)
from psana_ray_tpu.transport.registry import TransportClosed
from psana_ray_tpu.transport.ring import RingBuffer
from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
from psana_ray_tpu.utils.bufpool import BufferPool

RNG = np.random.default_rng(42)
SHUFFLE = get_codec("shuffle-rle")


def detector_u16(shape=(4, 128, 128)):
    """Pedestal + noise + sparse peaks — compressible detector content."""
    ped = 2000 + 200 * np.sin(np.linspace(0, 9, int(np.prod(shape)))).reshape(shape)
    f = (ped + RNG.normal(0, 3, shape)).clip(0, 65535).astype(np.uint16)
    hits = RNG.random(shape) < 1e-3
    f[hits] += RNG.integers(500, 3000, int(hits.sum())).astype(np.uint16)
    return f


def wire_roundtrip(rec, codec=SHUFFLE, pool=None):
    """Compress -> join to wire bytes -> decode; returns the decoded
    record (leases released)."""
    pool = pool or BufferPool()
    parts = encode_payload_parts(rec)
    wparts, lease = compress_encoded_parts(rec, parts, codec, pool)
    wire = b"".join(bytes(p) for p in wparts)
    if lease is not None:
        lease.release()
    return decode_payload(wire), wire, b"".join(bytes(p) for p in parts)


class TestCodecRoundTrip:
    @pytest.mark.parametrize(
        "dtype", [np.float32, np.float64, np.uint16, np.int32, np.uint8, np.int16]
    )
    def test_every_wire_dtype_roundtrips(self, dtype):
        # content with structure so most dtypes actually compress; the
        # round trip must hold either way (compressed or fallback)
        base = np.cumsum(RNG.normal(0, 2, (2, 48, 48))).reshape(2, 48, 48)
        panels = base.astype(dtype)
        rec = FrameRecord(3, 17, panels, 8.2, timestamp=1.5)
        out, wire, raw = wire_roundtrip(rec)
        assert out.equals(rec)
        assert out.panels.dtype == np.dtype(dtype)

    def test_noncontiguous_strided_panels(self):
        full = detector_u16((4, 128, 256))
        rec = FrameRecord(0, 5, full[:, ::2, ::4], 1.0)
        assert not rec.panels.flags.c_contiguous
        out, wire, raw = wire_roundtrip(rec)
        assert out.equals(rec)
        assert len(wire) < len(raw)  # strided content still compresses

    def test_detector_frames_compress_well(self):
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        out, wire, raw = wire_roundtrip(rec)
        assert out.equals(rec)
        assert len(raw) / len(wire) >= 2.0, "detector-like u16 must beat 2x"

    def test_pooled_decode_is_zero_copy_with_lease(self):
        pool = BufferPool()
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        _, wire, _ = wire_roundtrip(rec)
        lease = pool.lease(len(wire))
        lease.mv[:] = wire
        out = decode_payload(lease.mv, lease=lease)
        assert out.equals(rec)
        # the decompressed buffer lease rides the record; the compressed
        # staging lease goes straight back — a plain consumer never
        # relays, so caching the wire bytes would only double pool
        # residency per in-flight frame (the relay's lazy=True receive
        # is the path that keeps them)
        assert out.lease is not None and out.wire_cache is None
        assert pool.stats()["leases"] == 1
        out.release()
        assert pool.stats()["leases"] == 0

    def test_small_payloads_never_compress(self):
        rec = FrameRecord(0, 1, np.zeros((1, 4, 4), np.uint16), 1.0)
        assert rec.nbytes < WIRE_COMPRESS_MIN
        parts = encode_payload_parts(rec)
        wparts, lease = compress_encoded_parts(rec, parts, SHUFFLE, BufferPool())
        assert lease is None and wparts is parts

    def test_eos_and_pickle_never_compress(self):
        pool = BufferPool()
        for item in (EndOfStream(total_events=4), {"k": 1}):
            parts = encode_payload_parts(item)
            wparts, lease = compress_encoded_parts(item, parts, SHUFFLE, pool)
            assert lease is None and wparts is parts


class TestExpansionFallback:
    def test_uniform_noise_falls_back_to_raw(self):
        pool = BufferPool()
        rec = FrameRecord(0, 1, RNG.integers(0, 65536, (4, 64, 64), np.uint16), 1.0)
        parts = encode_payload_parts(rec)
        wparts, lease = compress_encoded_parts(rec, parts, SHUFFLE, pool)
        assert lease is None and wparts is parts  # identical raw framing
        assert b"".join(bytes(p) for p in wparts) == encode_payload(rec)
        assert pool.stats()["leases"] == 0  # staging lease went back

    def test_oversized_raw_frame_fails_fast_at_sender(self, monkeypatch):
        # the raw path's 256 MB send cap must survive compression: a
        # frame whose COMPRESSED size passes the transport wire check
        # but whose raw_len trips the receiver's guard would kill the
        # connection and ride the windowed resend forever (poison
        # record) — so the cap applies to the RAW size, before encode
        from psana_ray_tpu.transport import codec as codec_mod

        monkeypatch.setattr(codec_mod, "_MAX_RAW_PAYLOAD", 4096)
        pool = BufferPool()
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        parts = encode_payload_parts(rec)
        with pytest.raises(ValueError, match="exceeds wire maximum"):
            compress_encoded_parts(rec, parts, SHUFFLE, pool)
        assert pool.stats()["leases"] == 0

    def test_fallback_frames_relay_correctly(self):
        srv = TcpQueueServer(RingBuffer(4), host="127.0.0.1").serve_background()
        try:
            c = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            rec = FrameRecord(
                0, 1, RNG.integers(0, 65536, (4, 64, 64), np.uint16), 1.0
            )
            assert c.put(rec)
            out = c.get()
            assert out.equals(rec)
            c.disconnect()
        finally:
            srv.shutdown()


class TestNegotiation:
    def test_server_picks_first_known_codec(self):
        assert negotiate_codec(["nope", "shuffle-rle"]) is SHUFFLE
        assert negotiate_codec(["none", "shuffle-rle"]) is None
        assert negotiate_codec(["bogus", "alsobogus"]) is None

    def test_get_codec_rejects_unknown_names(self):
        with pytest.raises(ValueError, match="unknown wire codec"):
            get_codec("snappy-ultra")
        assert get_codec(CODEC_NONE) is None
        assert get_codec(None) is None
        assert "shuffle-rle" in available_codecs()

    def test_client_negotiates_and_survives_reconnect(self):
        srv = TcpQueueServer(RingBuffer(4), host="127.0.0.1").serve_background()
        try:
            c = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            assert c._codec is not None
            rec = FrameRecord(0, 1, detector_u16(), 9.5)
            assert c.put(rec)
            assert c.get().equals(rec)
            # sever the socket: the reconnect must renegotiate
            c._sock.close()
            assert c.put(rec)
            assert c._codec is not None
            assert c.get().equals(rec)
            c.disconnect()
        finally:
            srv.shutdown()

    def test_put_wait_compresses_once_under_backpressure(self, monkeypatch):
        """A backpressured put_wait retries the bounded-wait round trip
        but must pay the codec ONCE per frame: the compressed bytes
        depend only on (item, codec), so the encode is cached across
        full-queue retries (re-encoded only when a reconnect
        renegotiates the codec)."""
        from psana_ray_tpu.transport import tcp as tcp_mod
        from psana_ray_tpu.transport.codec import CODEC_STATS

        monkeypatch.setattr(tcp_mod, "_SERVER_WAIT_CAP_S", 0.15)
        srv = TcpQueueServer(RingBuffer(1), host="127.0.0.1").serve_background()
        try:
            c = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            blocker = FrameRecord(0, 0, detector_u16(), 9.5)
            assert c.put(blocker)  # queue (size 1) now full
            s0 = CODEC_STATS.stats()["frames_compressed_total"]
            rec = FrameRecord(0, 1, detector_u16(), 9.5)
            # >= 3 bounded-wait round trips before the deadline
            assert not c.put_wait(rec, timeout=0.6)
            assert CODEC_STATS.stats()["frames_compressed_total"] == s0 + 1
            # drain the blocker; the retried put then lands intact
            assert c.get().equals(blocker)
            assert c.put_wait(rec, timeout=5)
            assert c.get().equals(rec)
            c.disconnect()
        finally:
            srv.shutdown()

    def test_old_peer_degrades_to_none(self, monkeypatch):
        """A server that predates the 'Z' opcode answers protocol-error
        and drops the connection; the client must degrade to
        uncompressed (latched — no renegotiation storm) and keep
        working, not crash."""
        from psana_ray_tpu.transport import evloop

        monkeypatch.delitem(evloop._OPS, ord("Z"))
        srv = TcpQueueServer(RingBuffer(4), host="127.0.0.1").serve_background()
        try:
            c = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            assert c._codec is None and c._codec_refused
            rec = FrameRecord(0, 1, detector_u16((2, 32, 32)), 9.5)
            assert c.put(rec)  # reconnects (old server dropped us), raw
            out = c.get()
            assert out.equals(rec)
            assert out.wire_cache is None  # nothing was compressed
            c.disconnect()
        finally:
            srv.shutdown()

    def test_malformed_negotiation_reply_degrades_to_none(self, monkeypatch):
        """A buggy peer/proxy answering 'Z' with a codec name the client
        never advertised must degrade the client to uncompressed (same
        latch as the old-peer refusal), not surface a raw ValueError
        from the middle of connect/reconnect."""
        from psana_ray_tpu.transport import evloop

        class _Spoofed:
            name = "bogus-codec"

            def __getattr__(self, attr):
                return getattr(SHUFFLE, attr)

        monkeypatch.setattr(evloop, "negotiate_codec", lambda names: _Spoofed())
        srv = TcpQueueServer(RingBuffer(4), host="127.0.0.1").serve_background()
        try:
            c = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            assert c._codec is None and c._codec_refused
            rec = FrameRecord(0, 1, detector_u16((2, 32, 32)), 9.5)
            assert c.put(rec)  # raw put on the still-healthy connection
            out = c.get()
            assert out.equals(rec)
            c.disconnect()
        finally:
            srv.shutdown()

    def test_mixed_codec_connections_on_one_server(self):
        pool = BufferPool()
        srv = TcpQueueServer(
            RingBuffer(16), host="127.0.0.1", pool=pool
        ).serve_background()
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port, pool=pool, codec="shuffle-rle")
            cons_c = TcpQueueClient(
                "127.0.0.1", srv.port, pool=pool, codec="shuffle-rle"
            )
            cons_raw = TcpQueueClient("127.0.0.1", srv.port, pool=pool)
            recs = [FrameRecord(0, i, detector_u16() + i, 9.5) for i in range(4)]
            for r in recs:
                assert prod.put(r)
            assert cons_c.get().equals(recs[0])
            assert cons_raw.get().equals(recs[1])
            assert cons_c.get().equals(recs[2])
            assert cons_raw.get().equals(recs[3])
            for c in (prod, cons_c, cons_raw):
                c.disconnect()
        finally:
            srv.shutdown()


class TestHostilePayloads:
    def _wire(self, rec=None):
        rec = rec or FrameRecord(0, 1, detector_u16(), 9.5)
        _, wire, _ = wire_roundtrip(rec)
        return wire

    def test_truncated_payload_is_connection_error(self):
        wire = self._wire()
        for cut in (3, 9, len(wire) // 2, len(wire) - 1):
            with pytest.raises(ConnectionError, match="compressed"):
                decode_payload(wire[:cut])

    def test_bitflips_in_framing_are_connection_errors(self):
        wire = bytearray(self._wire())
        wire[1] = 0xEE  # unknown codec id
        with pytest.raises(ConnectionError, match="unknown wire codec"):
            decode_payload(bytes(wire))
        wire = bytearray(self._wire())
        struct.pack_into("<I", wire, 2, 1 << 30)  # absurd raw_len
        with pytest.raises(ConnectionError, match="compressed"):
            decode_payload(bytes(wire))

    def test_nested_compressed_framing_is_connection_error(self):
        """No encoder nests 'C' in 'C': a payload that decompresses to
        ANOTHER compressed payload is a crafted recursion/amplification
        bomb and must die as a ConnectionError at the first level, not
        recurse through decode_payload."""
        wire = self._wire(FrameRecord(0, 1, detector_u16((1, 64, 64)), 9.5))
        assert wire[0] == TAG_COMPRESSED[0]  # fixture really compressed
        assert len(wire) < 0xFFFF  # head_len is u16 in the prefix
        # outer frame: the inner compressed payload rides as the verbatim
        # head, plus a genuinely-compressed padding body so the outer
        # level exercises a REAL decompress before the nested check
        pad = bytes(4096)
        scratch = bytearray(8192)
        clen = SHUFFLE.compress(memoryview(pad), 1, memoryview(scratch))
        assert clen
        outer = (
            TAG_COMPRESSED
            + struct.pack("<BIH", wire[1], len(wire) + len(pad), len(wire))
            + wire
            + bytes(scratch[:clen])
        )
        with pytest.raises(ConnectionError, match="nested"):
            decode_payload(outer)

    def test_trailing_garbage_is_a_connection_error(self):
        wire = self._wire()
        with pytest.raises(ConnectionError, match="compressed"):
            decode_payload(wire + b"\x00" * 7)

    def test_zero_leaked_leases_after_decode_error(self):
        pool = BufferPool()
        wire = self._wire()
        bad = wire[: len(wire) - 9]
        lease = pool.lease(len(bad))
        lease.mv[:] = bad
        with pytest.raises(ConnectionError):
            decode_payload(lease.mv, lease=lease)
        assert pool.stats()["leases"] == 0, pool.stats()

    def test_hostile_rle_counts_fail_before_allocation(self, monkeypatch):
        """An RLE plane whose counts sum to far more than the plane size
        must raise BEFORE np.repeat materializes the expansion — a
        hostile peer could otherwise claim terabytes inside a payload
        that passes every length cap."""
        n_runs = 1000
        buf = bytearray(struct.pack("<I", n_runs))
        buf += b"\xaa" * n_runs  # run values
        buf += struct.pack("<H", 65535) * n_runs  # counts: sum ~65.5M

        def boom(*a, **k):
            raise AssertionError("np.repeat ran before the size check")

        monkeypatch.setattr(codec_mod.np, "repeat", boom)
        with pytest.raises(ValueError, match="expands to"):
            codec_mod._decode_plane(
                memoryview(bytes(buf)), 0, codec_mod._PLANE_RLE, len(buf), 4096
            )

    def test_validate_mirrors_decompress(self):
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        pool = BufferPool()
        parts = encode_payload_parts(rec)
        wparts, lease = compress_encoded_parts(rec, parts, SHUFFLE, pool)
        body = bytes(wparts[1])
        SHUFFLE.validate(memoryview(body), rec.nbytes)  # valid: no raise
        for cut in (1, 6, len(body) // 3, len(body) - 1):
            with pytest.raises(ValueError):
                SHUFFLE.validate(memoryview(body[:cut]), rec.nbytes)
        lease.release()

    def test_server_kills_conn_on_corrupt_put_and_requeue_survives(self):
        """A hostile compressed PUT dies as a CONNECTION error at
        receive (the server kills that connection — it never queues a
        poison frame), while the queue keeps serving and the standard
        in-flight requeue contract still runs for deliveries that die
        unacked — corruption never becomes silent loss NOR silent
        acceptance."""
        srv = TcpQueueServer(RingBuffer(8), host="127.0.0.1").serve_background()
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            rec = FrameRecord(0, 7, detector_u16((2, 64, 64)), 9.5)
            assert prod.put(rec)
            # raw protocol driving: a corrupt compressed PUT must kill
            # the connection (EOF, no status answer) — ConnectionError
            # semantics server-side, not a queued poison frame
            s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
            garbage = TAG_COMPRESSED + struct.pack("<BIH", 1, 4096, 2) + b"xx"
            s.sendall(b"P" + struct.pack("<I", len(garbage)) + garbage)
            s.settimeout(5.0)
            died = False
            try:
                died = s.recv(4096) == b""
            except OSError:
                died = True
            s.close()
            assert died, "server answered a corrupt compressed PUT"
            # the queue still serves; a delivery that dies UNACKED after
            # the corruption event still redelivers (requeue intact)
            s2 = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
            s2.sendall(b"G")
            assert s2.recv(1) == b"1"
            (n,) = struct.unpack("<I", s2.recv(4))
            got = 0
            while got < n:
                got += len(s2.recv(1 << 16))
            s2.close()  # no BYE, no next opcode: delivery stays unacked
            cons = TcpQueueClient("127.0.0.1", srv.port)
            out = cons.get_wait(timeout=10.0)
            assert isinstance(out, FrameRecord) and out.equals(rec)
            assert cons.size() == 0  # exactly one frame, no poison extras
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()


class TestLazyRelay:
    def test_lazy_frame_semantics(self):
        pool = BufferPool()
        rec = FrameRecord(2, 9, detector_u16(), 7.5, timestamp=2.5)
        _, wire, _ = wire_roundtrip(rec)
        lease = pool.lease(len(wire))
        lease.mv[:] = wire
        out = decode_payload(lease.mv, lease=lease, lazy=True)
        assert isinstance(out, LazyFrameRecord)
        # header fields real, no decompression yet (only the cache +
        # nothing else checked out beyond the wire lease)
        assert (out.shard_rank, out.event_idx) == (2, 9)
        assert out.nbytes == rec.nbytes
        assert out.lease is None and out.wire_cache is not None
        assert pool.stats()["leases"] == 1
        # first panels touch inflates into a lease
        assert np.array_equal(out.panels, rec.panels)
        assert out.lease is not None
        assert pool.stats()["leases"] == 2
        out.release()
        assert pool.stats()["leases"] == 0

    def test_lazy_materialize_detaches(self):
        pool = BufferPool()
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        _, wire, _ = wire_roundtrip(rec)
        lease = pool.lease(len(wire))
        lease.mv[:] = wire
        out = decode_payload(lease.mv, lease=lease, lazy=True)
        owned = out.materialize()
        assert type(owned) is FrameRecord
        assert owned.lease is None and owned.wire_cache is None
        assert owned.equals(rec)
        assert pool.stats()["leases"] == 0

    def test_lazy_corrupt_payload_still_fails_at_receive(self):
        pool = BufferPool()
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        _, wire, _ = wire_roundtrip(rec)
        bad = wire[: len(wire) - 5]
        lease = pool.lease(len(bad))
        lease.mv[:] = bad
        with pytest.raises(ConnectionError):
            decode_payload(lease.mv, lease=lease, lazy=True)
        assert pool.stats()["leases"] == 0

    def test_corrupt_raw_head_is_connection_error_on_eager_path(self):
        # a stream that DECOMPRESSES cleanly but whose raw head is
        # garbage (flipped frame-magic byte rides the prefix raw) is
        # corruption all the same: the eager consumer path must kill
        # the connection like every other corruption — not leak a
        # ValueError out of get() — and hand both leases back without
        # the GC __del__ backstop
        pool = BufferPool()
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        _, wire, _ = wire_roundtrip(rec)
        bad = bytearray(wire)
        bad[10] ^= 0xFF  # inside the raw head's frame magic
        lease = pool.lease(len(bad))
        lease.mv[:] = bytes(bad)
        with pytest.raises(ConnectionError):
            decode_payload(lease.mv, lease=lease)
        assert pool.stats()["leases"] == 0

    def test_passthrough_resends_identical_bytes_without_inflating(self):
        """The relay's send path (cached_wire_parts, consulted BEFORE
        any raw-part building) must re-send the exact received bytes
        and must NOT touch panels — the zero-codec-CPU relay claim,
        pinned."""
        from psana_ray_tpu.transport.codec import CODEC_STATS, cached_wire_parts

        pool = BufferPool()
        rec = FrameRecord(0, 1, detector_u16(), 9.5)
        _, wire, _ = wire_roundtrip(rec)
        lease = pool.lease(len(wire))
        lease.mv[:] = wire
        out = decode_payload(lease.mv, lease=lease, lazy=True)
        d0 = CODEC_STATS.stats()["frames_decompressed_total"]
        wparts = cached_wire_parts(out, SHUFFLE)
        assert wparts is not None and len(wparts) == 1
        assert bytes(wparts[0]) == wire
        assert "_panels" not in out.__dict__, "pass-through inflated panels"
        assert CODEC_STATS.stats()["frames_decompressed_total"] == d0
        # a DIFFERENT codec id misses the cache (re-encode path)
        class _Other:
            codec_id = 99

        assert cached_wire_parts(out, _Other()) is None
        # the compress_encoded_parts fallback arm still passes through
        parts2 = encode_payload_parts(out)  # this one inflates (mixed path)
        wparts2, staging = compress_encoded_parts(out, parts2, SHUFFLE, pool)
        assert staging is None and bytes(wparts2[0]) == wire
        out.release()

    def test_lazy_frame_relays_to_raw_consumer(self):
        """Mixed path: a compressed PUT relayed to an uncompressed
        consumer forces the server to inflate — bytes must be right."""
        srv = TcpQueueServer(RingBuffer(4), host="127.0.0.1").serve_background()
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            cons = TcpQueueClient("127.0.0.1", srv.port)
            rec = FrameRecord(0, 3, detector_u16(), 9.5)
            assert prod.put(rec)
            assert cons.get().equals(rec)
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()


class TestWireSavings:
    def test_relay_wire_bytes_shrink_deterministically(self):
        """The deterministic acceptance proxy (no wall clocks — this
        box's CPU share flutters): the SAME stream through the SAME
        byte-counting proxy must put >= 2x fewer bytes on the wire
        compressed than raw."""
        frames = [FrameRecord(0, i, detector_u16(), 9.5) for i in range(4)]

        def run(codec):
            srv = TcpQueueServer(RingBuffer(8), host="127.0.0.1").serve_background()
            # generous rate: counting bytes, not modelling bandwidth
            proxy = ThrottleProxy("127.0.0.1", srv.port, 1e9)
            try:
                prod = TcpQueueClient("127.0.0.1", proxy.port, codec=codec)
                cons = TcpQueueClient("127.0.0.1", proxy.port, codec=codec)
                for r in frames:
                    assert prod.put(r)
                for r in frames:
                    assert cons.get().equals(r)
                prod.disconnect()
                cons.disconnect()
                return proxy.bytes_forwarded("up") + proxy.bytes_forwarded("down")
            finally:
                proxy.close()
                srv.shutdown()

        raw_bytes = run(None)
        comp_bytes = run("shuffle-rle")
        assert comp_bytes * 2 <= raw_bytes, (comp_bytes, raw_bytes)

    def test_throttle_proxy_actually_throttles(self):
        """The bandwidth proxy must cap throughput near its rate — the
        delay-line proxy models latency and could not run this A/B."""
        srv = TcpQueueServer(RingBuffer(8), host="127.0.0.1").serve_background()
        rate = 2e6
        proxy = ThrottleProxy("127.0.0.1", srv.port, rate, burst_s=0.05)
        try:
            c = TcpQueueClient("127.0.0.1", proxy.port)
            payload = np.zeros((1, 512, 512), np.uint16)  # 512 KB
            t0 = time.monotonic()
            for i in range(8):  # ~4.2 MB up
                assert c.put_wait(FrameRecord(0, i, payload, 1.0), timeout=30)
            dt = time.monotonic() - t0
            sent = proxy.bytes_forwarded("up")
            # must take at least (bytes - burst) / rate
            floor = (sent - rate * 0.05) / rate * 0.7  # 30% slack
            assert dt >= floor, (dt, floor, sent)
            c.disconnect()
        finally:
            proxy.close()
            srv.shutdown()


class TestDtypeNarrowing:
    def test_narrow_panels_rounds_and_clips(self):
        f = np.array([[-5.4, 0.5, 70000.2, 123.6]], np.float32).reshape(1, 1, 4)
        out = narrow_panels(f, "uint16")
        assert out.dtype == np.uint16
        assert out.ravel().tolist() == [0, 0, 65535, 124]

    def test_narrow_panels_nan_maps_to_zero(self):
        # calibrated frames mark bad pixels NaN; NaN→int casts are
        # platform-undefined in numpy, so the narrowing must map them
        # deterministically (0, the masked-pixel convention) and ±inf
        # to the dtype bounds — with no RuntimeWarning on the hot path
        f = np.array([[np.nan, np.inf, -np.inf, 7.2]], np.float32).reshape(1, 1, 4)
        with np.errstate(invalid="raise"):
            out = narrow_panels(f, "uint16")
        assert out.ravel().tolist() == [0, 65535, 0, 7]

    def test_narrow_panels_float_target(self):
        f = np.linspace(0, 1, 8, dtype=np.float64).reshape(1, 2, 4)
        out = narrow_panels(f, "float32")
        assert out.dtype == np.float32

    def test_narrow_panels_noop_and_unknown(self):
        f = np.zeros((1, 2, 2), np.uint16)
        assert narrow_panels(f, "uint16") is f
        with pytest.raises(ValueError, match="not wire-codable"):
            narrow_panels(f, "complex64")

    def test_producer_cli_wires_the_flags(self):
        from psana_ray_tpu.producer import parse_arguments

        cfg, _ = parse_arguments(["--wire_codec", "auto", "--wire_dtype", "uint16"])
        assert cfg.transport.wire_codec == "auto"
        assert cfg.transport.wire_dtype == "uint16"
        with pytest.raises(ValueError, match="unknown wire codec"):
            parse_arguments(["--wire_codec", "zstd-hyper"])


class TestStreamedCompressed:
    def test_streamed_drain_compressed_end_to_end(self):
        pool = BufferPool()
        srv = TcpQueueServer(
            RingBuffer(16), host="127.0.0.1", pool=pool
        ).serve_background()
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port, pool=pool, codec="shuffle-rle")
            cons = TcpQueueClient(
                "127.0.0.1", srv.port, pool=pool, codec="shuffle-rle"
            )
            cons.stream_open(window=8)
            recs = [FrameRecord(0, i, detector_u16() + i, 9.5) for i in range(6)]

            def produce():
                for r in recs:
                    assert prod.put_pipelined(r, deadline=time.monotonic() + 30)
                assert prod.flush_puts(deadline=time.monotonic() + 30)

            t = threading.Thread(target=produce, daemon=True)
            t.start()
            got = []
            deadline = time.monotonic() + 30
            while len(got) < len(recs) and time.monotonic() < deadline:
                got += cons.get_batch_stream(8, timeout=1.0)
            t.join(timeout=10)
            assert len(got) == len(recs)
            for r, o in zip(recs, got):
                assert o.equals(r)
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()
        s = codec_mod.CODEC_STATS.stats()
        assert s["frames_compressed_total"] > 0

    def test_compressed_conn_death_redelivers(self):
        """At-least-once through the codec: kill a compressed streamed
        consumer mid-window; the unacked tail redelivers to a sibling
        byte-correct."""
        srv = TcpQueueServer(RingBuffer(16), host="127.0.0.1").serve_background()
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            cons = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            reader = cons.stream_open(window=4)
            recs = [FrameRecord(0, i, detector_u16((2, 64, 64)) + i, 9.5) for i in range(4)]
            for r in recs:
                assert prod.put(r)
            first = reader.get_batch_stream(1, timeout=10.0)
            assert first and first[0].equals(recs[0])
            # die without acking: everything pushed-but-unacked requeues
            cons._sock.close()
            sib = TcpQueueClient("127.0.0.1", srv.port, codec="shuffle-rle")
            seen = []
            deadline = time.monotonic() + 20
            while len(seen) < 4 and time.monotonic() < deadline:
                item = sib.get_wait(timeout=1.0)
                if isinstance(item, FrameRecord):
                    seen.append(item.event_idx)
            # all four frames (incl. the unacked first) land somewhere
            assert sorted(set(seen)) == [0, 1, 2, 3], seen
            prod.disconnect()
            sib.disconnect()
        finally:
            srv.shutdown()


def _rec(i, shape=(2, 16, 16)):
    return FrameRecord(0, i, np.full(shape, i % 251, np.uint16), 9.5)


def _flight_since(n0, kind):
    """Events of ``kind`` recorded after lifetime-count ``n0`` (marks
    are ``FLIGHT.count_of(kind)``) — robust to ring eviction, unlike
    slicing ``events()`` by the lifetime event_count."""
    evs = [e for e in FLIGHT.events() if e["kind"] == kind]
    new = FLIGHT.count_of(kind) - n0
    return evs[-new:] if new > 0 else []


class TestAutoCodecDecision:
    """``--wire_codec auto``: one-shot decision at
    connect from the link-rate probe, re-evaluated on reconnect,
    breadcrumbed — forced both ways via the env threshold override (no
    link shaping needed)."""

    def test_fast_link_decides_off(self, monkeypatch):
        monkeypatch.setenv("PSANA_AUTO_CODEC_MB_S", "0.000001")
        srv = TcpQueueServer(RingBuffer(8), host="127.0.0.1").serve_background()
        mark = FLIGHT.count_of("codec_auto_decision")
        c = TcpQueueClient("127.0.0.1", srv.port, codec="auto")
        try:
            assert c.codec_name is None
            evs = _flight_since(mark, "codec_auto_decision")
            assert evs and evs[-1]["codec_on"] is False
            assert evs[-1]["link_mb_s"] is not None
            rec = _rec(2)
            assert c.put(rec)
            out = c.get()
            assert out.equals(rec)
            out.release()
        finally:
            c.disconnect()
            srv.shutdown()

    def test_slow_link_decides_on_and_reconnect_redecides(self, monkeypatch):
        monkeypatch.setenv("PSANA_AUTO_CODEC_MB_S", "1e9")
        srv = TcpQueueServer(RingBuffer(8), host="127.0.0.1").serve_background()
        mark = FLIGHT.count_of("codec_auto_decision")
        c = TcpQueueClient("127.0.0.1", srv.port, codec="auto")
        try:
            assert c.codec_name == "shuffle-rle"
            evs = _flight_since(mark, "codec_auto_decision")
            assert evs and evs[-1]["codec_on"] is True
            # the link "changes" (threshold flips): a reconnect must
            # RE-DECIDE, landing uncompressed this time
            monkeypatch.setenv("PSANA_AUTO_CODEC_MB_S", "0.000001")
            mark = FLIGHT.count_of("codec_auto_decision")
            c._sock.close()  # sever: next op reconnects
            rec = _rec(3)
            assert c.put(rec)
            evs = _flight_since(mark, "codec_auto_decision")
            assert evs and evs[-1]["codec_on"] is False
            assert c.codec_name is None
            out = c.get()
            assert out.equals(rec)
            out.release()
        finally:
            c.disconnect()
            srv.shutdown()

    def test_producer_cli_accepts_auto(self):
        """The CLI value works standalone: --wire_codec auto parses and
        rides the config."""
        from psana_ray_tpu.producer import parse_arguments

        cfg, a = parse_arguments(["--wire_codec", "auto"])
        assert cfg.transport.wire_codec == "auto"
