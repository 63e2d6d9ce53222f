"""Multi-process data plane + kernel pass-through tests (ISSUE 17).

Covers the pieces separately, then the assembled fleet:

- :func:`queue_owner` — deterministic, respawn-stable rendezvous pinning
  and the per-worker balance proxy (message counts shard by queue name,
  so ownership spread IS the load spread for balanced queues);
- :class:`WorkerContext` — SCM_RIGHTS connection migration: the fd plus
  its JSON context arrive intact, bytes already in the kernel socket
  buffer travel with the fd, and malformed datagrams are dropped
  without leaking fds;
- :class:`WorkerSupervisor` — fork/reap/respawn with a STABLE worker id
  and a bounded stop;
- splice primitives — :class:`FileSpan` advance/materialize and the
  capability probes backing the sendfile pass-through;
- the spliced relay itself — lazy-spill durable queue served over TCP,
  plain connections splice (counters move), compressed connections
  downgrade to materialize, both roundtrip intact;
- the full ``--workers 2`` fleet over one real port: cross-worker
  routing, kill -9 of EVERY worker in turn with zero loss, and the CLI
  refusing the incompatible combinations loudly.
"""

import errno
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from psana_ray_tpu.records import FrameRecord, is_eos
from psana_ray_tpu.storage import DurableRingBuffer, SegmentLog
from psana_ray_tpu.transport import workers as workers_mod
from psana_ray_tpu.transport.splice import (
    SPLICE,
    FileSpan,
    fallback_errno,
    probe_report,
    sendfile_capable,
)
from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
from psana_ray_tpu.transport.workers import (
    DEFAULT_QUEUE_WORKER,
    WorkerContext,
    WorkerSupervisor,
    queue_owner,
    resolve_port,
)
from psana_ray_tpu.utils.bufpool import WIRE

HAVE_REUSEPORT = hasattr(socket, "SO_REUSEPORT")
HAVE_FORK = hasattr(os, "fork")


def _rec(i, shape=(1, 16, 16)):
    return FrameRecord(0, i, np.full(shape, i % 4096, np.uint16), 9.5)


def _drain(client, want, timeout=2.0, deadline_s=30.0):
    out = []
    deadline = time.monotonic() + deadline_s
    while len(out) < want and time.monotonic() < deadline:
        batch = client.get_batch(64, timeout=timeout)
        if not batch:
            continue
        out.extend(r for r in batch if not is_eos(r))
        if any(is_eos(r) for r in batch):
            break
    return out


# ---------------------------------------------------------------------------
# rendezvous pinning
# ---------------------------------------------------------------------------


class TestQueueOwner:
    def test_single_worker_owns_everything(self):
        assert all(queue_owner("ns", f"q{i}", 1) == 0 for i in range(16))

    def test_default_queue_pin_is_worker_zero(self):
        # the implicit default queue bypasses queue_owner entirely —
        # the evloop routes it by this constant
        assert DEFAULT_QUEUE_WORKER == 0

    def test_pinning_is_deterministic_and_exact(self):
        # pinned literal map: these EXACT values are what makes respawn
        # stability real — a drift here silently re-homes live queues
        assert {f"q{i}": queue_owner("ns", f"q{i}", 2) for i in range(8)} == {
            "q0": 0, "q1": 0, "q2": 0, "q3": 1,
            "q4": 0, "q5": 1, "q6": 0, "q7": 0,
        }

    def test_pinning_survives_process_boundary(self):
        # blake2b rendezvous, not hash(): a fresh interpreter (its own
        # PYTHONHASHSEED) must compute the identical map, or two workers
        # would each believe they own the same queue
        here = {f"q{i}": queue_owner("ns", f"q{i}", 3) for i in range(16)}
        code = (
            "from psana_ray_tpu.transport.workers import queue_owner;"
            "print({f'q{i}': queue_owner('ns', f'q{i}', 3) for i in range(16)})"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert eval(out.stdout.strip()) == here

    def test_balance_proxy(self):
        # messages shard by queue name, so ownership spread over many
        # names is the per-worker message-count proxy: no worker may be
        # starved (each holds >= a quarter of its fair share)
        for n in (2, 3, 4):
            counts = [0] * n
            for i in range(64):
                counts[queue_owner("bench", f"stream-{i}", n)] += 1
            assert sum(counts) == 64
            assert min(counts) >= (64 // n) // 4, (n, counts)

    def test_owner_in_range(self):
        for n in (1, 2, 5, 8):
            for i in range(32):
                assert 0 <= queue_owner("x", f"n{i}", n) < n


# ---------------------------------------------------------------------------
# SCM_RIGHTS migration plumbing
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    not hasattr(socket, "recv_fds"), reason="needs socket.recv_fds"
)
class TestWorkerContext:
    def _two(self, tmp_path):
        c0 = WorkerContext(0, 2, str(tmp_path))
        c1 = WorkerContext(1, 2, str(tmp_path))
        return c0, c1

    def test_fd_migration_carries_context_and_buffered_bytes(self, tmp_path):
        c0, c1 = self._two(tmp_path)
        try:
            a, b = socket.socketpair()
            try:
                # bytes the client pipelined BEFORE migration sit in a's
                # kernel buffer — they must survive the fd's journey
                b.sendall(b"pipelined")
                ctx = {"kind": "op", "op": 7, "codec": "shuffle-rle"}
                c0.send_conn(1, a, ctx)
            finally:
                a.close()  # sender's copy; the datagram holds its own ref
            adopted = c1.recv_conns()
            assert len(adopted) == 1
            sock, got_ctx = adopted[0]
            try:
                assert got_ctx == ctx
                sock.settimeout(5.0)
                assert sock.recv(16) == b"pipelined"
                sock.sendall(b"reply")
                b.settimeout(5.0)
                assert b.recv(16) == b"reply"
            finally:
                sock.close()
                b.close()
        finally:
            c0.close()
            c1.close()
            workers_mod._CURRENT_WORKER_ID = None

    def test_bad_datagram_drops_without_adoption(self, tmp_path):
        c0, c1 = self._two(tmp_path)
        try:
            a, b = socket.socketpair()
            try:
                # garbage header: length field claims more than the blob
                import array

                c0._send_sock.sendmsg(
                    [b"\xff\xff\xff\xff"],
                    [(
                        socket.SOL_SOCKET,
                        socket.SCM_RIGHTS,
                        array.array("i", [a.fileno()]),
                    )],
                    0,
                    os.path.join(str(tmp_path), "worker-1.sock"),
                )
            finally:
                a.close()
            assert c1.recv_conns() == []
        finally:
            b.close()
            c0.close()
            c1.close()
            workers_mod._CURRENT_WORKER_ID = None

    def test_recv_on_empty_socket_returns_immediately(self, tmp_path):
        c0 = WorkerContext(0, 1, str(tmp_path))
        try:
            # immediately = without waiting: the adoption socket never
            # blocks (a state, where a stopwatch would read the machine)
            assert c0.sock.getblocking() is False
            assert c0.recv_conns() == []
        finally:
            c0.close()
            workers_mod._CURRENT_WORKER_ID = None

    def test_owner_of_matches_module_fn(self, tmp_path):
        c0 = WorkerContext(0, 4, str(tmp_path))
        try:
            for i in range(8):
                assert c0.owner_of("ns", f"q{i}") == queue_owner("ns", f"q{i}", 4)
        finally:
            c0.close()
            workers_mod._CURRENT_WORKER_ID = None


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------


@pytest.mark.skipif(not HAVE_FORK, reason="needs os.fork")
class TestWorkerSupervisor:
    @staticmethod
    def _sleeper(worker_id):
        while True:
            time.sleep(3600)

    def test_respawn_keeps_worker_id(self):
        sup = WorkerSupervisor(2, self._sleeper).start()
        try:
            pids = sup.pids()
            assert set(pids) == {0, 1}
            victim = pids[1]
            os.kill(victim, signal.SIGKILL)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                cur = sup.pids()
                if set(cur) == {0, 1} and cur[1] != victim:
                    break
                time.sleep(0.05)
            cur = sup.pids()
            assert set(cur) == {0, 1}, cur
            assert cur[1] != victim
            assert cur[0] == pids[0]  # the survivor was not disturbed
            assert sup.snapshot()["respawns_total"] >= 1
        finally:
            sup.stop(timeout_s=10.0)
        assert sup.pids() == {}

    def test_stop_reaps_the_fleet(self):
        sup = WorkerSupervisor(2, self._sleeper).start()
        pids = list(sup.pids().values())
        sup.stop(timeout_s=10.0)
        assert sup.pids() == {}
        for pid in pids:
            # reaped: the pid no longer names our child (signal 0 probe)
            with pytest.raises(ProcessLookupError):
                os.kill(pid, 0)

    def test_rejects_zero_workers(self):
        with pytest.raises(ValueError):
            WorkerSupervisor(0, self._sleeper)


# ---------------------------------------------------------------------------
# kernel pass-through primitives
# ---------------------------------------------------------------------------


class TestSplicePrimitives:
    def test_filespan_advance_and_materialize(self, tmp_path):
        p = tmp_path / "seg"
        p.write_bytes(b"xxx" + b"payload-bytes" + b"yyy")
        with open(p, "rb") as f:
            span = FileSpan(f, 3, 13)
            assert span.materialize() == b"payload-bytes"
            span.advance(8)
            assert (span.pos, span.nbytes) == (11, 5)
            assert span.materialize() == b"bytes"
            # materialize is pread: the file's own position is untouched
            assert f.tell() == 0

    def test_fallback_errno_classification(self):
        assert fallback_errno(OSError(errno.EINVAL, "x"))
        assert fallback_errno(OSError(errno.ENOTSOCK, "x"))
        assert not fallback_errno(OSError(errno.EPIPE, "x"))
        assert not fallback_errno(OSError(errno.ECONNRESET, "x"))

    def test_probe_report_shape(self):
        rep = probe_report()
        assert set(rep) == {"sendfile", "msg_zerocopy"}
        assert all(isinstance(v, bool) for v in rep.values())
        # probe is memoized: second call agrees
        assert sendfile_capable() == rep["sendfile"]

    def test_resolve_port_is_bindable(self):
        if not HAVE_REUSEPORT:
            pytest.skip("needs SO_REUSEPORT")
        port = resolve_port("127.0.0.1", 0)
        assert 0 < port < 65536
        assert resolve_port("127.0.0.1", port) == port
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            s.bind(("127.0.0.1", port))
        finally:
            s.close()


# ---------------------------------------------------------------------------
# the spliced relay (single process)
# ---------------------------------------------------------------------------


def _lazy_spill_server(root, maxsize=500, ram_items=1):
    """Durable server whose queues spill almost immediately and deliver
    spilled records as un-read handles — every relayed frame past the
    tiny RAM window rides the sendfile path on plain connections."""

    def factory(ns, name, maxsize_):
        log = SegmentLog(
            os.path.join(str(root), f"{ns}__{name}"),
            name=name, segment_bytes=1 << 20, fsync="none",
        )
        return DurableRingBuffer(
            log, maxsize=maxsize_, name=name,
            ram_items=ram_items, lazy_spill=True,
        )

    return TcpQueueServer(
        factory("default", "default", maxsize),
        host="127.0.0.1", maxsize=maxsize, queue_factory=factory,
        group_store_path=os.path.join(str(root), "groups.json"),
    ).serve_background()


class TestSplicedRelay:
    def test_plain_connection_splices_and_roundtrips(self, tmp_path):
        srv = _lazy_spill_server(tmp_path)
        try:
            before = SPLICE.snapshot()
            prod = TcpQueueClient(
                "127.0.0.1", srv.port, namespace="ns", queue_name="sp",
                reconnect_tries=1,
            )
            for i in range(24):
                assert prod.put(_rec(i))
            cons = TcpQueueClient(
                "127.0.0.1", srv.port, namespace="ns", queue_name="sp",
                reconnect_tries=1,
            )
            got = _drain(cons, 24)
            assert [r.event_idx for r in got] == list(range(24))
            assert all(
                np.array_equal(r.panels, _rec(r.event_idx).panels) for r in got
            )
            after = SPLICE.snapshot()
            if sendfile_capable():
                # everything past the 1-item RAM window spilled, and a
                # plain connection moves spilled payloads by sendfile
                assert (
                    after["spliced_frames_total"]
                    > before["spliced_frames_total"]
                )
                assert after["spliced_bytes_total"] > before["spliced_bytes_total"]
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()

    def test_compressed_connection_materializes(self, tmp_path):
        srv = _lazy_spill_server(tmp_path)
        try:
            prod = TcpQueueClient(
                "127.0.0.1", srv.port, namespace="ns", queue_name="cz",
                reconnect_tries=1,
            )
            for i in range(12):
                assert prod.put(_rec(i))
            # a negotiated codec must re-encode the payload, so the
            # spilled bytes get read back into the interpreter — the
            # downgrade is invisible to the client
            cons = TcpQueueClient(
                "127.0.0.1", srv.port, namespace="ns", queue_name="cz",
                reconnect_tries=1, codec="shuffle-rle",
            )
            got = _drain(cons, 12)
            assert [r.event_idx for r in got] == list(range(12))
            assert all(
                np.array_equal(r.panels, _rec(r.event_idx).panels) for r in got
            )
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()


    @pytest.mark.skipif(not sendfile_capable(), reason="no os.sendfile here")
    @pytest.mark.parametrize("codec", [None, "shuffle-rle"], ids=["spliced", "materialized"])
    def test_payload_bytes_through_python_on_the_drain(self, tmp_path, codec):
        """The drain of a spilled backlog, counted by the wire counters
        the cost model's ``py_bytes_per_frame`` reads: a plain connection
        moves 0 payload bytes through the interpreter (mmap page ->
        socket by ``os.sendfile``; the client's records view their
        receive leases), a compressed one reads every spilled frame back
        to re-encode it — so the counter would see a lost splice."""
        n, ram_items = 24, 1
        srv = _lazy_spill_server(tmp_path, ram_items=ram_items)
        try:
            prod = TcpQueueClient(
                "127.0.0.1", srv.port, namespace="ns", queue_name="pb",
                reconnect_tries=1,
            )
            for i in range(n):
                assert prod.put(_rec(i))
            prod.disconnect()
            # the appends paid their log memcpy above; the window is the drain
            w0, s0 = WIRE.stats(), SPLICE.snapshot()
            cons = TcpQueueClient(
                "127.0.0.1", srv.port, namespace="ns", queue_name="pb",
                reconnect_tries=1, codec=codec,
            )
            got = _drain(cons, n)
            w1, s1 = WIRE.stats(), SPLICE.snapshot()
            assert [r.event_idx for r in got] == list(range(n))
            assert all(
                np.array_equal(r.panels, _rec(r.event_idx).panels) for r in got
            )
            cons.disconnect()
        finally:
            srv.shutdown()
        py_bytes = w1["bytes_copied_total"] - w0["bytes_copied_total"]
        spliced = s1["spliced_frames_total"] - s0["spliced_frames_total"]
        if codec is None:
            assert spliced >= n - ram_items and s1["fallback_total"] == s0["fallback_total"]
            assert py_bytes == 0, f"{py_bytes} payload bytes crossed Python on a spliced drain"
        else:
            assert spliced == 0
            assert py_bytes >= (n - ram_items) * _rec(0).panels.nbytes


# ---------------------------------------------------------------------------
# the assembled fleet (--workers 2, real port, real processes)
# ---------------------------------------------------------------------------


def _worker_pids(parent_pid):
    """Direct children of ``parent_pid`` via /proc (the fleet's workers)."""
    out = []
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat", "rb") as f:
                stat = f.read().decode("latin-1")
        except OSError:
            continue
        try:
            ppid = int(stat.rsplit(")", 1)[1].split()[1])
        except (IndexError, ValueError):
            continue
        if ppid == parent_pid:
            out.append(int(d))
    return sorted(out)


@pytest.mark.skipif(
    not (HAVE_REUSEPORT and HAVE_FORK and os.path.isdir("/proc")),
    reason="needs SO_REUSEPORT + fork + /proc",
)
class TestWorkersFleet:
    @staticmethod
    def _start(durable_dir, port_file, n=2):
        if os.path.exists(port_file):
            os.remove(port_file)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "psana_ray_tpu.queue_server",
                "--workers", str(n), "--host", "127.0.0.1", "--port", "0",
                "--durable_dir", durable_dir,
                "--fsync", "batch", "--fsync_batch_n", "1",
                "--port_file", port_file, "--stall_poll_s", "0",
                "--queue_size", "500",
                "--segment_bytes", str(1 << 20),
            ],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            stderr=subprocess.DEVNULL,
        )
        deadline = time.monotonic() + 30
        while not os.path.exists(port_file):
            assert proc.poll() is None, "fleet parent died on startup"
            assert time.monotonic() < deadline, "no port file"
            time.sleep(0.05)
        return proc, int(open(port_file).read())

    @staticmethod
    def _stop(proc):
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)

    def test_cross_worker_routing_roundtrips(self, tmp_path):
        # q0 is pinned to worker 0, q3 to worker 1 (the exact map is a
        # test above): whichever worker the kernel's accept sharding
        # lands each connection on, migration must deliver both queues
        proc, port = self._start(str(tmp_path / "log"), str(tmp_path / "port"))
        try:
            for qname in ("q0", "q3"):
                prod = TcpQueueClient(
                    "127.0.0.1", port, namespace="ns", queue_name=qname,
                )
                for i in range(10):
                    assert prod.put(_rec(i))
                cons = TcpQueueClient(
                    "127.0.0.1", port, namespace="ns", queue_name=qname,
                )
                got = _drain(cons, 10)
                assert [r.event_idx for r in got] == list(range(10)), qname
                prod.disconnect()
                cons.disconnect()
        finally:
            self._stop(proc)

    def test_default_queue_roundtrips(self, tmp_path):
        proc, port = self._start(str(tmp_path / "log"), str(tmp_path / "port"))
        try:
            prod = TcpQueueClient("127.0.0.1", port)
            for i in range(10):
                assert prod.put(_rec(i))
            cons = TcpQueueClient("127.0.0.1", port)
            got = _drain(cons, 10)
            assert [r.event_idx for r in got] == list(range(10))
            prod.disconnect()
            cons.disconnect()
        finally:
            self._stop(proc)

    def test_kill9_each_worker_mid_stream_zero_loss(self, tmp_path):
        # the ISSUE 17 acceptance row: a consumer is MID-STREAM (has
        # consumed a prefix, holds a live connection) when every worker
        # is killed -9 in turn — so the queue's owner dies exactly
        # once, whichever worker that is. The supervisor respawns with
        # the same worker id, the durable log re-exposes everything
        # unacked, and the SAME client resumes via its reconnect
        # envelope: zero loss, dupes allowed (at-least-once, as ever)
        proc, port = self._start(str(tmp_path / "log"), str(tmp_path / "port"))
        try:
            prod = TcpQueueClient(
                "127.0.0.1", port, namespace="ns", queue_name="q3",
            )
            for i in range(20):
                assert prod.put(_rec(i))
            prod.disconnect()

            # (twelve dials, not the default four: with full jitter four
            # can be over in half a second, and a respawned pid is not
            # yet a listening worker on a loaded machine)
            cons = TcpQueueClient(
                "127.0.0.1", port, namespace="ns", queue_name="q3",
                reconnect_tries=12,
            )
            first = cons.get_batch(6, timeout=10.0)
            assert len(first) == 6
            cons.size()  # implicit-ack: the committed offset moves

            initial = _worker_pids(proc.pid)
            assert len(initial) == 2, initial
            for victim in initial:
                os.kill(victim, signal.SIGKILL)
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline:
                    cur = _worker_pids(proc.pid)
                    if victim not in cur and len(cur) == 2:
                        break
                    time.sleep(0.05)
                cur = _worker_pids(proc.pid)
                assert victim not in cur and len(cur) == 2, (victim, cur)

            # the same client keeps consuming: its reconnect envelope
            # rides out the dead connection and replays the OPEN.
            # Collect until the union is complete (dupes are legal —
            # at-least-once — so a fixed count would be wrong both ways)
            seen = {r.event_idx for r in first}
            deadline = time.monotonic() + 30
            while seen != set(range(20)) and time.monotonic() < deadline:
                for r in cons.get_batch(64, timeout=10.0):
                    seen.add(r.event_idx)
            assert seen == set(range(20)), (
                f"lost={sorted(set(range(20)) - seen)}"
            )
            cons.disconnect()
        finally:
            self._stop(proc)

    def test_cli_refuses_incompatible_planes(self, tmp_path):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for extra in (
            ["--shm", "ring"],
            [
                "--replicate_peers", "a:1,b:2", "--advertise", "a:1",
                "--durable_dir", str(tmp_path / "d"),
            ],
        ):
            out = subprocess.run(
                [
                    sys.executable, "-m", "psana_ray_tpu.queue_server",
                    "--workers", "2", "--port", "0",
                ] + extra,
                capture_output=True, cwd=root, timeout=60,
            )
            assert out.returncode == 2, out.stderr
            assert b"--workers" in out.stderr
