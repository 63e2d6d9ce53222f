"""Durable queue server integration tests (ISSUE 8): committed offsets
over the wire, kill -9 crash-restart with zero loss and exact resume,
replay for a second consumer group, bounded spill through the relay,
fault-proxy-driven recovery, and coordinator-state persistence."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from faultproxy import FaultProxy
from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.records import EndOfStream, FrameRecord, is_eos
from psana_ray_tpu.storage import DurableRingBuffer, SegmentLog
from psana_ray_tpu.transport import RingBuffer
from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
from psana_ray_tpu.utils.bufpool import WIRE


def _rec(i, shape=(1, 16, 16)):
    return FrameRecord(0, i, np.full(shape, i, np.uint16), 9.5)


def _durable_server(root, maxsize=500, ram_items=None, **log_kw):
    log_kw.setdefault("segment_bytes", 1 << 20)
    log_kw.setdefault("fsync", "none")

    def factory(ns, name, maxsize_):
        log = SegmentLog(
            os.path.join(str(root), f"{ns}__{name}"), name=name, **log_kw
        )
        return DurableRingBuffer(
            log, maxsize=maxsize_, name=name, ram_items=ram_items
        )

    srv = TcpQueueServer(
        factory("default", "default", maxsize),
        host="127.0.0.1", maxsize=maxsize, queue_factory=factory,
        group_store_path=os.path.join(str(root), "groups.json"),
    ).serve_background()
    return srv


def _drain(client, timeout=1.0):
    out = []
    while True:
        batch = client.get_batch(64, timeout=timeout)
        if not batch:
            return out
        out.extend(batch)
        if any(is_eos(x) for x in batch):
            return out


class TestCommittedOffsets:
    def test_implicit_ack_commits_over_the_wire(self, tmp_path):
        srv = _durable_server(tmp_path)
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            for i in range(10):
                assert prod.put(_rec(i))
            cons = TcpQueueClient("127.0.0.1", srv.port)
            got = cons.get_batch(4, timeout=1.0)
            assert len(got) == 4
            # nothing committed yet: the response is still in flight
            assert srv.queue.stats()["committed_offset"] == -1
            cons.size()  # the next opcode IS the ack
            assert srv.queue.stats()["committed_offset"] == 3
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()

    def test_consumer_death_without_ack_redelivers(self, tmp_path):
        srv = _durable_server(tmp_path)
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            for i in range(8):
                assert prod.put(_rec(i))
            cons = TcpQueueClient("127.0.0.1", srv.port)
            got = cons.get_batch(3, timeout=1.0)
            assert len(got) == 3
            cons._sock.close()  # crash: no BYE, no next opcode, no ack
            cons2 = TcpQueueClient("127.0.0.1", srv.port)
            deadline = time.monotonic() + 5.0
            redelivered = []
            while len(redelivered) < 8 and time.monotonic() < deadline:
                redelivered.extend(cons2.get_batch(8, timeout=0.25))
            # requeue-at-head within this life; floor never moved
            assert [r.event_idx for r in redelivered] == list(range(8))
            cons2.size()
            assert srv.queue.stats()["committed_offset"] == 7
            prod.disconnect()
            cons2.disconnect()
        finally:
            srv.shutdown()

    def test_stream_cumulative_ack_commits(self, tmp_path):
        srv = _durable_server(tmp_path)
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            for i in range(6):
                assert prod.put(_rec(i))
            cons = TcpQueueClient("127.0.0.1", srv.port)
            reader = cons.stream_open(window=8)
            first = reader.get_batch_stream(6, timeout=2.0)
            # acked when the consumer comes back for more
            reader.get_batch_stream(1, timeout=0.1)
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                if srv.queue.stats()["committed_offset"] == len(first) - 1:
                    break
                time.sleep(0.02)
            assert srv.queue.stats()["committed_offset"] == len(first) - 1
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()


class TestCrashRestart:
    """kill -9 the queue-server PROCESS mid-stream, restart on the same
    --durable_dir, assert zero loss and exact resume at the committed
    offset — the ISSUE 8 acceptance row."""

    @staticmethod
    def _start(durable_dir, port_file, fsync="batch"):
        if os.path.exists(port_file):
            os.remove(port_file)
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "psana_ray_tpu.queue_server",
                "--port", "0", "--durable_dir", durable_dir,
                "--fsync", fsync, "--fsync_batch_n", "8",
                "--port_file", port_file, "--stall_poll_s", "0",
                "--queue_size", "500",
                "--segment_bytes", str(1 << 20),
            ],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            stderr=subprocess.DEVNULL,
        )
        deadline = time.monotonic() + 30
        while not os.path.exists(port_file):
            assert proc.poll() is None, "queue server died on startup"
            assert time.monotonic() < deadline, "server never wrote port file"
            time.sleep(0.05)
        return proc, int(open(port_file).read())

    def test_kill9_zero_loss_exact_resume(self, tmp_path):
        durable_dir = str(tmp_path / "log")
        port_file = str(tmp_path / "port")
        proc, port = self._start(durable_dir, port_file)
        try:
            prod = TcpQueueClient(
                "127.0.0.1", port, namespace="ns", queue_name="q",
                reconnect_tries=1,
            )
            # windowed pipelined puts with sampled fsync points (batch=8)
            for i in range(60):
                assert prod.put_pipelined(_rec(i))
            assert prod.flush_puts()
            cons = TcpQueueClient(
                "127.0.0.1", port, namespace="ns", queue_name="q",
                reconnect_tries=1,
            )
            first = cons.get_batch(25, timeout=2.0)
            cons.size()  # implicit-ack: committed offset = 24
            assert len(first) == 25

            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=10)

            t0 = time.monotonic()
            proc, port = self._start(durable_dir, port_file)
            recovery_s = time.monotonic() - t0
            cons2 = TcpQueueClient(
                "127.0.0.1", port, namespace="ns", queue_name="q",
                reconnect_tries=1,
            )
            rest = _drain(cons2)
            idxs = sorted(r.event_idx for r in rest)
            # exact resume at the committed offset: 25..59, no loss, and
            # no redelivery of the acked prefix either
            assert idxs == list(range(25, 60)), (
                f"lost={sorted(set(range(25, 60)) - set(idxs))} "
                f"dup={len(idxs) - len(set(idxs))}"
            )
            assert recovery_s < 30
            cons2.disconnect()
        finally:
            if proc.poll() is None:
                os.kill(proc.pid, signal.SIGKILL)
                proc.wait(timeout=10)

    def test_torn_tail_repair_breadcrumb_on_reboot(self, tmp_path):
        # build a log, corrupt the last record on disk, reboot the
        # backing: the scan must truncate and leave the breadcrumb
        log = SegmentLog(
            str(tmp_path / "q"), segment_bytes=1 << 20, fsync="none", name="q"
        )
        q = DurableRingBuffer(log, maxsize=64, name="q")
        for i in range(5):
            q.put(_rec(i))
        seg = log._segments[-1]
        pos = seg.find(4)
        path = seg.path
        log.close()
        with open(path, "r+b") as f:
            f.seek(pos + 30)
            f.write(b"\xff\xff\xff\xff")
        n0 = FLIGHT.event_count
        log2 = SegmentLog(
            str(tmp_path / "q"), segment_bytes=1 << 20, fsync="none", name="q"
        )
        q2 = DurableRingBuffer(log2, maxsize=64, name="q")
        kinds = [e["kind"] for e in FLIGHT.events()]
        assert "torn_tail_repair" in kinds and "recovery_scan" in kinds
        assert FLIGHT.event_count > n0
        # the 4 intact records re-expose; the torn 5th redelivers via the
        # producer-side resend contract, never silently served
        assert [r.event_idx for r in q2.get_batch(16, timeout=0)] == [0, 1, 2, 3]
        log2.close()


class TestReplay:
    def test_second_group_replays_from_begin_without_disturbing_live(
        self, tmp_path
    ):
        srv = _durable_server(tmp_path)
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            for i in range(12):
                assert prod.put(_rec(i))
            prod.put(EndOfStream(total_events=12))
            live = TcpQueueClient("127.0.0.1", srv.port)
            first_live = live.get_batch(5, timeout=1.0)
            live.size()  # ack

            rep = TcpQueueClient("127.0.0.1", srv.port)
            info = rep.replay_open("begin", group="model-v2")
            assert info["start"] == 0
            replayed = _drain(rep)
            idxs = [getattr(r, "event_idx", "EOS") for r in replayed]
            assert idxs == [*range(12), "EOS"]  # the FULL retained range
            assert rep.commit_offset() is True

            # live consumption continues exactly where it was
            rest_live = _drain(live)
            live_idxs = [getattr(r, "event_idx", "EOS") for r in rest_live]
            assert live_idxs == [*range(5, 12), "EOS"]
            for c in (prod, live, rep):
                c.disconnect()
        finally:
            srv.shutdown()

    def test_replay_resume_continues_after_crash(self, tmp_path):
        srv = _durable_server(tmp_path)
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            for i in range(10):
                assert prod.put(_rec(i))
            rep = TcpQueueClient("127.0.0.1", srv.port)
            rep.replay_open("begin", group="g2")
            first = rep.get_batch(4, timeout=1.0)
            rep.size()  # implicit ack commits g2 through offset 3
            # crash the replay consumer without BYE
            rep._sock.close()
            rep2 = TcpQueueClient("127.0.0.1", srv.port)
            rep2.replay_open("resume", group="g2")
            rest = rep2.get_batch(32, timeout=1.0)
            assert [r.event_idx for r in first] == [0, 1, 2, 3]
            assert [r.event_idx for r in rest] == [4, 5, 6, 7, 8, 9]
            prod.disconnect()
            rep2.disconnect()
        finally:
            srv.shutdown()

    def test_replay_open_on_streamed_connection_refused(self, tmp_path):
        srv = _durable_server(tmp_path)
        try:
            c = TcpQueueClient("127.0.0.1", srv.port)
            c.stream_open(window=4)
            with pytest.raises(RuntimeError, match="streamed"):
                c.replay_open("begin")
            c.disconnect()
        finally:
            srv.shutdown()

    def test_oversized_record_errors_without_killing_the_loop(self, tmp_path):
        # a record bigger than segment_bytes raises ValueError inside the
        # durable queue; via the PARKED put path ('U' against a full
        # queue) that exception must answer THIS client with a protocol
        # error — not escape the pump and take down the whole server
        srv = _durable_server(tmp_path, maxsize=1, segment_bytes=1 << 16)
        try:
            filler = TcpQueueClient("127.0.0.1", srv.port)
            assert filler.put(_rec(0))  # queue (maxsize=1) now full
            big = _rec(1, shape=(8, 64, 64))  # 64 KB payload > 64 KB segment
            blocked = TcpQueueClient("127.0.0.1", srv.port)
            with pytest.raises(RuntimeError, match="protocol error"):
                # parks as a 'U' waiter, then the pump's put raises when
                # space frees
                import threading as _t

                def free_soon():
                    time.sleep(0.3)
                    drainer = TcpQueueClient("127.0.0.1", srv.port)
                    drainer.get_batch(4, timeout=1.0)
                    drainer.disconnect()

                _t.Thread(target=free_soon, daemon=True).start()
                blocked.put_wait(big, timeout=5.0)
            # the loop survived: a fresh client still gets served
            probe = TcpQueueClient("127.0.0.1", srv.port)
            assert isinstance(probe.size(), int)
            for c in (filler, probe):
                c.disconnect()
        finally:
            srv.shutdown()

    def test_replay_refused_on_memory_only_queue(self, tmp_path):
        from psana_ray_tpu.transport.ring import RingBuffer

        srv = TcpQueueServer(RingBuffer(10), host="127.0.0.1").serve_background()
        try:
            c = TcpQueueClient("127.0.0.1", srv.port)
            with pytest.raises(RuntimeError, match="no segment log"):
                c.replay_open("begin")
            c.disconnect()
        finally:
            srv.shutdown()


class TestSpillThroughRelay:
    def test_depth_beyond_ram_arrives_intact(self, tmp_path):
        srv = _durable_server(tmp_path, maxsize=300, ram_items=8)
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            for i in range(120):
                assert prod.put_pipelined(_rec(i))
            assert prod.flush_puts()
            st = srv.queue.stats()
            assert st["spilled"] >= 100 and st["resident"] <= 8
            cons = TcpQueueClient("127.0.0.1", srv.port)
            got = []
            while len(got) < 120:
                batch = cons.get_batch(64, timeout=1.0)
                if not batch:
                    break
                got.extend(batch)
            assert [r.event_idx for r in got] == list(range(120))
            # spilled frames decode byte-exact
            assert np.array_equal(got[100].panels, _rec(100).panels)
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()


    @pytest.mark.parametrize("fsync,relay_copies", [(None, 0), ("none", 1), ("batch", 1)],
                             ids=["log_off", "fsync_none", "fsync_batch"])
    def test_relay_added_copies_a_frame(self, tmp_path, fsync, relay_copies):
        """What durability costs the relay in payload copies, counted:
        a memory-only server adds none (put by ``sendmsg``, the queued
        record views its receive lease, the get sends that same buffer),
        a log-backed one adds EXACTLY one — ``encode_into``'s memcpy into
        the mmap'd segment, no intermediate bytes — whatever the fsync
        policy. (The consumer's batch-arena copy is downstream of the
        relay: ``tests/test_wire_zero_copy.py``.)"""
        n = 48
        if fsync is None:
            srv = TcpQueueServer(RingBuffer(64), host="127.0.0.1").serve_background()
        else:
            srv = _durable_server(tmp_path, maxsize=64, fsync=fsync, fsync_batch_n=8)
        try:
            prod = TcpQueueClient("127.0.0.1", srv.port)
            cons = TcpQueueClient("127.0.0.1", srv.port)
            c0 = WIRE.stats()
            for i in range(n):
                assert prod.put_pipelined(_rec(i), deadline=time.monotonic() + 30)
            assert prod.flush_puts(deadline=time.monotonic() + 30)
            assert prod.put_wait(EndOfStream(total_events=n), timeout=30)
            got = [r for r in _drain(cons) if not is_eos(r)]
            c1 = WIRE.stats()
            assert [r.event_idx for r in got] == list(range(n))
            assert np.array_equal(got[-1].panels, _rec(n - 1).panels)
            prod.disconnect()
            cons.disconnect()
        finally:
            srv.shutdown()
        assert c1["copies_total"] - c0["copies_total"] == relay_copies * n
        assert c1["bytes_copied_total"] - c0["bytes_copied_total"] == (
            relay_copies * n * _rec(0).panels.nbytes
        )


class TestFaultProxyDriven:
    def test_kill_at_byte_mid_put_loses_nothing(self, tmp_path):
        """Sever the producer wire mid-record: the windowed-put resend
        plus the durable floor must deliver every frame, holes never."""
        srv = _durable_server(tmp_path)
        proxy = FaultProxy("127.0.0.1", srv.port)
        try:
            prod = TcpQueueClient("127.0.0.1", proxy.port)
            wire_one = len(b"".join(
                bytes(p) for p in __import__(
                    "psana_ray_tpu.transport.codec", fromlist=["*"]
                ).encode_payload_parts(_rec(0))
            ))
            # cut mid-way through the 5th frame's payload
            fault = proxy.kill_at("up", int(4.5 * wire_one))
            for i in range(20):
                assert prod.put_pipelined(_rec(i))
            assert prod.flush_puts()
            assert fault.fired
            cons = TcpQueueClient("127.0.0.1", srv.port)
            got = []
            while True:
                batch = cons.get_batch(64, timeout=0.5)
                if not batch:
                    break
                got.extend(batch)
            idxs = [r.event_idx for r in got]
            assert sorted(set(idxs)) == list(range(20)), "holes!"
            prod.disconnect()
            cons.disconnect()
        finally:
            proxy.close()
            srv.shutdown()

    def test_stall_injection_rides_backpressure(self, tmp_path):
        srv = _durable_server(tmp_path)
        proxy = FaultProxy("127.0.0.1", srv.port)
        try:
            prod = TcpQueueClient("127.0.0.1", proxy.port)
            proxy.stall_at("up", 1024, stall_s=0.4)
            t0 = time.monotonic()
            for i in range(8):
                assert prod.put(_rec(i))
            assert time.monotonic() - t0 >= 0.3  # the stall really bit
            cons = TcpQueueClient("127.0.0.1", srv.port)
            got = cons.get_batch(16, timeout=1.0)
            assert [r.event_idx for r in got] == list(range(8))
            prod.disconnect()
            cons.disconnect()
        finally:
            proxy.close()
            srv.shutdown()


class TestCoordinatorPersistence:
    def test_registry_recovers_groups_from_store(self, tmp_path):
        from psana_ray_tpu.cluster.coordinator import GroupRegistry

        store = str(tmp_path / "groups.json")
        reg = GroupRegistry(store_path=store)
        resp = reg.handle(
            {"op": "join", "group": "g", "member": "m1", "n_partitions": 4}
        )
        gen = resp["generation"]
        reg.handle({
            "op": "drained", "group": "g", "member": "m1",
            "generation": gen, "partition": 2, "offset": 41,
        })
        # coordinator restart: a FRESH registry over the same store
        reg2 = GroupRegistry(store_path=store)
        info = reg2.handle({"op": "info", "group": "g"})
        assert info["n_partitions"] == 4
        assert info["drained"] == [2]
        assert info["offsets"] == {"2": 41}
        # generations continue monotonically: stale members stay fenced
        assert info["generation"] >= gen
        fenced = reg2.handle({
            "op": "drained", "group": "g", "member": "m1",
            "generation": gen - 1, "partition": 3,
        })
        assert fenced.get("fenced") is True

    def test_midstream_recovery_survives_the_first_rejoin(self, tmp_path):
        """The recovered drained/offsets state must NOT be wiped by the
        new-epoch heuristic when members rejoin after a coordinator
        restart — their EOS markers are already consumed; nobody could
        ever re-commit the drained partitions."""
        from psana_ray_tpu.cluster.coordinator import GroupRegistry

        store = str(tmp_path / "groups.json")
        reg = GroupRegistry(store_path=store)
        gen = reg.handle(
            {"op": "join", "group": "g", "member": "m1", "n_partitions": 4}
        )["generation"]
        reg.handle({
            "op": "drained", "group": "g", "member": "m1",
            "generation": gen, "partition": 1, "offset": 7,
        })
        # coordinator restart MID-STREAM (drain incomplete: 1 of 4)
        reg2 = GroupRegistry(store_path=store)
        resp = reg2.handle(
            {"op": "join", "group": "g", "member": "m1", "n_partitions": 4}
        )
        assert resp["drained"] == [1], "recovered drain progress was wiped"
        assert resp["offsets"] == {"1": 7}
        # but a FINISHED run reusing the group name after a restart is
        # a new epoch: the stale complete drain set must clear
        reg3 = GroupRegistry(store_path=store)
        gen3 = reg3.handle(
            {"op": "join", "group": "g2", "member": "m", "n_partitions": 2}
        )["generation"]
        for part in (0, 1):
            gen3 = reg3.handle({
                "op": "drained", "group": "g2", "member": "m",
                "generation": gen3, "partition": part,
            })["generation"]
        reg3.handle({"op": "leave", "group": "g2", "member": "m"})
        reg4 = GroupRegistry(store_path=store)
        fresh = reg4.handle(
            {"op": "join", "group": "g2", "member": "m9", "n_partitions": 2}
        )
        assert fresh["drained"] == [], "finished-run state leaked into a new epoch"

    def test_memory_only_registry_still_forgets(self, tmp_path):
        from psana_ray_tpu.cluster.coordinator import GroupRegistry

        reg = GroupRegistry()
        reg.handle({"op": "join", "group": "g", "member": "m", "n_partitions": 2})
        reg2 = GroupRegistry()
        assert reg2.handle({"op": "info", "group": "g"}).get("unknown_group")


class TestClusterMigration:
    def test_add_server_drains_log_backed_partitions(self, tmp_path):
        from psana_ray_tpu.cluster.client import ClusterClient

        servers = [
            _durable_server(tmp_path / f"s{i}", maxsize=200) for i in range(5)
        ]
        try:
            addrs = [f"127.0.0.1:{s.port}" for s in servers[:2]]
            prod = ClusterClient(addrs, queue_name="q", n_partitions=8, maxsize=200)
            for i in range(30):
                assert prod.put_pipelined(_rec(i))
            assert prod.put(EndOfStream(total_events=30))
            cons = ClusterClient(addrs, queue_name="q", n_partitions=8, maxsize=200)
            # rendezvous hashing may hand a particular newcomer nothing
            # (placement is a function of the random ephemeral ports):
            # keep growing until one actually wins a partition
            moved = 0
            for s in servers[2:]:
                moved = cons.add_server(f"127.0.0.1:{s.port}")
                if moved:
                    break
            assert moved > 0  # a newcomer won something
            seen = []
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                batch = cons.get_batch(32, timeout=1.0)
                if not batch:
                    continue
                done = False
                for r in batch:
                    if is_eos(r):
                        done = True
                    else:
                        seen.append(r.event_idx)
                if done:
                    break
            # the PR 7 gap is closed for log-backed queues: nothing the
            # old owner still held is stranded
            assert sorted(set(seen)) == list(range(30))
            prod.disconnect()
            cons.disconnect()
        finally:
            for s in servers:
                s.shutdown()
