"""Sharded queue cluster (ISSUE 7): partition placement stability,
routing-client semantics, consumer groups with generation-fenced
rebalance, cross-server EOS aggregation, and server-death failover.

Everything here is jax-free and loopback-only. Wall-clock throughput is
no tier-1 matter (the slow-marked class below models it); the tier-1
acceptance pin uses the deterministic message-count proxy (the PR 5/6
flake-avoidance convention): with a balanced map over 4 servers no
server hosts more than 3/8 of the stream, so aggregate capacity is
>= 2x any single server's at equal service rates — and every frame is
still delivered exactly through the merged streams.
"""

import threading
import time

import numpy as np
import pytest

from psana_ray_tpu.cluster.client import ClusterClient, parse_cluster_address
from psana_ray_tpu.cluster.coordinator import GroupRegistry
from psana_ray_tpu.cluster.hashring import (
    PartitionMap,
    assign_group_partitions,
    partition_queue_name,
)
from psana_ray_tpu.cluster.telemetry import CLUSTER
from psana_ray_tpu.records import EndOfStream, FrameRecord, is_eos
from psana_ray_tpu.transport import TransportClosed
from psana_ray_tpu.transport.ring import RingBuffer
from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer


def _frame(i, rank=0):
    return FrameRecord(rank, i, np.full((1, 4, 4), float(i), np.float32), 1.0)


def _servers(n, maxsize=64):
    servers = [
        TcpQueueServer(RingBuffer(maxsize), host="127.0.0.1", maxsize=maxsize)
        .serve_background()
        for _ in range(n)
    ]
    addrs = [f"127.0.0.1:{s.port}" for s in servers]
    return servers, addrs


def _shutdown(servers):
    for s in servers:
        try:
            s.shutdown()
        except Exception:
            pass


def _drain_until_eos(cons, budget_s=30.0, batch=16):
    """Drain merged streams until the ONE synthesized EOS; returns
    (event indices in arrival order, eos count)."""
    got, eos = [], 0
    deadline = time.monotonic() + budget_s
    while not eos and time.monotonic() < deadline:
        for item in cons.get_batch_stream(batch, timeout=0.5):
            if is_eos(item):
                eos += 1
            else:
                got.append(item.event_idx)
    return got, eos


# ---------------------------------------------------------------------------
# partition map: rendezvous stability
# ---------------------------------------------------------------------------

class TestPartitionMap:
    ADDRS = [f"10.0.0.{i}:7000" for i in range(1, 9)]  # fixed: deterministic

    def test_deterministic_and_exhaustive(self):
        a = PartitionMap.compute(self.ADDRS[:4], "q", 32)
        b = PartitionMap.compute(self.ADDRS[:4], "q", 32)
        assert a.assignments == b.assignments
        assert sorted(a.assignments) == list(range(32))
        assert set(a.assignments.values()) <= set(self.ADDRS[:4])

    def test_join_moves_at_most_its_expected_share(self):
        """Adding a server moves ONLY partitions the newcomer wins:
        ~1/(N+1) of them in expectation, and never a partition between
        two incumbent servers."""
        P = 64
        before = PartitionMap.compute(self.ADDRS[:4], "q", P)
        after = before.recompute(self.ADDRS[:5])
        moved = after.moved_from(before)
        # every move is TO the newcomer (rendezvous property, exact)
        assert all(after.assignments[p] == self.ADDRS[4] for p in moved)
        # and the share is ~P/5 — allow 2.5x slack over expectation
        assert len(moved) <= int(2.5 * P / 5), len(moved)
        assert after.version == before.version + 1

    def test_death_moves_only_the_dead_servers_partitions(self):
        P = 64
        before = PartitionMap.compute(self.ADDRS[:4], "q", P)
        dead = self.ADDRS[1]
        after = before.recompute([a for a in self.ADDRS[:4] if a != dead])
        moved = set(after.moved_from(before))
        assert moved == set(before.partitions_on(dead))
        # survivors' other partitions did not reshuffle
        for p in range(P):
            if p not in moved:
                assert after.assignments[p] == before.assignments[p]

    def test_group_assignment_disjoint_and_exhaustive(self):
        members = ["m-c", "m-a", "m-b"]
        P = 8
        all_parts = []
        for m in members:
            parts = assign_group_partitions(members, m, P)
            all_parts.extend(parts)
            # every member computes every OTHER member's view identically
            for other in members:
                assert assign_group_partitions(
                    list(reversed(members)), other, P
                ) == assign_group_partitions(members, other, P)
        assert sorted(all_parts) == list(range(P))
        assert assign_group_partitions(members, "not-a-member", P) == ()

    def test_parse_cluster_address(self):
        assert parse_cluster_address("cluster://a:1,b:2") == ["a:1", "b:2"]
        assert parse_cluster_address("a:1, b:2 ,") == ["a:1", "b:2"]
        with pytest.raises(ValueError):
            parse_cluster_address("cluster://")
        with pytest.raises(ValueError):
            parse_cluster_address("cluster://nohostport")


# ---------------------------------------------------------------------------
# routing client: transparent partitioned puts/gets + EOS aggregation
# ---------------------------------------------------------------------------

class TestClusterClient:
    def test_put_get_round_trip_spreads_over_servers(self):
        servers, addrs = _servers(2)
        prod = cons = None
        try:
            # search a queue name whose map puts >=1 partition on EVERY
            # server (ephemeral ports make the hash per-run; the search
            # is deterministic given them)
            qname = _balanced_queue_name(addrs, P=4, per_server_cap=3)
            prod = ClusterClient(addrs, queue_name=qname, n_partitions=4,
                                 maxsize=64)
            cons = ClusterClient(addrs, queue_name=qname, n_partitions=4,
                                 maxsize=64)
            N = 24
            for i in range(N):
                assert prod.put(_frame(i))
            # the partitions are ordinary named queues on their owners
            depths = [s.depth() for s in servers]
            assert sum(depths) == N
            assert all(d > 0 for d in depths), (
                f"one server hosts everything: {depths} — routing is not "
                f"spreading partitions"
            )
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            got, eos = _drain_until_eos(cons)
            assert sorted(got) == list(range(N))
            assert eos == 1
            # after the synthesized EOS the drain stays terminated
            assert cons.get_batch_stream(4, timeout=0.2) == []
        finally:
            if prod:
                prod.disconnect()
            if cons:
                cons.disconnect()
            _shutdown(servers)

    def test_eos_waits_for_every_partition_and_every_producer(self):
        """Cross-server EOS: two producer runtimes (ranks 0 and 1 of 2)
        each broadcast their marker; no partition may complete — and no
        synthesized EOS may surface — until BOTH producers' markers
        covered every partition."""
        servers, addrs = _servers(2)
        p0 = p1 = cons = None
        try:
            P = 4
            p0 = ClusterClient(addrs, n_partitions=P, maxsize=64)
            p1 = ClusterClient(addrs, n_partitions=P, maxsize=64)
            cons = ClusterClient(addrs, n_partitions=P, maxsize=64)
            for i in range(8):
                assert p0.put(_frame(i, rank=0))
            assert p0.put_wait(
                EndOfStream(producer_rank=0, shards_done=1, total_shards=2),
                timeout=10,
            )
            # producer 0 finished but producer 1 has not: the stream is
            # NOT over — the consumer must keep waiting, not stop early
            got, eos = [], 0
            deadline = time.monotonic() + 3.0
            while time.monotonic() < deadline and len(got) < 8:
                for item in cons.get_batch_stream(8, timeout=0.2):
                    if is_eos(item):
                        eos += 1
                    else:
                        got.append(item.event_idx)
            assert eos == 0, "EOS surfaced before all producers finished"
            assert sorted(got) == list(range(8))
            for i in range(8, 12):
                assert p1.put(_frame(i, rank=1))
            assert p1.put_wait(
                EndOfStream(producer_rank=1, shards_done=1, total_shards=2),
                timeout=10,
            )
            got2, eos = _drain_until_eos(cons)
            assert sorted(got2) == list(range(8, 12))
            assert eos == 1
        finally:
            for c in (p0, p1, cons):
                if c:
                    c.disconnect()
            _shutdown(servers)

    def test_data_reader_integration_terminates_exactly_once(self):
        """DataReader against a cluster:// address — the existing
        consumer surface works with only an address change."""
        from psana_ray_tpu.config import TransportConfig
        from psana_ray_tpu.consumer import DataReader

        servers, addrs = _servers(2)
        prod = None
        try:
            cfg = TransportConfig(
                address="cluster://" + ",".join(addrs), cluster_partitions=4
            )
            prod = ClusterClient(addrs, n_partitions=4, maxsize=64)
            N = 10
            for i in range(N):
                assert prod.put(_frame(i))
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            with DataReader(address=cfg.address, config=cfg) as reader:
                seen = [rec.event_idx for rec in reader.iter_records()]
            assert sorted(seen) == list(range(N))
        finally:
            if prod:
                prod.disconnect()
            _shutdown(servers)

    def test_batches_from_queue_over_cluster(self):
        """The infeed drain (batcher fan-in) over the merged streams:
        fixed-shape batches out, EOS flush, nothing lost."""
        from psana_ray_tpu.infeed.batcher import batches_from_queue

        servers, addrs = _servers(2)
        prod = cons = None
        try:
            prod = ClusterClient(addrs, n_partitions=4, maxsize=64)
            cons = ClusterClient(addrs, n_partitions=4, maxsize=64)
            N = 22  # deliberately not a batch multiple: pad+mask tail
            for i in range(N):
                assert prod.put(_frame(i))
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            seen = []
            for batch in batches_from_queue(cons, batch_size=8, max_wait_s=30.0):
                seen.extend(
                    int(batch.event_idx[j]) for j in range(batch.num_valid)
                )
            assert sorted(seen) == list(range(N))
        finally:
            if prod:
                prod.disconnect()
            if cons:
                cons.disconnect()
            _shutdown(servers)


# ---------------------------------------------------------------------------
# consumer groups: coordinator, fencing, rebalance
# ---------------------------------------------------------------------------

class TestGroupRegistry:
    def test_join_heartbeat_generations_and_fencing(self):
        reg = GroupRegistry(session_timeout_s=30.0)
        r1 = reg.handle({"op": "join", "group": "g", "member": "m1",
                         "n_partitions": 4})
        assert r1["ok"] and r1["members"] == ["m1"]
        gen1 = r1["generation"]
        r2 = reg.handle({"op": "join", "group": "g", "member": "m2",
                         "n_partitions": 4})
        gen2 = r2["generation"]
        assert gen2 > gen1 and r2["members"] == ["m1", "m2"]
        # m1 missed the rebalance: anything it sends at gen1 is FENCED
        hb = reg.handle({"op": "heartbeat", "group": "g", "member": "m1",
                         "generation": gen1})
        assert hb["fenced"] and not hb["ok"]
        drained = reg.handle({"op": "drained", "group": "g", "member": "m1",
                              "generation": gen1, "partition": 0})
        assert drained["fenced"] and not drained["ok"]
        assert reg.handle({"op": "info", "group": "g"})["drained"] == []
        # at the CURRENT generation the same ops succeed
        ok = reg.handle({"op": "drained", "group": "g", "member": "m1",
                         "generation": gen2, "partition": 0})
        assert ok["ok"] and ok["drained"] == [0]

    def test_lease_expiry_bumps_generation(self):
        reg = GroupRegistry(session_timeout_s=0.2)
        reg.handle({"op": "join", "group": "g", "member": "m1",
                    "n_partitions": 2})
        g0 = reg.handle({"op": "join", "group": "g", "member": "m2",
                         "n_partitions": 2})["generation"]
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            time.sleep(0.1)
            r = reg.handle({"op": "join", "group": "g", "member": "m1",
                            "n_partitions": 2})
            if r["members"] == ["m1"]:
                break
        else:
            pytest.fail("expired member never swept")
        assert r["generation"] > g0

    def test_unknown_group_and_bad_requests(self):
        reg = GroupRegistry()
        assert reg.handle({"op": "heartbeat", "group": "nope",
                           "member": "m", "generation": 0})["unknown_group"]
        assert not reg.handle({"op": "join", "group": ""})["ok"]
        assert not reg.handle({"op": "wat", "group": "g"}).get("ok", True) or \
            reg.handle({"op": "join", "group": "g", "member": "m"})["ok"]

    def test_rpc_over_the_wire(self):
        """The 'N' opcode end to end: the registry lives on the server,
        the client speaks JSON through cluster_rpc."""
        servers, addrs = _servers(1)
        try:
            host, _, port = addrs[0].rpartition(":")
            c = TcpQueueClient(host, int(port))
            r = c.cluster_rpc({"op": "join", "group": "wire", "member": "m1",
                               "n_partitions": 2})
            assert r["ok"] and r["members"] == ["m1"]
            r2 = c.cluster_rpc({"op": "heartbeat", "group": "wire",
                                "member": "m1", "generation": r["generation"]})
            assert r2["ok"]
            c.disconnect()
        finally:
            _shutdown(servers)


class TestConsumerGroups:
    def test_two_members_disjoint_partitions_one_eos_each(self):
        servers, addrs = _servers(2)
        clients = []
        try:
            P = 4
            prod = ClusterClient(addrs, n_partitions=P, maxsize=64)
            m1 = ClusterClient(addrs, n_partitions=P, maxsize=64,
                               group="g1", member_id="m1", heartbeat_s=0.2)
            m2 = ClusterClient(addrs, n_partitions=P, maxsize=64,
                               group="g1", member_id="m2", heartbeat_s=0.2)
            clients = [prod, m1, m2]
            N = 32
            for i in range(N):
                assert prod.put(_frame(i))
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            got1 = got2 = None
            eos1 = eos2 = 0
            got1, got2 = [], []
            deadline = time.monotonic() + 30.0
            while (not eos1 or not eos2) and time.monotonic() < deadline:
                for it in m1.get_batch_stream(8, timeout=0.2):
                    if is_eos(it):
                        eos1 += 1
                    else:
                        got1.append(it.event_idx)
                for it in m2.get_batch_stream(8, timeout=0.2):
                    if is_eos(it):
                        eos2 += 1
                    else:
                        got2.append(it.event_idx)
            # disjoint coverage, complete union, one aggregated EOS each
            assert sorted(got1 + got2) == list(range(N))
            assert got1 and got2, "a member was starved of partitions"
            assert not (set(got1) & set(got2)), "partitions not disjoint"
            assert eos1 == 1 and eos2 == 1
        finally:
            for c in clients:
                c.disconnect()
            _shutdown(servers)

    def test_member_join_rebalances_and_loses_nothing(self):
        """m1 owns everything, drains a bit; m2 joins mid-stream; the
        union after rebalance is still every frame (duplicates allowed —
        revoked in-flight frames requeue at head), and both finish."""
        servers, addrs = _servers(2)
        clients = []
        try:
            P = 4
            prod = ClusterClient(addrs, n_partitions=P, maxsize=128)
            m1 = ClusterClient(addrs, n_partitions=P, maxsize=128,
                               group="g2", member_id="m1", heartbeat_s=0.1)
            clients = [prod, m1]
            N = 64
            for i in range(N):
                assert prod.put(_frame(i))
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            seen = set()
            # m1 alone drains a few batches
            deadline = time.monotonic() + 10.0
            while len(seen) < 8 and time.monotonic() < deadline:
                for it in m1.get_batch_stream(4, timeout=0.3):
                    if not is_eos(it):
                        seen.add(it.event_idx)
            assert len(seen) >= 8
            # m2 joins: generation bumps, m1 rebalances on its next beat
            m2 = ClusterClient(addrs, n_partitions=P, maxsize=128,
                               group="g2", member_id="m2", heartbeat_s=0.1)
            clients.append(m2)
            eos1 = eos2 = 0
            deadline = time.monotonic() + 30.0
            while (not eos1 or not eos2) and time.monotonic() < deadline:
                for it in m1.get_batch_stream(8, timeout=0.2):
                    if is_eos(it):
                        eos1 += 1
                    else:
                        seen.add(it.event_idx)
                for it in m2.get_batch_stream(8, timeout=0.2):
                    if is_eos(it):
                        eos2 += 1
                    else:
                        seen.add(it.event_idx)
            assert seen >= set(range(N)), sorted(set(range(N)) - seen)
            assert eos1 == 1 and eos2 == 1
            assert CLUSTER.stats()["rebalances_total"] >= 1
        finally:
            for c in clients:
                c.disconnect()
            _shutdown(servers)

    def test_member_death_reassigns_with_zero_loss(self):
        """Kill a member WITHOUT leave (sockets die, lease expires): its
        pushed-but-unconsumed frames requeue at head, the survivor
        absorbs its partitions after the lease times out, and the union
        is still complete."""
        servers, addrs = _servers(2)
        clients = []
        try:
            for s in servers:
                s.groups.session_timeout_s = 0.6  # fast lease expiry
            P = 4
            prod = ClusterClient(addrs, n_partitions=P, maxsize=128)
            m1 = ClusterClient(addrs, n_partitions=P, maxsize=128,
                               group="g3", member_id="m1", heartbeat_s=0.15)
            m2 = ClusterClient(addrs, n_partitions=P, maxsize=128,
                               group="g3", member_id="m2", heartbeat_s=0.15)
            clients = [prod, m1]
            N = 48
            for i in range(N):
                assert prod.put(_frame(i))
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            seen = set()
            # both drain a little so both are real members with streams
            for _ in range(3):
                for it in m1.get_batch_stream(4, timeout=0.3):
                    if not is_eos(it):
                        seen.add(it.event_idx)
                for it in m2.get_batch_stream(4, timeout=0.3):
                    if not is_eos(it):
                        seen.add(it.event_idx)
            # m2 "crashes": abrupt socket death, no leave, no final ack.
            # A real crash takes the background heartbeat thread with the
            # process — stop it first, else the keepalive would faithfully
            # renew a zombie's lease forever (lease liveness IS process
            # liveness, by design)
            if m2._hb_stop is not None:
                m2._hb_stop.set()
                m2._hb_thread.join(timeout=2.0)
            for c in list(m2._clients.values()):
                c._sock.close()
            if m2._coord is not None:
                m2._coord._sock.close()
            eos1 = 0
            deadline = time.monotonic() + 30.0
            while not eos1 and time.monotonic() < deadline:
                for it in m1.get_batch_stream(8, timeout=0.2):
                    if is_eos(it):
                        eos1 += 1
                    else:
                        seen.add(it.event_idx)
            assert seen >= set(range(N)), sorted(set(range(N)) - seen)
            assert eos1 == 1
        finally:
            for c in clients:
                c.disconnect()
            _shutdown(servers)

    def test_fenced_drain_commit_is_retried_not_dropped(self):
        """Review fix: a drained-commit fenced mid-rebalance is a
        DEFERRAL, not a drop. Deterministic interleaving: a phantom
        member joins behind m1's back (generation bump) right before
        m1's tallies complete; every commit m1 sends is fenced. m1 must
        (a) retry commits for partitions it keeps, (b) re-seed consumed
        markers on partitions it lost, and (c) still produce exactly one
        group EOS once the phantom's lease expires and it reacquires
        everything."""
        servers, addrs = _servers(1)
        prod = m1 = None
        try:
            servers[0].groups.session_timeout_s = 0.8  # phantom expires fast
            P = 2
            prod = ClusterClient(addrs, n_partitions=P, maxsize=32)
            m1 = ClusterClient(addrs, n_partitions=P, maxsize=32,
                               group="g5", member_id="m1", heartbeat_s=0.1)
            N = 8
            for i in range(N):
                assert prod.put(_frame(i))
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            with m1._lock:
                m1._ensure_joined()
            # phantom member joins directly on the registry: m1's next
            # commit carries a stale generation and is FENCED
            servers[0].groups.handle({"op": "join", "group": "g5",
                                      "member": "zz-phantom",
                                      "n_partitions": P})
            got, eos = _drain_until_eos(m1, budget_s=30.0)
            assert sorted(set(got)) == list(range(N))
            assert eos == 1
            # the group really did commit every partition (registry view)
            info = servers[0].groups.handle({"op": "info", "group": "g5"})
            assert sorted(info["drained"]) == list(range(P))
        finally:
            if prod:
                prod.disconnect()
            if m1:
                m1.disconnect()
            _shutdown(servers)

    def test_group_name_reuse_starts_a_fresh_drain_epoch(self):
        """Review fix: queue servers are long-lived services — a second
        stream reusing a group name must NOT inherit the first stream's
        drained set (that handed new members an instant bogus EOS and
        silently stranded every new frame). A join into an EMPTY group
        clears the drained state: one name, many runs."""
        servers, addrs = _servers(1)
        clients = []
        try:
            P = 2
            for run in range(2):
                prod = ClusterClient(addrs, n_partitions=P, maxsize=32)
                m = ClusterClient(addrs, n_partitions=P, maxsize=32,
                                  group="reuse", member_id=f"m{run}",
                                  heartbeat_s=0.2)
                clients += [prod, m]
                lo = run * 4
                for i in range(lo, lo + 4):
                    assert prod.put(_frame(i))
                assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
                got, eos = _drain_until_eos(m, budget_s=20.0)
                assert sorted(got) == list(range(lo, lo + 4)), (run, got)
                assert eos == 1
                m.disconnect()  # leaves: the group empties between runs
                prod.disconnect()
        finally:
            for c in clients:
                try:
                    c.disconnect()
                except Exception:
                    pass
            _shutdown(servers)

    def test_stale_member_commit_is_fenced_end_to_end(self):
        """Generation fencing through the full stack: a member that
        missed a rebalance gets its drained-commit REJECTED (and its
        session rejoins) — the registry state is never corrupted by a
        stale writer."""
        servers, addrs = _servers(1)
        try:
            m1 = ClusterClient(addrs, n_partitions=2, maxsize=16,
                               group="g4", member_id="m1", heartbeat_s=999)
            with m1._lock:
                m1._ensure_joined()
            stale_gen = m1._session.generation
            # a second member joins behind m1's back -> generation moves
            m2 = ClusterClient(addrs, n_partitions=2, maxsize=16,
                               group="g4", member_id="m2", heartbeat_s=999)
            with m2._lock:
                m2._ensure_joined()
            fenced_before = CLUSTER.stats()["fenced_total"]
            # m1 tries to commit at the stale generation
            assert m1._session.generation == stale_gen
            assert m1._session.commit_drained(0) is False
            assert CLUSTER.stats()["fenced_total"] > fenced_before
            # the registry did NOT record the stale commit...
            info = servers[0].groups.handle({"op": "info", "group": "g4"})
            assert info["drained"] == []
            # ...and the fenced member came back current (rejoined)
            assert m1._session.generation > stale_gen
            assert m1._session.commit_drained(0) is True
            info = servers[0].groups.handle({"op": "info", "group": "g4"})
            assert info["drained"] == [0]
            m1.disconnect()
            m2.disconnect()
        finally:
            _shutdown(servers)


# ---------------------------------------------------------------------------
# failure handling: server death
# ---------------------------------------------------------------------------

class TestServerDeath:
    def test_kill_one_server_mid_stream_loses_zero_frames(self):
        """The ISSUE 7 acceptance shape: kill one of the servers while
        frames are in flight — surviving servers absorb its partitions,
        the producer resends its retained + unacked frames there, and
        every frame is delivered at least once (duplicates allowed)."""
        servers, addrs = _servers(3)
        prod = cons = None
        try:
            P = 4
            prod = ClusterClient(addrs, n_partitions=P, maxsize=64,
                                 retain=256, reconnect_tries=1,
                                 reconnect_base_s=0.05)
            cons = ClusterClient(addrs, n_partitions=P, maxsize=64,
                                 reconnect_tries=1, reconnect_base_s=0.05)
            # victim: the server owning the MOST partitions — ephemeral
            # ports randomize the map per run, and killing a server that
            # happens to own nothing would test nothing
            pmap = prod.partition_map
            victim_addr = max(addrs, key=lambda a: len(pmap.partitions_on(a)))
            victim = servers[addrs.index(victim_addr)]
            assert pmap.partitions_on(victim_addr)
            N = 60
            seen = set()
            for i in range(N):
                assert prod.put_pipelined(
                    _frame(i), deadline=time.monotonic() + 10
                )
                if i == 20:
                    # drain a little, then kill the server that is
                    # holding queued + acked frames
                    for it in cons.get_batch_stream(8, timeout=0.5):
                        if not is_eos(it):
                            seen.add(it.event_idx)
                    victim.shutdown()
            assert prod.flush_puts(time.monotonic() + 30)
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=20)
            got, eos = _drain_until_eos(cons)
            seen |= set(got)
            missing = set(range(N)) - seen
            assert not missing, f"frames LOST on server death: {sorted(missing)}"
            assert eos == 1
            # both sides observed the same recomputed map
            assert prod.partition_map.version >= 2
            assert cons.partition_map.version >= 2
            stats = CLUSTER.stats()
            assert stats["reassignments_total"] >= 1
        finally:
            if prod:
                prod.disconnect()
            if cons:
                cons.disconnect()
            _shutdown(servers)

    def test_exact_unacked_tail_resends_to_the_new_owner(self):
        """The PR 5 windowed-resend invariant across servers, pinned
        exactly: with retention off, the frames resent to the new owner
        are PRECISELY the tail still unacknowledged after the client
        drained every ack the dead server managed to deliver — no holes
        inside the tail, and no spurious resend of acked frames."""
        servers, addrs = _servers(2, maxsize=8)
        prod = None
        try:
            P = 1  # one partition: full control of what sits where
            prod = ClusterClient(addrs, n_partitions=P, maxsize=8,
                                 retain=0, reconnect_tries=1,
                                 reconnect_base_s=0.05)
            owner = prod.partition_map.assignments[0]
            owner_srv = servers[addrs.index(owner)]
            survivor = servers[1 - addrs.index(owner)]
            # frames 0..2: windowed puts, acks fully drained (known-acked)
            for i in range(3):
                assert prod.put_pipelined(_frame(i), deadline=time.monotonic() + 5)
            assert prod.flush_puts(time.monotonic() + 10)
            # frames 3..10: 3..7 enqueue (acks written but not yet read
            # by the client); 8..10 park server-side against the full
            # queue, their acks never written — the true unacked tail
            for i in range(3, 11):
                assert prod.put_pipelined(_frame(i), deadline=time.monotonic() + 5)
            with prod._lock:
                tail = [r.event_idx for r in prod._clients[0].unacked_puts()]
            assert tail == list(range(3, 11))  # nothing read yet
            # determinism: wait until the owner PROCESSED 3..7 (depth at
            # maxsize) so their acks are committed to the wire before it
            # dies — TCP delivers written data ahead of the FIN
            deadline = time.monotonic() + 5.0
            while owner_srv.depth() < 8 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert owner_srv.depth() == 8
            owner_srv.shutdown()
            # the next flush drains the delivered acks (3..7 become
            # known-acked), hits EOF, fails over, and resends EXACTLY
            # the remaining unacked tail: 8..10. Frames 3..7 died with
            # the server's queue — the exposure `retain` exists to cover
            # (the zero-loss test above runs the same kill WITH retain).
            assert prod.flush_puts(time.monotonic() + 30)
            host, _, port = addrs[1 - addrs.index(owner)].rpartition(":")
            drain = TcpQueueClient(
                host, int(port), namespace="default",
                queue_name=partition_queue_name("shared_queue", 0),
            )
            redelivered = sorted(
                r.event_idx for r in drain.get_batch(64, timeout=1.0)
            )
            assert redelivered == [8, 9, 10], redelivered
            assert survivor.depth() == 0  # nothing else was resent
            drain.disconnect()
        finally:
            if prod:
                prod.disconnect()
            _shutdown(servers)

    def test_eos_broadcast_survives_server_death_via_retention(self):
        """Review fix: EndOfStream markers ride the producer retention
        buffer like frames — a server that dies AFTER acking the EOS
        broadcast must not take its partitions' end-of-stream with it.
        The producer's next partition op fails over and resends retained
        frames AND the marker; the consumer still terminates."""
        servers, addrs = _servers(2)
        prod = cons = None
        try:
            P = 2
            qname = _balanced_queue_name(addrs, P, per_server_cap=1)
            prod = ClusterClient(addrs, queue_name=qname, n_partitions=P,
                                 maxsize=16, retain=16, reconnect_tries=1,
                                 reconnect_base_s=0.05)
            N = 6
            for i in range(N):
                assert prod.put(_frame(i))
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            # the broadcast is fully acked; NOW a server dies with its
            # queued frames + marker
            pmap = prod.partition_map
            victim_addr = max(addrs, key=lambda a: len(pmap.partitions_on(a)))
            servers[addrs.index(victim_addr)].shutdown()
            # any partition op on the live producer triggers failover +
            # retained resend (frames AND the EOS marker)
            prod.size()
            cons = ClusterClient(addrs, queue_name=qname, n_partitions=P,
                                 maxsize=16, reconnect_tries=1,
                                 reconnect_base_s=0.05)
            got, eos = _drain_until_eos(cons, budget_s=20.0)
            assert set(got) >= set(range(N)), sorted(set(range(N)) - set(got))
            assert eos == 1
        finally:
            if prod:
                prod.disconnect()
            if cons:
                cons.disconnect()
            _shutdown(servers)

    def test_all_servers_dead_raises(self):
        servers, addrs = _servers(2)
        prod = None
        try:
            prod = ClusterClient(addrs, n_partitions=2, maxsize=16,
                                 reconnect_tries=1, reconnect_base_s=0.05)
            assert prod.put(_frame(0))
            _shutdown(servers)
            with pytest.raises(TransportClosed):
                for i in range(1, 8):
                    prod.put(_frame(i))
        finally:
            if prod:
                prod.disconnect()


# ---------------------------------------------------------------------------
# the acceptance pin: deterministic message-count scaling proxy
# ---------------------------------------------------------------------------

def _balanced_queue_name(addrs, P=8, per_server_cap=3):
    """Search a queue name whose rendezvous map spreads partitions with
    no server above ``per_server_cap`` — deterministic given the ports,
    and the capacity precondition the proxy asserts against."""
    for i in range(512):
        name = f"scaling_q{i}"
        m = PartitionMap.compute(addrs, name, P)
        if max(len(m.partitions_on(a)) for a in addrs) <= per_server_cap:
            return name
    raise AssertionError("no balanced map found — hashring is degenerate")


class _RelayCore:
    """Saturated-relay model: one
    token bucket per server caps its queue ops/s — the regime where the
    single Python relay core is the bottleneck (ROADMAP item 2), which
    a 2-core loopback box cannot otherwise reach."""

    def __init__(self, ops_per_s):
        self._interval = 1.0 / ops_per_s
        self._next = 0.0
        self._lock = threading.Lock()

    def tick(self, n=1):
        with self._lock:
            now = time.monotonic()
            t = max(self._next, now)
            self._next = t + n * self._interval
        delay = t - now
        if delay > 0:
            time.sleep(delay)


class _ThrottledRing(RingBuffer):
    def __init__(self, maxsize, core, name=None):
        super().__init__(maxsize, name=name)
        self._core = core

    def put(self, item):
        self._core.tick()
        return super().put(item)

    def get_batch(self, max_items, timeout=0.0):
        items = super().get_batch(max_items, timeout)
        if items:
            self._core.tick(len(items))
        return items


@pytest.mark.slow
class TestClusterScalingWallClock:
    """The wall-clock half of the ISSUE 7 acceptance, slow-marked with
    best-of-retries per the PR 5 convention (the GIL quantum on this
    2-core box episodically dominates); tier-1 keeps the deterministic
    message-count proxy below."""

    def _run(self, n_servers, n_frames=400, ops_per_s=250.0):
        servers = []
        for _ in range(n_servers):
            core = _RelayCore(ops_per_s)
            servers.append(
                TcpQueueServer(
                    _ThrottledRing(256, core), host="127.0.0.1", maxsize=256,
                    queue_factory=(
                        lambda ns, name, maxsize, _c=core:
                        _ThrottledRing(maxsize, _c, name=f"{ns}__{name}")
                    ),
                ).serve_background()
            )
        addrs = [f"127.0.0.1:{s.port}" for s in servers]
        prod = cons = None
        try:
            qname = _balanced_queue_name(addrs, 8, per_server_cap=8 // n_servers + 1)
            prod = ClusterClient(addrs, queue_name=qname, n_partitions=8,
                                 maxsize=256)
            cons = ClusterClient(addrs, queue_name=qname, n_partitions=8,
                                 maxsize=256)

            def produce():
                for i in range(n_frames):
                    assert prod.put_pipelined(
                        _frame(i), deadline=time.monotonic() + 60
                    )
                prod.flush_puts(time.monotonic() + 60)
                prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=60)

            t = threading.Thread(target=produce, daemon=True)
            t0 = time.monotonic()
            t.start()
            got, eos = _drain_until_eos(cons, budget_s=120.0, batch=32)
            dt = time.monotonic() - t0
            t.join(timeout=10.0)
            assert sorted(set(got)) == list(range(n_frames))
            assert eos == 1
            return n_frames / dt
        finally:
            if prod:
                prod.disconnect()
            if cons:
                cons.disconnect()
            _shutdown(servers)

    def test_four_servers_at_least_2x_one_server_under_relay_model(self):
        best = 0.0
        for _ in range(2):  # best-of-retries: GIL-quantum flake armor
            fps1 = self._run(1)
            fps4 = self._run(4)
            best = max(best, fps4 / fps1)
            if best >= 2.0:
                break
        assert best >= 2.0, (
            f"4-server aggregate only {best:.2f}x the 1-server figure "
            f"under the saturated-relay model"
        )


class TestClusterScalingProxy:
    def test_four_servers_balanced_capacity_and_complete_delivery(self):
        """ISSUE 7 acceptance, deterministic proxy form (the wall-clock
        2x is the slow-marked class above): with 4 servers and a
        balanced 8-partition map, round-robin placement puts <= 3/8 of
        the stream on any one server — aggregate capacity >= 2x any
        single server at equal service rates — and the merged streams
        deliver every message exactly (no crashes -> no duplicates)."""
        servers, addrs = _servers(4)
        prod = cons = None
        try:
            P = 8
            qname = _balanced_queue_name(addrs, P)
            prod = ClusterClient(addrs, queue_name=qname, n_partitions=P,
                                 maxsize=64)
            cons = ClusterClient(addrs, queue_name=qname, n_partitions=P,
                                 maxsize=64)
            N = 64  # 8 per partition, exactly, by round-robin
            for i in range(N):
                assert prod.put(_frame(i))
            # message-count proxy: hosted frames per server == the map's
            # partition share x N/P, exactly (deterministic placement)
            pmap = prod.partition_map
            for s, addr in zip(servers, addrs):
                expect = len(pmap.partitions_on(addr)) * (N // P)
                assert s.depth() == expect, (addr, s.depth(), expect)
            shares = [s.depth() / N for s in servers]
            assert max(shares) <= 3 / 8, shares  # >= 2x single-server capacity
            assert sum(1 for sh in shares if sh > 0) >= 3
            assert prod.put_wait(EndOfStream(0, -1, 1, 1), timeout=10)
            got, eos = _drain_until_eos(cons)
            assert sorted(got) == list(range(N))  # exactly once, nothing lost
            assert eos == 1
        finally:
            if prod:
                prod.disconnect()
            if cons:
                cons.disconnect()
            _shutdown(servers)
