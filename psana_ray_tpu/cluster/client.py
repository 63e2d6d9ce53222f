"""ClusterClient: N queue servers presented as ONE logical queue.

The reference's Ray actor registry let any producer/consumer rendezvous
on a named queue anywhere in the cluster; our single queue-server
process was the remaining scale choke point (ROADMAP item 2). This
module is the disaggregation layer tf.data argues for (PAPERS.md): a
logical queue becomes ``n_partitions`` partitions, each an ordinary
named queue (``<queue>#p<N>``) living on ONE server, placed by
rendezvous hashing over the live server set
(:mod:`psana_ray_tpu.cluster.hashring`). The client wraps one
:class:`~psana_ray_tpu.transport.tcp.TcpQueueClient` per partition and
presents the SAME transport contract (put/get/size/put_wait/get_wait/
get_batch/get_batch_stream/put_pipelined/flush_puts/stream_open/
disconnect), so ``DataReader``, ``batches_from_queue``, the producer's
``_Sender`` and the consumer/sfx CLIs work against a cluster with only
an address-list change (``cluster://host:port,host:port``).

Semantics, carried across servers unchanged:

- **Placement**: ``put`` round-robins partitions (or hashes a caller
  key — ``partition_key``); consumers merge per-partition credit-based
  streams. Adding a server moves ~1/N of partitions; a dead server's
  partitions reassign to the survivors.
- **At-least-once**: the per-server windowed-PUT resend and streamed
  redelivery contracts (PR 5) hold per partition. When a server DIES
  for good (reconnects exhausted, listener unreachable), the producer
  resends to the partition's new owner: the unacked windowed tail
  always (holes never), plus the last ``retain`` acknowledged frames
  (``retain`` bounds the acked-but-possibly-undelivered exposure a
  crashed server creates — frames it had queued die with it unless a
  copy is still client-side). Duplicates possible, loss never, provided
  ``retain >= partition queue depth + consumer credit windows``.
- **Consumer groups**: members of a named group get disjoint partition
  assignments — the deterministic function of the coordinator's
  generation-fenced membership list (:mod:`psana_ray_tpu.cluster.
  group`). Rebalance on join/leave/death closes revoked partitions
  (their in-flight frames requeue at head for the new owner) and
  re-seeds any partially-observed EOS markers so drain progress is
  never lost to a fence.
- **Cross-server EOS**: a produced ``EndOfStream`` broadcasts to every
  partition; the consuming client tallies markers PER PARTITION
  (:class:`~psana_ray_tpu.records.EosTally` — multi-producer coverage
  works per partition exactly as it did per queue) and surfaces ONE
  synthesized end-of-stream only after every partition drained (group
  mode: committed group-wide through the coordinator, so the answer is
  one EOS per group even across rebalances).

Blocking discipline: this class sits inside the batcher's audited drain
graph (``get_batch_stream`` is reachable from ``batches_from_queue``
via the same seed edge as the single-server stream reader). Every wait
here is a caller-deadline-bounded slice delegated to the per-partition
clients (socket timeouts) or an interruptible ``Event.wait`` — no
sleeps, no unbounded reads.
"""

from __future__ import annotations

import socket
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

from psana_ray_tpu.cluster.coordinator import coordinator_address
from psana_ray_tpu.cluster.group import GroupSession
from psana_ray_tpu.cluster.hashring import PartitionMap, partition_queue_name
from psana_ray_tpu.cluster.telemetry import CLUSTER
from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.records import EndOfStream, EosTally, is_eos
from psana_ray_tpu.transport.registry import TransportClosed
from psana_ray_tpu.transport.ring import EMPTY
from psana_ray_tpu.transport.tcp import DEFAULT_STREAM_WINDOW, TcpQueueClient

# how long one liveness probe may spend deciding dead-vs-graceful when a
# partition op failed with TransportClosed (a fresh TCP dial)
_PROBE_CONNECT_TIMEOUT_S = 0.75
# merge-drain pacing: the bounded slice blocked on ONE partition before
# re-sweeping the others for already-buffered frames (streaming mode —
# the sweep is free there, it reads local push buffers)
_MERGE_SLICE_S = 0.05
# pull mode blocks longer per rotation: each slice is a server-side
# bounded wait ('D'), so a longer slice means FEWER round trips while
# idle — the rotation across partitions still bounds per-partition
# attention to one slice
_PULL_SLICE_S = 0.25
# default producer-side retention of acknowledged frames per partition
# (the crashed-server exposure bound — see the module docstring)
DEFAULT_RETAIN = 128


def parse_cluster_address(address: str) -> List[str]:
    """``cluster://h1:p1,h2:p2,...`` -> ordered server list (the order
    is part of the config: the FIRST server is the group coordinator)."""
    body = address[len("cluster://"):] if address.startswith("cluster://") else address
    servers = [a.strip() for a in body.split(",") if a.strip()]
    if not servers:
        raise ValueError(f"cluster address {address!r} names no servers")
    for a in servers:
        host, _, port = a.rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(f"bad cluster server {a!r} (want host:port)")
    return servers


class ClusterClient:
    """One logical queue over N servers — see the module docstring."""

    def __init__(
        self,
        servers: Sequence[str],
        namespace: str = "default",
        queue_name: str = "shared_queue",
        n_partitions: int = 8,
        maxsize: int = 0,
        group: Optional[str] = None,
        member_id: Optional[str] = None,
        partition_key: Optional[Callable[[Any], int]] = None,
        retain: int = DEFAULT_RETAIN,
        stream_window: int = DEFAULT_STREAM_WINDOW,
        put_window: int = DEFAULT_STREAM_WINDOW,
        timeout_s: float = 30.0,
        reconnect_tries: int = 2,
        reconnect_base_s: float = 0.2,
        heartbeat_s: float = 1.0,
        pool=None,
        codec=None,
        tenant=None,
        tenant_weight: int = 1,
    ):
        self._addresses = parse_cluster_address(
            servers if isinstance(servers, str) else ",".join(servers)
        )
        self.namespace = namespace
        self.queue_name = queue_name
        self._maxsize = maxsize
        self._partition_key = partition_key
        self._retain = max(0, int(retain))
        self._stream_window = stream_window
        self._put_window = put_window
        self._timeout_s = timeout_s
        self._reconnect_tries = reconnect_tries
        self._reconnect_base_s = reconnect_base_s
        self._pool = pool
        # wire compression (ISSUE 9): negotiated PER PARTITION CONNECTION
        # — each TcpQueueClient advertises this and its server picks, so
        # a mixed-version cluster degrades per server, not per stream.
        # The tenant hello (ISSUE 12) rides the same exchange, so every
        # partition connection carries the stream's fair-share identity
        self._codec = codec
        self._tenant = tenant
        self._tenant_weight = tenant_weight
        self._lock = threading.RLock()
        self._map = PartitionMap.compute(
            self._addresses, queue_name, n_partitions
        )  # guarded-by: _lock
        self._dead: set = set()  # guarded-by: _lock
        self._clients: Dict[int, TcpQueueClient] = {}  # guarded-by: _lock
        # partitions whose owner DIED (not merely moved): the next
        # connection to the new owner sends 'Y' promote first, so a
        # replica log there (ISSUE 11) is fenced + mounted as the live
        # queue before OPEN touches it
        self._promote_pending: set = set()  # guarded-by: _lock
        self._resend_pending: Dict[int, List[Any]] = {}  # guarded-by: _lock
        self._retained: Dict[int, deque] = {}  # guarded-by: _lock
        self._rr = 0  # round-robin put cursor  # guarded-by: _lock
        self._scan = 0  # merge-drain rotation cursor  # guarded-by: _lock
        self._streaming = False  # guarded-by: _lock
        # durable replay (ISSUE 8): (from, group) applied to each
        # partition connection on first consumer use — per-partition
        # segment logs have per-partition offsets, so "from=<N>" is a
        # per-partition position; "begin"/"resume" do what they say on
        # every partition
        self._replay: Optional[tuple] = None  # guarded-by: _lock
        # server address -> bool: whether that server mounts durable
        # queues (fixed for a server's lifetime) — probed once, so the
        # drained-commit offset lookup costs memory-only clusters zero
        # extra RPCs
        self._durable_servers: Dict[str, bool] = {}  # guarded-by: _lock
        self._tallies: Dict[int, EosTally] = {}  # guarded-by: _lock
        self._drained: set = set()  # guarded-by: _lock
        # drained partitions whose group-wide commit was FENCED and must
        # be retried under the new generation (a fenced commit is a
        # deferral, never a drop — the group EOS depends on it landing)
        self._commit_retry: set = set()  # guarded-by: _lock
        # the generation whose assignment this client last APPLIED —
        # compared against the session's current generation every drain
        # pass, so a rebalance observed through ANY rpc (heartbeat,
        # fenced-commit rejoin, ...) is applied, not just heartbeats
        self._applied_gen = -1  # guarded-by: _lock
        self._eos_emitted = False  # guarded-by: _lock
        self._idle = threading.Event()  # interruptible bounded pause
        # consumer group: the session is created NOW but joins LAZILY on
        # first consumer use — a monitor handle (size()/stats() probes)
        # must never become a group member
        self._session: Optional[GroupSession] = None
        self._coord: Optional[TcpQueueClient] = None  # guarded-by: _lock
        self._coord_addr: Optional[str] = None  # guarded-by: _lock
        if group:
            self._session = GroupSession(
                self._rpc, group, member_id,
                n_partitions=n_partitions, heartbeat_s=heartbeat_s,
            )
        self._session_hb_s = heartbeat_s
        self._hb_stop: Optional[threading.Event] = None
        self._hb_thread: Optional[threading.Thread] = None
        self._joined = False  # guarded-by: _lock
        self._held: set = set()  # partitions with an open consumer view  # guarded-by: _lock
        CLUSTER.map_changed(
            self._map.version, len(self._map.servers), 0, n_partitions
        )

    # -- topology ----------------------------------------------------------
    @property
    def n_partitions(self) -> int:
        with self._lock:
            return self._map.n_partitions

    @property
    def partition_map(self) -> PartitionMap:
        with self._lock:
            return self._map

    def add_server(self, address: str) -> int:
        """Grow the cluster: recompute the map over the widened live set
        (rendezvous hashing moves ~1/N of partitions to the newcomer).
        Returns how many partitions moved.

        LOG-BACKED partitions (servers started with --durable_dir,
        ISSUE 8) migrate their queued backlog: the old owner — alive, by
        definition of an ADD — drains each moved partition's retained
        unconsumed range to the new owner before this call returns, so
        mid-stream growth strands nothing (duplicates possible as ever:
        a frame popped for migration rides the windowed-put resend
        contract to the new owner). Memory-only partitions keep the
        PR 7 documented limit: frames already queued at the old owner
        are not migrated — add memory-only servers between runs."""
        with self._lock:
            if address in self._addresses:
                return 0
            self._addresses.append(address)
            new_map = self._map.recompute(
                [a for a in self._addresses if a not in self._dead]
            )
            moved = new_map.moved_from(self._map)
            old_owners = {p: self._map.assignments[p] for p in moved}
            n = self._apply_map(new_map)
        # migrate OUTSIDE the client lock: the drain is bounded network
        # work (_MIGRATE_DEADLINE_S per partition), and holding the lock
        # through it would stall every other op on this client AND
        # starve the group-heartbeat thread past its lease (a rebalance
        # storm, the exact failure the lease keepalive exists to avoid).
        # Concurrent ops already see the new map; migration only adds
        # the old owner's backlog on top.
        if old_owners:
            self._migrate_moved_partitions(old_owners)
        return n

    # per-partition wall-clock bound on one migration drain: add_server
    # is an admin op but it runs under the client lock — a full/slow new
    # owner defers the remainder through _resend_pending instead of
    # wedging every other op on this client
    _MIGRATE_DEADLINE_S = 20.0

    def _migrate_moved_partitions(self, old_owners: Dict[int, str]) -> None:
        """Drain each moved partition's queued backlog from its (alive)
        old owner into the new owner — log-backed queues only (the old
        owner's stats announce ``durable``); memory-only partitions keep
        the documented no-migration limit.

        Ack discipline (holes never): a batch popped from the old owner
        is implicitly ACKed there ONLY AFTER the new owner has
        acknowledged every frame of it (``flush_puts``) — a crash
        mid-migration leaves the batch unacked on the old owner, which
        redelivers it (duplicates possible, loss never). Frames that
        cannot be confirmed within the bounded window go to the
        standard deferred-resend queue and the old-owner copy stays
        unacked.

        Runs WITHOUT the cluster lock held (add_server releases it
        first — holding it through a bounded network drain would stall
        every other op and starve the heartbeat lease); per-op locking
        happens inside _with_failover and the explicit _resend_pending
        mutation."""
        for p, addr in sorted(old_owners.items()):
            host, _, port = addr.rpartition(":")
            try:
                old = TcpQueueClient(
                    host, int(port),
                    timeout_s=min(self._timeout_s, 10.0),
                    namespace=self.namespace,
                    queue_name=partition_queue_name(self.queue_name, p),
                    reconnect_tries=1, reconnect_base_s=0.1,
                    pool=self._pool,
                    codec=self._codec,  # backlog drains compressed too
                )
            except TransportClosed:
                continue  # old owner gone after all: nothing to drain
            migrated = 0
            confirmed = True
            deadline = time.monotonic() + self._MIGRATE_DEADLINE_S
            try:
                if not old.stats().get("durable"):
                    FLIGHT.record(
                        "cluster_migrate_skipped", partition=p,
                        reason="memory-only",
                    )
                    continue
                while time.monotonic() < deadline:
                    batch = old.get_batch(64, timeout=0.25)
                    if not batch:
                        break
                    sent_all = True
                    for i, item in enumerate(batch):
                        # new owner via the freshly applied map; windowed
                        # puts so the at-least-once resend contract rides
                        if not self._with_failover(
                            p,
                            lambda c, _i=item: c.put_pipelined(
                                _i, deadline=deadline
                            ),
                        ):
                            # window full at the bound: defer the rest
                            # through the standard resend machinery (the
                            # old-owner copies ALSO stay unacked — dupes
                            # possible, holes never)
                            with self._lock:
                                pending = self._resend_pending.setdefault(
                                    p, []
                                )
                                pending.extend(batch[i:])
                            sent_all = False
                            break
                    ok = sent_all and self._with_failover(
                        p, lambda c: c.flush_puts(deadline=deadline)
                    )
                    if not ok:
                        confirmed = False
                        break
                    migrated += len(batch)
                    # only NOW is the batch safe on the new owner: the
                    # implicit ack may advance the old owner's floor
                    old.size()
            except TransportClosed:
                confirmed = False  # partial drain: old owner redelivers
            finally:
                if confirmed:
                    try:
                        old.disconnect()  # BYE: acks the final delivery
                    except Exception:  # noqa: BLE001 — already closing
                        _close_quietly(old)
                else:
                    # NEVER send BYE here: it would ack a delivery the
                    # new owner has not confirmed
                    _close_quietly(old)
                    FLIGHT.record(
                        "cluster_migrate_deferred", partition=p,
                        migrated=migrated,
                    )
            if migrated:
                CLUSTER.resent(0, migrated)
                FLIGHT.record(
                    "cluster_partition_migrated", partition=p,
                    frames=migrated, from_server=addr,
                )

    def _apply_map(self, new_map: PartitionMap) -> int:
        """Swap in a recomputed map; drop connections of moved
        partitions and queue their producer-side resend state."""
        # guarded-by-caller: _lock
        moved = new_map.moved_from(self._map)
        self._map = new_map
        for p in moved:
            old = self._clients.pop(p, None)
            tail: List[Any] = []
            if old is not None:
                try:
                    tail = old.unacked_puts()
                except Exception:  # noqa: BLE001 — the old server is gone
                    tail = []
                _close_quietly(old)
            pending = self._resend_pending.setdefault(p, [])
            pending_ids = {id(y) for y in pending}
            retained = list(self._retained.get(p, ()))
            seen = {id(x) for x in retained}
            resend = retained + [x for x in tail if id(x) not in seen]
            CLUSTER.resent(len(retained), len(resend) - len(retained))
            for x in resend:
                if id(x) not in pending_ids:
                    pending.append(x)
                    pending_ids.add(id(x))
        CLUSTER.map_changed(
            new_map.version, len(new_map.servers), len(self._dead),
            new_map.n_partitions, len(moved),
        )
        if moved:
            FLIGHT.record(
                "cluster_reassign", version=new_map.version,
                partitions=len(moved), live=len(new_map.servers),
            )
        return len(moved)

    def _server_alive(self, addr: str) -> bool:
        host, _, port = addr.rpartition(":")
        try:
            s = socket.create_connection(
                (host, int(port)), timeout=_PROBE_CONNECT_TIMEOUT_S
            )
            s.close()
            return True
        except OSError:
            return False

    def _failover(self, addr: str) -> bool:
        """A partition op on ``addr`` saw TransportClosed. True when the
        server is actually DEAD and its partitions were reassigned
        (retry the op on the new owner); False when the server is alive
        (graceful close — a protocol answer, not an outage)."""
        # guarded-by-caller: _lock
        if addr not in self._map.servers:
            return True  # a concurrent failover already handled it
        if self._server_alive(addr):
            return False
        # second opinion after a short beat: the dead verdict is
        # PERMANENT for this client's lifetime (deaths are a per-client
        # decision — restart clients to re-admit a recovered server),
        # so one dial racing a supervisor restart must not split the
        # producer's and consumer's maps for good
        self._idle.wait(0.25)
        if self._server_alive(addr):
            return False
        self._dead.add(addr)
        survivors = [s for s in self._map.servers if s != addr]
        if self._coord_addr == addr:
            if self._coord is not None:
                _close_quietly(self._coord)
            self._coord, self._coord_addr = None, None
        if not survivors:
            raise TransportClosed(
                f"every cluster server is dead (last: {addr})"
            )
        FLIGHT.record("cluster_server_dead", server=addr)
        new_map = self._map.recompute(survivors)
        moved = new_map.moved_from(self._map)
        self._apply_map(new_map)
        # a DEATH-forced move lands on the rendezvous runner-up — the
        # very server holding the partition's replica log when the
        # cluster replicates: promote before first touch
        self._promote_pending.update(moved)
        return True

    # -- per-partition plumbing -------------------------------------------
    def _client(self, p: int) -> TcpQueueClient:
        # guarded-by-caller: _lock
        c = self._clients.get(p)
        if c is None:
            addr = self._map.assignments[p]
            host, _, port = addr.rpartition(":")
            qname = partition_queue_name(self.queue_name, p)
            promote = p in self._promote_pending
            if promote:
                # failover landing: dial WITHOUT the binding, promote
                # the replica log ('Y') so OPEN mounts the replicated
                # backlog, THEN bind. An old server without the opcode
                # answers protocol-error — degrade to a plain open
                # (the partition starts empty there, as before ISSUE 11)
                c = TcpQueueClient(
                    host, int(port),
                    timeout_s=self._timeout_s,
                    maxsize=self._maxsize,
                    reconnect_tries=self._reconnect_tries,
                    reconnect_base_s=self._reconnect_base_s,
                    pool=self._pool,
                    put_window=self._put_window,
                    codec=self._codec,
                    tenant=self._tenant,
                    tenant_weight=self._tenant_weight,
                )
                rng = None
                try:
                    try:
                        rng = c.promote(self.namespace, qname)
                    except TransportClosed:
                        raise  # dead server, NOT a protocol answer
                    except RuntimeError:
                        pass  # pre-replication server: plain failover
                    CLUSTER.promoted(served=rng is not None)
                    FLIGHT.record(
                        "replica_promote", partition=p, server=addr,
                        served=rng is not None,
                        **(rng or {}),
                    )
                    c.open(self.namespace, qname, self._maxsize)
                except TransportClosed:
                    # the new owner died mid-promotion: drop the
                    # half-built client (pending stays set — the NEXT
                    # owner gets its promote) and let failover run
                    _close_quietly(c)
                    raise
                self._promote_pending.discard(p)
            else:
                c = TcpQueueClient(
                    host, int(port),
                    timeout_s=self._timeout_s,
                    namespace=self.namespace,
                    queue_name=qname,
                    maxsize=self._maxsize,
                    reconnect_tries=self._reconnect_tries,
                    reconnect_base_s=self._reconnect_base_s,
                    pool=self._pool,
                    put_window=self._put_window,
                    codec=self._codec,
                    tenant=self._tenant,
                    tenant_weight=self._tenant_weight,
                )
            self._clients[p] = c
        return c  # deferred resend flushes in _with_failover, once per op

    # how long one failover-resend attempt may block per partition op:
    # a FULL new-owner queue must not wedge the caller past its own
    # deadline (the remainder stays queued and flushes on later ops)
    _RESEND_SLICE_S = 2.0

    def _flush_pending(self, p: int, c: TcpQueueClient) -> None:
        """Bounded cross-server resend: ship queued retained/tail frames
        to the partition's (new) owner, at most ``_RESEND_SLICE_S`` of
        blocking per call — backpressure from a full destination queue
        defers the remainder to the next op on this partition instead of
        wedging the caller indefinitely (holes never: nothing is dropped,
        only deferred; duplicates possible as ever)."""
        # guarded-by-caller: _lock
        pending = self._resend_pending.get(p)
        if not pending:
            return
        deadline = time.monotonic() + self._RESEND_SLICE_S
        try:
            while pending and c.put_pipelined(pending[0], deadline=deadline):
                pending.pop(0)
        except TransportClosed:
            # this owner died too: the next failover re-queues the tail
            raise
        finally:
            if not pending:
                self._resend_pending.pop(p, None)
                FLIGHT.record("cluster_resend_flushed", partition=p)
            else:
                FLIGHT.record(
                    "cluster_resend_deferred", partition=p, left=len(pending)
                )

    def _with_failover(self, p: int, fn):
        """Run ``fn(partition client)``; when the owning server is dead
        for good, reassign and retry on the new owner — bounded by the
        server count (cascading deaths converge or raise)."""
        with self._lock:
            for _ in range(len(self._addresses) + 1):
                addr = self._map.assignments[p]
                try:
                    c = self._client(p)
                    self._flush_pending(p, c)  # deferred resend remainder
                    return fn(c)
                except TransportClosed:
                    if not self._failover(addr):
                        raise
            raise TransportClosed(
                f"partition {p} unreachable after exhausting failovers"
            )

    # -- producer surface --------------------------------------------------
    def _next_partition(self, item: Any) -> int:
        # guarded-by-caller: _lock
        if self._partition_key is not None:
            return int(self._partition_key(item)) % self._map.n_partitions
        p = self._rr % self._map.n_partitions
        self._rr += 1
        return p

    def _remember(self, p: int, item: Any) -> None:
        # guarded-by-caller: _lock
        if self._retain <= 0:
            return
        d = self._retained.get(p)
        if d is None:
            d = self._retained[p] = deque(maxlen=self._retain)
        d.append(item)

    def put(self, item: Any, deadline: Optional[float] = None) -> bool:
        if is_eos(item):
            return self._broadcast_eos(item, deadline)
        with self._lock:
            p = self._next_partition(item)
        ok = self._with_failover(p, lambda c: c.put(item, deadline))
        if ok:
            with self._lock:
                self._remember(p, item)
        return ok

    def put_wait(
        self, item: Any, timeout: Optional[float] = None, poll_s: float = 0.001
    ) -> bool:
        if is_eos(item):
            deadline = None if timeout is None else time.monotonic() + timeout
            return self._broadcast_eos(item, deadline)
        with self._lock:
            p = self._next_partition(item)
        ok = self._with_failover(p, lambda c: c.put_wait(item, timeout, poll_s))
        if ok:
            with self._lock:
                self._remember(p, item)
        return ok

    def put_pipelined(self, item: Any, deadline: Optional[float] = None) -> bool:
        """Windowed pipelined put routed to the item's partition: the
        PR 5 per-connection contract per partition, plus the
        cross-server resend on owner death (module docstring)."""
        if is_eos(item):
            return self._broadcast_eos(item, deadline)
        with self._lock:
            p = self._next_partition(item)
        ok = self._with_failover(p, lambda c: c.put_pipelined(item, deadline))
        if ok:
            with self._lock:
                self._remember(p, item)
        return ok

    def put_batch(self, items: List[Any]) -> int:
        accepted = 0
        for item in items:
            if not self.put(item):
                break
            accepted += 1
        return accepted

    def flush_puts(self, deadline: Optional[float] = None) -> bool:
        """Every partition's windowed tail acknowledged (the durability
        point before EOS) — failing over mid-flush resends and retries."""
        ok = True
        with self._lock:
            parts = sorted(set(self._clients) | set(self._resend_pending))
        for p in parts:
            # a deferred failover-resend remainder counts as unflushed:
            # durability (EOS, shutdown) must not be declared while
            # retained frames still wait for queue space on a new owner
            ok = self._with_failover(
                p,
                lambda c, _p=p: (
                    not self._resend_pending.get(_p) and c.flush_puts(deadline)
                ),
            ) and ok
        return ok

    def _broadcast_eos(self, eos: EndOfStream, deadline: Optional[float]) -> bool:
        """EOS fans out to EVERY partition (each partition's consumers
        tally it independently). The windowed tails flush first so the
        marker follows all data on every wire. All-or-False: a False
        return means retry the whole broadcast — duplicate markers are
        idempotent per producer rank, so re-broadcast is safe."""
        if not self.flush_puts(deadline):
            return False
        with self._lock:
            n_partitions = self._map.n_partitions
        for p in range(n_partitions):
            while True:
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    return False
                slice_s = 2.0 if remaining is None else min(2.0, remaining)

                def _put_eos(c, _p=p, _slice=slice_s):
                    # the marker must FOLLOW every frame on this
                    # partition's wire: while a failover-resend
                    # remainder is deferred, putting the EOS now would
                    # let the tally complete ahead of redelivered
                    # frames (readers stop at EOS — stranded data)
                    if self._resend_pending.get(_p):
                        return False
                    return c.put_wait(eos, timeout=_slice)

                if self._with_failover(p, _put_eos):
                    # EOS markers ride the retention buffer like frames:
                    # a server that dies AFTER acking the broadcast must
                    # not take its partitions' end-of-stream with it (the
                    # resend duplicates are idempotent per producer rank)
                    with self._lock:
                        self._remember(p, eos)
                    break
        return True

    # -- consumer surface --------------------------------------------------
    def replay_open(self, from_offset=None, group: str = "replay") -> "ClusterClient":
        """Durable clusters: switch the drain surface to NON-destructive
        replay of every assigned partition's retained segment-log range
        under ``group`` — live consumers are undisturbed, progress
        commits per partition at the connections' implicit-ACK points.
        ``from_offset``: ``"begin"`` / ``"resume"`` / per-partition
        offset int (each partition's log has its own offset space)."""
        with self._lock:
            self._replay = (from_offset, group)
            self._streaming = False  # replay is pull-mode by design
        return self

    def stream_open(self, window: int = 0) -> "ClusterClient":
        """Switch the drain surface to merged server-push streams: each
        assigned partition's connection subscribes (lazily, on first
        drain) with its own credit window — per-partition flow control
        composes, total client memory is window x assigned partitions."""
        with self._lock:
            self._streaming = True
            if window:
                self._stream_window = window
        return self

    def _ensure_joined(self) -> None:
        # guarded-by-caller: _lock
        if self._session is not None and not self._joined:
            # join FIRST, flag after: a transient coordinator outage on
            # the first drain call must leave this branch re-entrant (a
            # raised TransportClosed here retries on the next call), not
            # permanently skip the keepalive thread below
            self._session.join_group()
            self._joined = True
            # nothing held yet: the initial assignment needs no apply
            self._applied_gen = self._session.generation
            CLUSTER.rebalanced(self._session.generation)
            # lease keepalive off the drain path: a consumer spending
            # longer than the session timeout on downstream work (a
            # device step, a checkpoint write) between drains must NOT
            # expire and trigger a group-wide rebalance storm. The beat
            # runs WITHOUT the cluster lock (GroupSession serializes its
            # own state; the wire exchange happens outside both locks),
            # so a coordinator round trip never stalls the data path.
            # The thread only BEATS; rebalances still apply on the drain
            # loop (generation comparison), so partition ownership
            # changes exactly where frames are read. Lease liveness is
            # PROCESS liveness: a wedged-but-alive consumer keeps its
            # partitions (the stall detector's jurisdiction, as ever).
            session = self._session
            self._hb_stop = threading.Event()

            def _beat():
                while not self._hb_stop.wait(self._session_hb_s):
                    try:
                        session.maybe_heartbeat()
                    except TransportClosed:
                        continue  # drain-path rpc failover handles it
                    except Exception:  # noqa: BLE001 — keepalive must survive
                        continue

            self._hb_thread = threading.Thread(
                target=_beat, daemon=True, name="cluster-heartbeat"
            )
            self._hb_thread.start()

    def _assigned(self) -> List[int]:
        # guarded-by-caller: _lock
        if self._session is not None:
            return list(self._session.assigned())
        return list(range(self._map.n_partitions))

    def _active(self) -> List[int]:
        # guarded-by-caller: _lock
        drained = set(self._drained)
        if self._session is not None:
            drained |= set(self._session.drained)
        return [p for p in self._assigned() if p not in drained]

    def _complete(self) -> bool:
        # guarded-by-caller: _lock
        if self._session is not None:
            return self._session.all_drained()
        return len(self._drained) >= self._map.n_partitions

    def _maybe_rebalance(self) -> None:
        # guarded-by-caller: _lock
        if self._session is None:
            return
        self._ensure_joined()
        self._session.maybe_heartbeat()
        # compare against the APPLIED generation, not the heartbeat's
        # return value: a rebalance can also surface through a fenced
        # commit's embedded rejoin (any rpc that absorbs state) — the
        # next drain pass must still release revoked partitions
        if self._session.generation != self._applied_gen:
            self._apply_assignment()
        self._retry_drain_commits()

    def _retry_drain_commits(self) -> None:
        """Re-commit partitions whose drained-commit was fenced: the
        fence deferred the commit to the new generation, it did not
        erase the drain — without the retry no member would ever commit
        (the markers are already consumed) and the group EOS would
        never fire."""
        # guarded-by-caller: _lock
        for p in sorted(self._commit_retry):
            if p not in set(self._session.assigned()):
                continue  # revoked: _apply_assignment re-seeded markers
            if self._session.commit_drained(p):
                self._commit_retry.discard(p)
            if self._session.generation != self._applied_gen:
                self._apply_assignment()

    def _apply_assignment(self) -> None:
        """The generation moved: release revoked partitions (clean
        disconnect — consumed frames stay acked, pushed-but-unconsumed
        frames requeue at head for the new owner) and re-seed any
        partially observed EOS markers so the new owner's tally can
        still complete."""
        # guarded-by-caller: _lock
        assigned = set(self._session.assigned())
        revoked = self._held - assigned
        for p in sorted(revoked):
            c = self._clients.pop(p, None)
            tally = self._tallies.pop(p, None)
            if tally is not None and c is not None:
                # re-seed the markers this member consumed, through the
                # RECOVERY path (timed retries against a full queue — a
                # plain put's False would silently drop drain progress
                # and the new owner's tally could never complete)
                from psana_ray_tpu.transport.recovery import return_to_queue

                try:
                    return_to_queue(
                        c, tally.markers(), timeout_s=10.0,
                        what="revoked-partition EOS marker",
                    )
                except TransportClosed:
                    pass
            if c is not None:
                try:
                    c.disconnect()
                except Exception:  # noqa: BLE001 — revocation is best-effort
                    _close_quietly(c)
            self._held.discard(p)
            # the new owner re-tallies from the re-seeded markers; any
            # commit THIS member still owed for p is moot (if the group
            # already has p committed, it stays committed server-side)
            self._drained.discard(p)
            self._commit_retry.discard(p)
        self._applied_gen = self._session.generation
        CLUSTER.rebalanced(self._session.generation)
        FLIGHT.record(
            "cluster_rebalance",
            generation=self._session.generation,
            assigned=len(assigned), revoked=len(revoked),
        )

    def _pop(self, p: int, n: int, timeout: float) -> List[Any]:
        def _do(c: TcpQueueClient):
            with self._lock:
                self._held.add(p)
                replay = self._replay
            if replay is not None and c._replay_args is None:
                c.replay_open(replay[0], group=replay[1])
            if self._streaming:
                if c._stream is None:
                    c.stream_open(self._stream_window)
                return c.get_batch_stream(n, timeout)
            return c.get_batch(n, timeout=timeout)

        return self._with_failover(p, _do)

    def _sift(self, p: int, items: List[Any], out: List[Any]) -> None:
        """Frames pass through; EOS markers feed the partition tally and
        never surface (the synthesized cluster EOS is the only one the
        caller ever sees)."""
        for item in items:
            if not is_eos(item):
                out.append(item)
                continue
            with self._lock:
                tally = self._tallies.setdefault(p, EosTally())
                done = tally.process(item)
            if done:
                self._partition_drained(p, tally)

    def _partition_drained(self, p: int, tally: EosTally) -> None:
        with self._lock:
            if p in self._drained:
                return
            self._drained.add(p)
        CLUSTER.drained()
        FLIGHT.record("cluster_partition_drained", partition=p)
        # return held sibling copies to the partition (competing
        # consumers outside group mode still need them), then stop
        # reading it — a drained partition never re-earns attention
        try:
            self._with_failover(
                p, lambda c: tally.flush_duplicates(c, final=True)
            )
        except TransportClosed:
            pass
        with self._lock:
            session = self._session
        offset = None
        if session is not None:
            # durable partitions: the drained commit CARRIES the
            # partition's committed log offset, so the coordinator's
            # persisted group state records how far consumption provably
            # reached (recovered on coordinator restart). Durability is
            # fixed per server, so a memory-only server is probed ONCE,
            # not once per drained partition.
            with self._lock:
                addr = self._map.assignments.get(p)
                known = self._durable_servers.get(addr)
            if known is not False:
                try:
                    st = self._with_failover(p, lambda c: c.stats())
                    durable = bool(st.get("durable"))
                    with self._lock:
                        if addr is not None:
                            self._durable_servers[addr] = durable
                    if durable:
                        offset = st.get("committed_offset")
                except TransportClosed:
                    offset = None
        if session is not None and not session.commit_drained(p, offset=offset):
            # FENCED: the commit is deferred to the new generation, not
            # dropped — the markers are already consumed, so if nobody
            # retries, no member can ever commit p and the group EOS
            # never fires. The drain loop retries while p stays ours;
            # _apply_assignment re-seeds the markers if it was revoked.
            with self._lock:
                self._commit_retry.add(p)

    def _final_eos(self, out: List[Any]) -> List[Any]:
        with self._lock:
            if self._eos_emitted:
                return out
            self._eos_emitted = True
        CLUSTER.eos_emitted()
        FLIGHT.record("cluster_eos", queue=self.queue_name)
        out.append(EndOfStream(producer_rank=0, shards_done=1, total_shards=1))
        return out

    def get_batch_stream(
        self, max_items: int, timeout: Optional[float] = None
    ) -> List[Any]:
        """THE merged drain: sweep every active partition for buffered
        frames (no blocking), then block one caller-bounded slice on a
        rotating partition. Returns [] on timeout; returns the one
        synthesized EOS (once) after every partition drains."""
        with self._lock:
            self._streaming = True
        return self._merge_drain(max_items, timeout)

    def get_batch(
        self,
        max_items: int,
        timeout: Optional[float] = None,
        poll_s: float = 0.001,
    ) -> List[Any]:
        return self._merge_drain(max_items, timeout)

    def _merge_drain(self, max_items: int, timeout: Optional[float]) -> List[Any]:
        deadline = None if timeout is None else time.monotonic() + timeout
        out: List[Any] = []
        max_items = int(max_items)
        first_sweep = True
        while True:
            with self._lock:
                self._maybe_rebalance()
                active = self._active()
                complete = self._complete()
                scan = self._scan
                streaming = self._streaming
            if complete:
                return self._final_eos(out)
            # Sweep every partition for already-available frames. In
            # streaming mode this costs NO round trips (it drains the
            # local push buffers) so it runs every iteration; in pull
            # mode each zero-timeout probe is a full request/response,
            # so only the FIRST pass sweeps — after an empty sweep the
            # rotating bounded wait below carries the waiting (the 'D'
            # round-trip-economy contract, kept across the cluster:
            # ~4 requests per idle second, not hundreds)
            if active and (streaming or first_sweep):
                for i in range(len(active)):
                    p = active[(scan + i) % len(active)]
                    self._sift(p, self._pop(p, max_items - len(out), 0.0), out)
                    if len(out) >= max_items:
                        return out
            first_sweep = False
            if out:
                return out
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                return []
            if not active:
                # a member with nothing assigned (more members than
                # partitions, or waiting on the group-wide drain):
                # bounded interruptible pause, then re-check
                self._idle.wait(
                    _MERGE_SLICE_S if remaining is None
                    else min(_MERGE_SLICE_S, remaining)
                )
                continue
            # block ONE slice on the rotating partition, then loop
            with self._lock:
                self._scan = scan + 1
            p = active[scan % len(active)]
            cap = _MERGE_SLICE_S if streaming else _PULL_SLICE_S
            slice_s = cap if remaining is None else min(cap, remaining)
            self._sift(p, self._pop(p, max_items - len(out), slice_s), out)
            if out:
                return out

    def get(self, deadline: Optional[float] = None) -> Any:
        batch = self._merge_drain(1, 0.0)
        return batch[0] if batch else EMPTY

    def get_wait(self, timeout: Optional[float] = None, poll_s: float = 0.001) -> Any:
        batch = self._merge_drain(1, timeout)
        return batch[0] if batch else EMPTY

    # -- probes ------------------------------------------------------------
    def size(self, deadline: Optional[float] = None) -> int:
        """Total queued across every partition (best-effort: partitions
        on unreachable servers count 0 rather than blocking the probe)."""
        total = 0
        depths: Dict[str, int] = {}
        with self._lock:
            amap = dict(self._map.assignments)
        for p, addr in amap.items():
            try:
                n = self._with_failover(p, lambda c: c.size(deadline))
            except TransportClosed:
                continue
            total += n
            depths[addr] = depths.get(addr, 0) + n
        CLUSTER.observe_depths(depths)
        return total

    def stats(self, deadline: Optional[float] = None) -> dict:
        depth = self.size(deadline)
        with self._lock:
            m = self._map
            return {
                "cluster": True,
                "depth": depth,
                "map_version": m.version,
                "n_partitions": m.n_partitions,
                "servers": list(m.servers),
                "dead_servers": sorted(self._dead),
                "drained_partitions": sorted(self._drained),
                "telemetry": CLUSTER.stats(),
            }

    def anchor(self, deadline: Optional[float] = None) -> dict:
        """Clock anchor against partition 0's owner (trace alignment —
        single-server parity; per-server skew is below the RTT bound on
        one LAN, which is the deployment a cluster targets)."""
        return self._with_failover(0, lambda c: c.anchor(deadline))

    # -- group RPC plumbing ------------------------------------------------
    def _rpc(self, payload: dict) -> dict:
        """Coordinator RPC with failover: the coordinator is the first
        LIVE server of the configured list; a dead coordinator fails
        over to the next (whose empty registry makes members rejoin —
        generations restart together, so fencing stays consistent)."""
        last: Optional[BaseException] = None
        for _ in range(len(self._addresses) + 1):
            with self._lock:
                live = [a for a in self._addresses if a not in self._dead]
                addr = coordinator_address(live)
                c = self._coord if self._coord_addr == addr else None
            if c is None:
                # dial OUTSIDE the cluster lock, with a control-plane
                # timeout: a blackholed coordinator must cost the
                # heartbeat thread a few seconds, never freeze the data
                # path behind the lock for the full data-plane envelope
                host, _, port = addr.rpartition(":")
                try:
                    nc = TcpQueueClient(
                        host, int(port),
                        timeout_s=min(self._timeout_s, 5.0),
                        reconnect_tries=self._reconnect_tries,
                        reconnect_base_s=self._reconnect_base_s,
                    )
                except TransportClosed as e:
                    last = e
                    with self._lock:
                        self._failover(addr)
                    continue
                with self._lock:
                    if self._coord is not None and self._coord_addr == addr:
                        _close_quietly(nc)  # a concurrent rpc won the dial
                        c = self._coord
                    else:
                        if self._coord is not None:
                            _close_quietly(self._coord)
                        self._coord, self._coord_addr = nc, addr
                        c = nc
            try:
                return c.cluster_rpc(payload)
            except TransportClosed as e:
                last = e
                with self._lock:
                    if not self._failover(addr):
                        raise
        raise TransportClosed(
            f"no live coordinator among {self._addresses}"
        ) from last

    # -- lifecycle ---------------------------------------------------------
    def disconnect(self):
        with self._lock:
            # snapshot under the lock (_ensure_joined installs these
            # there); the set/join runs outside it so the heartbeat
            # thread can finish its in-flight RPC without deadlocking
            hb_stop, hb_thread = self._hb_stop, self._hb_thread
        if hb_stop is not None:
            hb_stop.set()
        if hb_thread is not None:
            hb_thread.join(timeout=2.0)
        with self._lock:
            session, self._session = self._session, None
            clients, self._clients = dict(self._clients), {}
            coord, self._coord = self._coord, None
            tallies, self._tallies = dict(self._tallies), {}
            joined = self._joined
        if session is not None and joined:
            try:
                session.leave()
            except Exception:  # noqa: BLE001 — the lease would expire anyway
                pass
        for p, c in sorted(clients.items()):
            tally = tallies.get(p)
            if tally is not None:
                try:
                    tally.flush_duplicates(c, final=True)
                except Exception:  # noqa: BLE001 — already closing
                    pass
            try:
                c.disconnect()
            except Exception:  # noqa: BLE001 — already closing
                _close_quietly(c)
        if coord is not None:
            try:
                coord.disconnect()
            except Exception:  # noqa: BLE001 — already closing
                _close_quietly(coord)

    def close_remote(self):
        """Close every partition queue (fault-injection / teardown)."""
        with self._lock:
            parts = list(range(self._map.n_partitions))
        for p in parts:
            try:
                self._with_failover(p, lambda c: c.close_remote())
            except TransportClosed:
                continue


def _close_quietly(c: TcpQueueClient) -> None:
    """Drop a client whose server is gone WITHOUT the disconnect
    pleasantries (BYE / ack draining would wait on a dead peer)."""
    sock = getattr(c, "_sock", None)
    if sock is not None:
        try:
            sock.close()
        except OSError:
            pass
    side = getattr(c, "_side", None)
    if side is not None:
        _close_quietly(side)
