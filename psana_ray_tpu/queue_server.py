"""Standalone queue server — the ``ray start --head`` of this framework.

The reference's runbook starts a Ray head node whose GCS hosts detached
queue actors by (namespace, name) (``README.md:13-18``,
``shared_queue.py:33-38``); producers and consumers on other nodes join it
by address. Here the equivalent service is one process hosting *many named
queues* over TCP (:mod:`transport.tcp` OPEN opcode): clients reach it with
``--address tcp://host:port`` and their configured (namespace, queue_name)
get-or-creates the queue server-side — one server serves every detector's
stream. Named queues are detached: they outlive the clients that created
them, until this process stops.

``--workers N`` (ISSUE 17) breaks the single-core ceiling: N forked
evloop processes share the ONE listening port via ``SO_REUSEPORT``, each
named queue rendezvous-pinned to exactly one worker, connections shipped
between workers over ``SCM_RIGHTS`` when the kernel's connection
sharding disagrees with the queue pinning. The client contract is
unchanged — one address, same ordering, same redelivery.

Optionally backed by a shared-memory ring (``--shm``) so local processes on
the serving host can bypass TCP entirely while remote ones fan in/out over
the network.

Teardown parity (``ray stop``, reference ``README.md:37-40``): SIGINT/SIGTERM
closes the queue, unblocking all clients with a dead-transport error.
"""

from __future__ import annotations

import argparse
import logging
import signal
import threading
import time

logger = logging.getLogger(__name__)


def main(argv=None):
    p = argparse.ArgumentParser(prog="psana-ray-tpu-queue")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=6379, help="reference head-node port")
    p.add_argument("--queue_size", type=int, default=100)
    p.add_argument(
        "--workers",
        type=int,
        default=1,
        help=(
            "fork this many evloop server processes sharing ONE port via "
            "SO_REUSEPORT (ISSUE 17): each named queue lives on exactly "
            "one worker (rendezvous-pinned, respawn-stable), connections "
            "migrate between workers over SCM_RIGHTS when the kernel's "
            "accept sharding disagrees with the pinning, and a crashed "
            "worker is respawned with its queues recovered from the "
            "durable log. Clients see one address and the unchanged "
            "contract. Incompatible with --shm and --replicate_peers"
        ),
    )
    p.add_argument(
        "--shm",
        default=None,
        metavar="NAME",
        help=(
            "back queues with shm rings: the default queue uses ring NAME "
            "(local procs attach via shm://NAME); named queues use ring "
            "<namespace>__<queue_name> (local procs attach via shm:// "
            "with matching config)"
        ),
    )
    p.add_argument(
        "--durable_dir",
        default=None,
        metavar="DIR",
        help=(
            "back every queue with a recycled mmap'd segment log under "
            "DIR (ISSUE 8): queued frames survive kill -9/restart (boot "
            "re-exposes everything above the committed offset, repairing "
            "a torn tail by CRC truncation), depth beyond RAM spills to "
            "the log, consumers can --replay the retained range, and the "
            "consumer-group coordinator state is persisted too. "
            "Incompatible with --shm"
        ),
    )
    from psana_ray_tpu.config import DurabilityConfig

    # ONE source of truth for the durability knobs: the dataclass the
    # library surface documents is also where the CLI defaults live
    dur_defaults = DurabilityConfig()
    p.add_argument(
        "--segment_bytes", type=int, default=dur_defaults.segment_bytes,
        help="pre-allocated size of one segment file (recycled, never "
        "reallocated; must fit the largest record)",
    )
    p.add_argument(
        "--retain_segments", type=int, default=dur_defaults.retain_segments,
        help="fully-consumed segments kept for --replay before being "
        "recycled; unconsumed records are NEVER recycled regardless",
    )
    p.add_argument(
        "--fsync", choices=("none", "batch", "always"),
        default=dur_defaults.fsync,
        help="segment-log fsync policy: 'none' survives process death "
        "(page cache) but a machine crash may lose the tail; 'batch' "
        "fsyncs every --fsync_batch_n appends + on roll/commit; "
        "'always' fsyncs per append (one disk flush a frame)",
    )
    p.add_argument(
        "--fsync_batch_n", type=int, default=dur_defaults.fsync_batch_n,
        help="appends per fsync under --fsync batch",
    )
    p.add_argument(
        "--ram_items", type=int, default=dur_defaults.ram_items,
        help="RAM-resident records per durable queue before spilling "
        "delivery to log reads (0 = the queue's --queue_size)",
    )
    p.add_argument(
        "--replicate_peers",
        default=None,
        metavar="HOST:PORT,...",
        help=(
            "chain-replicate durable partition logs across this static "
            "server list (ISSUE 11): each durable queue this server "
            "owns ships its segment log to the next server in the "
            "partition's rendezvous ranking, producer acks wait for "
            "the follower (replicated ack floor), and the consumer-"
            "group coordinator snapshot replicates under a leader "
            "lease. Every server of the cluster should be started "
            "with the SAME list. Requires --durable_dir and "
            "--advertise"
        ),
    )
    p.add_argument(
        "--advertise",
        default=None,
        metavar="HOST:PORT",
        help=(
            "this server's own address AS IT APPEARS in "
            "--replicate_peers (placement is computed from the peer "
            "list, so the spelling must match exactly)"
        ),
    )
    p.add_argument(
        "--replica_codec",
        default=None,
        help=(
            "wire codec for the replication links ('auto', a codec "
            "name, or unset for raw) — the segment log ships "
            "compressed exactly like any other negotiated link"
        ),
    )
    p.add_argument(
        "--port_file", default=None,
        help="write the bound port to this file once listening (harness "
        "support: lets a supervisor/test start with --port 0 and learn "
        "the port without parsing logs)",
    )
    p.add_argument(
        "--max_conns",
        type=int,
        default=0,
        help=(
            "admission control: refuse connections past this many with a "
            "clean protocol error instead of accepting unboundedly (an "
            "accept storm must not OOM the relay); 0 = unlimited"
        ),
    )
    p.add_argument(
        "--drain_s",
        type=float,
        default=10.0,
        help=(
            "graceful-shutdown window: on SIGINT/SIGTERM the server stops "
            "accepting PUTs but keeps serving GETs until every queue is "
            "empty or this many seconds pass, THEN closes (0 = abrupt)"
        ),
    )
    from psana_ray_tpu.obs import (
        add_history_args,
        add_metrics_args,
        add_profile_args,
        add_trace_args,
    )

    add_metrics_args(p)
    add_trace_args(p)
    add_history_args(p)
    add_profile_args(p)
    p.add_argument(
        "--stall_poll_s", type=float, default=1.0,
        help="queue-health poll interval for the stall detector "
        "(backpressure / consumer-stall / producer-idle warnings); "
        "0 = detector off",
    )
    p.add_argument(
        "--stall_full_s", type=float, default=5.0,
        help="warn 'backpressure' after a queue sits at maxsize this long",
    )
    p.add_argument(
        "--stall_idle_s", type=float, default=10.0,
        help="warn 'consumer_stall'/'producer_idle' after put/get "
        "counters freeze this long",
    )
    p.add_argument("--log_level", default="INFO")
    a = p.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, a.log_level.upper(), logging.INFO),
        format="%(asctime)s - %(levelname)s - %(message)s",
    )

    if a.durable_dir and a.shm:
        p.error("--durable_dir and --shm are mutually exclusive (the "
                "segment log backs in-process queues; shm rings have "
                "their own lifetime)")
    if a.replicate_peers and not (a.durable_dir and a.advertise):
        p.error("--replicate_peers requires --durable_dir (the segment "
                "log is what replicates) and --advertise (this server's "
                "own address in the peer list)")
    if a.replicate_peers:
        _peers = [s.strip() for s in a.replicate_peers.split(",") if s.strip()]
        if a.advertise not in _peers:
            # a spelling mismatch would silently disable all shipping
            # (placement can't find this server in the chain)
            p.error(f"--advertise {a.advertise!r} does not appear in "
                    f"--replicate_peers {_peers} — the spellings must "
                    f"match exactly or no queue will ever replicate")
    if a.workers > 1:
        import socket as _socket

        if not hasattr(_socket, "SO_REUSEPORT"):
            p.error("--workers needs SO_REUSEPORT, which this platform "
                    "does not expose — run a single worker")
        if a.shm:
            p.error("--workers is incompatible with --shm (shm rings "
                    "already give local processes multi-process access; "
                    "pick one data plane)")
        if a.replicate_peers:
            p.error("--workers is incompatible with --replicate_peers "
                    "(replica links bind queues directly to one serving "
                    "process; run replicated servers single-worker)")
        return _run_workers(a)
    return _serve(a)


def _run_workers(a) -> int:
    """The parent of a ``--workers N`` fleet: resolve the shared port,
    fork N workers (each builds its full server in :func:`_serve`),
    respawn the dead, forward shutdown. The parent itself serves
    nothing — it is pure supervision, and it forks BEFORE starting any
    thread so no lock is ever cloned mid-hold."""
    import os
    import tempfile

    from psana_ray_tpu.transport.splice import probe_report
    from psana_ray_tpu.transport.workers import (
        WorkerContext,
        WorkerSupervisor,
        resolve_port,
    )

    port = resolve_port(a.host, a.port)
    sock_dir = tempfile.mkdtemp(prefix="psana-workers-")

    def _worker_entry(worker_id):
        ctx = WorkerContext(worker_id, a.workers, sock_dir)
        _serve(a, worker_ctx=ctx, port=port)

    sup = WorkerSupervisor(a.workers, _worker_entry).start()
    if a.port_file:
        with open(a.port_file + ".tmp", "w") as f:
            f.write(str(port))
        os.replace(a.port_file + ".tmp", a.port_file)  # atomic: no torn read
    logger.info(
        "queue server: %d workers sharing %s:%d via SO_REUSEPORT "
        "(rendezvous-pinned queues, SCM_RIGHTS migration, respawn on "
        "death; kernel pass-through probe: %s) — clients use "
        "--address tcp://<host>:%d exactly as with one worker",
        a.workers, a.host, port, probe_report(), port,
    )

    done = threading.Event()

    def _stop(sig, frame):
        logger.info("signal %s — shutting down worker fleet", sig)
        done.set()

    signal.signal(signal.SIGINT, _stop)
    signal.signal(signal.SIGTERM, _stop)
    done.wait()
    # each worker runs its own graceful drain inside its SIGTERM handler
    sup.stop(timeout_s=a.drain_s + 10.0)
    return 0


def _serve(a, worker_ctx=None, port=None) -> int:
    """One full queue-server process: backing, TCP server, obs plane,
    signal-driven drain. With ``worker_ctx`` this is one
    worker of a ``--workers`` fleet: it reuseport-binds the shared
    port, owns only its rendezvous partitions, and tags its telemetry
    with the worker id."""
    from psana_ray_tpu.obs import MetricsRegistry, StallDetector, start_metrics_server
    from psana_ray_tpu.transport.ring import RingBuffer
    from psana_ray_tpu.transport.tcp import TcpQueueServer

    wid = worker_ctx.worker_id if worker_ctx is not None else None
    owns_default = worker_ctx is None or wid == worker_ctx.default_owner
    queue_factory = None
    group_store_path = None
    replication = None
    if a.durable_dir:
        import os

        from psana_ray_tpu.storage import DurableRingBuffer, SegmentLog

        os.makedirs(a.durable_dir, exist_ok=True)
        # per-worker coordinator state: a queue's consumer groups are
        # only ever touched by its owning worker (ops route there), so
        # per-worker files never race; keep --workers N stable across
        # restarts or group progress stays in the old owner's file
        group_store_path = os.path.join(
            a.durable_dir,
            "groups.json" if wid is None else f"groups-w{wid}.json",
        )

        def _durable_backing(ns, name, maxsize):
            # one log directory per named queue; the boot-time recovery
            # scan runs inside SegmentLog.__init__
            qdir = os.path.join(a.durable_dir, f"{ns}__{name}")
            log = SegmentLog(
                qdir,
                segment_bytes=a.segment_bytes,
                retain_segments=a.retain_segments,
                fsync=a.fsync,
                fsync_batch_n=a.fsync_batch_n,
                name=f"{ns}/{name}",
            )
            q = DurableRingBuffer(
                log, maxsize=maxsize, name=f"{ns}__{name}",
                ram_items=a.ram_items or None,
                # spill reads resolve lazily so the evloop can splice
                # the on-disk payload straight to the socket (ISSUE 17)
                lazy_spill=True,
            )
            depth = q.size()
            if depth:
                logger.info(
                    "durable queue (%s, %s): recovered %d unconsumed "
                    "record(s) from %s (committed offset %d%s)",
                    ns, name, depth, qdir, log.committed(""),
                    ", TORN TAIL repaired" if log.torn_tail_repaired else "",
                )
            return q

        queue_factory = _durable_backing
        if owns_default:
            backing = _durable_backing("default", "default", a.queue_size)
        else:
            # this worker never serves the default queue (ops on it
            # migrate to its owner); a plain ring satisfies the server
            # ctor without touching the owner's log directory
            backing = RingBuffer(a.queue_size)
        logger.info(
            "backing queues: segment logs under %s (segment_bytes=%d, "
            "retain=%d, fsync=%s)",
            a.durable_dir, a.segment_bytes, a.retain_segments, a.fsync,
        )
        if a.replicate_peers:
            from psana_ray_tpu.cluster.replication import ReplicationManager

            peers = [s.strip() for s in a.replicate_peers.split(",") if s.strip()]
            replication = ReplicationManager(
                a.durable_dir, peers, a.advertise,
                codec=a.replica_codec,
                segment_bytes=a.segment_bytes,
                retain_segments=a.retain_segments,
                fsync=a.fsync,
                fsync_batch_n=a.fsync_batch_n,
            )
            logger.info(
                "replication: chain over %s (advertise=%s, codec=%s) — "
                "owned durable queues ship to their rendezvous "
                "runner-up; producer acks ride the replicated floor",
                peers, a.advertise, a.replica_codec or "raw",
            )
    elif a.shm:
        from psana_ray_tpu.transport.shm_ring import ShmRingBuffer

        def _shm_backing(name, maxsize):
            try:
                return ShmRingBuffer.create(name, maxsize=maxsize)
            except RuntimeError:
                return ShmRingBuffer.attach(name, retries=1, interval_s=0.1)

        backing = _shm_backing(a.shm, a.queue_size)
        # named queues (OPEN opcode) get shm backings too, named with the
        # SAME <namespace>__<queue_name> derivation as transport/
        # addressing.shm_ring_name — so a local consumer using
        # `--address shm://` with matching config reads the very ring that
        # remote producers feed over TCP (no second copy, no TCP hop)
        def queue_factory(ns, name, maxsize):
            shm_name = f"{ns}__{name}"
            logger.info("named queue (%s, %s) -> shm ring %r", ns, name, shm_name)
            return _shm_backing(shm_name, maxsize)

        logger.info("backing queues: shm rings (default ring %r)", a.shm)
    else:
        backing = RingBuffer(a.queue_size)

    server = TcpQueueServer(
        backing, host=a.host, port=port if port is not None else a.port,
        maxsize=a.queue_size,
        queue_factory=queue_factory, max_conns=a.max_conns,
        group_store_path=group_store_path, replication=replication,
        reuseport=worker_ctx is not None, worker_ctx=worker_ctx,
    ).serve_background()
    if a.port_file and worker_ctx is None:  # fleet parent already wrote it
        with open(a.port_file + ".tmp", "w") as f:
            f.write(str(server.port))
        import os as _os

        _os.replace(a.port_file + ".tmp", a.port_file)  # atomic: no torn read
    if worker_ctx is not None:
        from psana_ray_tpu.transport.splice import probe_report

        logger.info(
            "worker %d/%d listening on %s:%d (splice: %s)",
            wid, worker_ctx.n_workers, a.host, server.port, probe_report(),
        )
    else:
        logger.info(
            "queue server listening on %s:%d (size=%d%s) — clients use "
            "--address tcp://<host>:%d, or start N of these and point "
            "clients at --cluster host:port,host:port (sharded queue "
            "service; the legacy thread-per-connection --server_mode was "
            "removed, the epoll event loop is THE server)",
            a.host, server.port, a.queue_size,
            f", max_conns={a.max_conns}" if a.max_conns else "",
            server.port,
        )

    # Observability: every queue (default + OPENed named ones) as a
    # registry source, the Prometheus endpoint over it, and the stall
    # detector watching the same dynamic population. All three are
    # zero-cost when their flags are off. The relay's recv-buffer pool
    # self-registers as the `bufpool` source (leases/hits/misses) with
    # payload-copy counters under `wire` — the zero-copy datapath's
    # steady state is visible on the same endpoint.
    MetricsRegistry.default().register("queue_server", server.stats_all)
    # a worker fleet staggers the scrape endpoints: worker i serves
    # --metrics_port + i (one process cannot answer for its siblings;
    # the federation collector aggregates per-worker series instead)
    metrics_port = a.metrics_port
    if metrics_port and wid is not None:
        metrics_port += wid
    metrics_server = start_metrics_server(metrics_port, host=a.metrics_host)
    # Time-series history (ISSUE 13): the bounded per-key snapshot ring
    # behind flight-dump tails and the federation collector's 'N'
    # metrics RPC (this server answers it regardless; the sampler adds
    # the local HISTORY dimension). One daemon thread, preallocated
    # rings, --history_interval 0 turns it off.
    from psana_ray_tpu.obs import configure_history_from_args, configure_profiling_from_args

    history = configure_history_from_args(a)
    # continuous profiler (ISSUE 16): bills the event loop's dispatch
    # pass to the "dispatch" stage; --profile_hz 0 = off. Workers spool
    # under distinct process names so prof_merge shows per-worker rows.
    profiler = configure_profiling_from_args(
        a, "queue_server" if wid is None else f"queue_server-w{wid}"
    )
    # Tracing (relay spans: queue_dwell/relay per sampled frame) and the
    # flight recorder (dump-on-stall/SIGUSR2/exception — the black box for
    # wedged runs) arm from the shared --trace_dir/--flight_dir flags.
    from psana_ray_tpu.obs import FLIGHT, configure_tracing_from_args

    configure_tracing_from_args(
        a, "queue_server" if wid is None else f"queue_server-w{wid}"
    )
    stall = None
    if a.stall_poll_s > 0:
        stall = StallDetector(
            poll_interval_s=a.stall_poll_s,
            full_threshold_s=a.stall_full_s,
            idle_threshold_s=a.stall_idle_s,
            # every stall event lands in the flight ring; when a dump dir
            # is armed the firing ALSO writes the postmortem black box
            # (events + metrics snapshot + all thread stacks)
            on_event=FLIGHT.on_stall,
        ).watch_provider(server.queues_by_name)
        MetricsRegistry.default().register("stalls", stall)
        stall.start()

    done = threading.Event()
    force = threading.Event()

    def _stop(sig, frame):
        if done.is_set():
            # second signal: the operator wants OUT now (double-Ctrl-C
            # convention) — abort the drain window
            logger.info("second signal %s — forcing immediate shutdown", sig)
            force.set()
            return
        logger.info("signal %s — shutting down queue server", sig)
        done.set()

    signal.signal(signal.SIGINT, _stop)
    signal.signal(signal.SIGTERM, _stop)
    done.wait()
    if a.drain_s > 0 and not force.is_set():
        # graceful drain: producers are refused (clean dead-queue exits),
        # consumers keep reading until the queues empty or the window ends
        server.begin_drain()
        start = time.monotonic()
        while time.monotonic() - start < a.drain_s and not force.is_set():
            if server.depth() == 0:
                logger.info("drained — all queues empty")
                break
            force.wait(0.2)
        else:
            logger.warning(
                "drain window ended with %d item(s) still queued", server.depth()
            )
    if stall is not None:
        stall.stop()
    if history is not None:
        history.stop()
    if metrics_server is not None:
        metrics_server.close()
    server.close_all()  # unblock ALL clients with TransportClosed (dead-queue parity)
    server.shutdown()
    for q in server.all_queues():
        log = getattr(q, "log", None)
        if log is not None:  # durable backings: flush + unmap segments
            log.close()
    if worker_ctx is not None:
        worker_ctx.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
