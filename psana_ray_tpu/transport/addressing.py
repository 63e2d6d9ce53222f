"""Address-scheme resolution: one queue-opening surface for every transport.

The reference rendezvouses through Ray's GCS: producers and consumers name
a queue and namespace, and the cluster resolves it (``shared_queue.py:35``,
``producer.py:56-67``, ``data_reader.py:20``). Here the address string
selects the transport and the (namespace, queue_name) pair still names the
queue within it:

- ``auto`` / ``local`` — in-process :class:`Registry` (tests, single-process
  pipelines, threads);
- ``shm://`` or ``shm://<name>`` — cross-process POSIX shared-memory ring on
  one host. With no explicit ``<name>``, the ring is named from
  ``<namespace>__<queue_name>`` so the producer CLI and DataReader
  rendezvous from config alone, exactly like the reference's named actors.
  The ring is *detached* (parity: ``shared_queue.py:35``): it outlives its
  creator until destroyed;
- ``tcp://host:port`` — cross-host queue server (see
  :mod:`psana_ray_tpu.queue_server`). The (namespace, queue_name) pair
  selects a *named queue on that server* (OPEN opcode): one server per
  cluster hosts every detector's queue, exactly like Ray's GCS hosts many
  named actors.
- ``cluster://host:port,host:port,...`` — a SHARDED queue service over N
  queue servers (:mod:`psana_ray_tpu.cluster`): the logical queue splits
  into ``config.cluster_partitions`` partitions placed by rendezvous
  hashing over the server list; the returned :class:`~psana_ray_tpu.
  cluster.client.ClusterClient` speaks the same transport contract, so
  everything downstream is unchanged. ``config.group`` enrolls a
  consumer in a named consumer group (disjoint partition assignment,
  rebalance on membership change, one aggregated EOS per group).

Producers open with ``role='producer'`` (get-or-create semantics, parity
``producer.py:42-48``); consumers with ``role='consumer'`` (resolve with
retry, parity ``producer.py:56-67``).
"""

from __future__ import annotations

from typing import Optional

from psana_ray_tpu.config import TransportConfig
from psana_ray_tpu.transport.registry import Registry, RendezvousTimeout


def shm_ring_name(config: TransportConfig, address: Optional[str] = None) -> str:
    """The shm object name for a config: explicit ``shm://<name>`` wins,
    else derived from (namespace, queue_name)."""
    address = address or config.address
    explicit = address[len("shm://"):] if address.startswith("shm://") else ""
    return explicit or f"{config.namespace}__{config.queue_name}"


def add_cluster_args(parser, consumer: bool = False) -> None:
    """The shared ``--cluster`` CLI surface (producer / consumer / sfx):
    pointing a CLI at a sharded queue service is an address-list change,
    nothing else."""
    parser.add_argument(
        "--cluster", default=None, metavar="HOST:PORT,HOST:PORT",
        help="queue-server cluster: shard the logical queue over these "
        "servers (overrides --address with cluster://...). The FIRST "
        "server doubles as the consumer-group coordinator. Every "
        "producer and consumer of one stream must pass the same list "
        "and --partitions",
    )
    parser.add_argument(
        "--partitions", type=int, default=8,
        help="partitions the logical queue shards into across the "
        "cluster (fixed for the life of a stream)",
    )
    if consumer:
        parser.add_argument(
            "--group", default="",
            help="consumer-group name: members share the stream with "
            "disjoint partition assignments, rebalancing on "
            "join/leave/death; empty = compete on all partitions",
        )
        parser.add_argument(
            "--member_id", default="",
            help="stable member id within --group (default: random per "
            "process — fine unless you want sticky assignment)",
        )


def apply_cluster_args(config: TransportConfig, args) -> TransportConfig:
    """Fold the ``--cluster`` flags into a TransportConfig (no-op when
    the flag is absent)."""
    import dataclasses

    if not getattr(args, "cluster", None):
        return config
    return dataclasses.replace(
        config,
        address=f"cluster://{args.cluster}",
        cluster_partitions=args.partitions,
        group=getattr(args, "group", "") or "",
        member_id=getattr(args, "member_id", "") or "",
    )


def add_wire_args(parser, producer: bool = False) -> None:
    """The shared wire-compression CLI surface (ISSUE 9)."""
    parser.add_argument(
        "--wire_codec", default="", metavar="auto|none|NAME[,NAME]",
        help="negotiate per-connection wire compression with the queue "
        "server (tcp:// and cluster:// transports): 'auto' DECIDES per "
        "connection from a brief link-rate probe at connect — "
        "compression on through slow links (tunnels), off on fast LANs "
        "where the codec only burns CPU — re-decided on every "
        "reconnect (codec_auto_decision flight breadcrumb either way). "
        "A name advertises exactly that "
        "codec (pure-numpy shuffle-rle always; lz4/bitshuffle when "
        "installed). The server picks; old servers degrade the "
        "connection to uncompressed. Default: off (wire bytes "
        "byte-identical to pre-codec builds)",
    )
    if producer:
        parser.add_argument(
            "--wire_dtype", default="", metavar="DTYPE",
            help="LOSSY opt-in: narrow panels to this dtype before "
            "encode (e.g. uint16 halves f32 wire bytes; integer "
            "targets round + clip). Off by default",
        )


def add_tenant_args(parser) -> None:
    """The serving fair-share CLI surface (ISSUE 12)."""
    parser.add_argument(
        "--tenant", default="", metavar="NAME",
        help="fair-share tenant identity for this endpoint's queue "
        "connections (tcp:// and cluster:// transports): the event "
        "loop's stream pump serves tenants by weighted deficit "
        "round-robin, so one greedy tenant cannot starve the rest. "
        "Rides the existing capability exchange — zero new wire "
        "surface; old servers ignore it. Default: the shared default "
        "tenant",
    )
    parser.add_argument(
        "--tenant_weight", type=int, default=1, metavar="1-64",
        help="this tenant's fair-share weight (goodput under "
        "contention converges to the weight shares)",
    )


def apply_tenant_args(config: TransportConfig, args) -> TransportConfig:
    """Fold the tenant flags into a TransportConfig."""
    import dataclasses

    tenant = getattr(args, "tenant", "") or ""
    weight = int(getattr(args, "tenant_weight", 1) or 1)
    if not 1 <= weight <= 64:
        raise ValueError(f"--tenant_weight must be in [1, 64], got {weight}")
    if not tenant:
        if weight != 1:
            # refusing loudly beats silently serving at default weight:
            # the weight only means something under a tenant identity
            raise ValueError("--tenant_weight requires --tenant")
        return config
    return dataclasses.replace(config, tenant=tenant, tenant_weight=weight)


def apply_wire_args(config: TransportConfig, args) -> TransportConfig:
    """Fold the wire-compression flags into a TransportConfig."""
    import dataclasses

    codec = getattr(args, "wire_codec", "") or ""
    dtype = getattr(args, "wire_dtype", "") or ""
    if not codec and not dtype:
        return config
    if codec and codec != "none":
        from psana_ray_tpu.transport.codec import get_codec

        if codec != "auto":
            for name in codec.split(","):
                get_codec(name.strip())  # fail fast on unknown names
    if dtype:
        from psana_ray_tpu.records import validate_wire_dtype

        validate_wire_dtype(dtype)  # fail fast, one shared rule
    return dataclasses.replace(config, wire_codec=codec, wire_dtype=dtype)


def open_queue(
    config: TransportConfig,
    role: str = "consumer",
    address: Optional[str] = None,
    registry: Optional[Registry] = None,
):
    """Open the queue named by ``config`` over the transport its address
    selects. Returns an object with the transport contract (put/get/size/
    put_wait/get_wait/get_batch/close)."""
    if role not in ("producer", "consumer"):
        raise ValueError(f"role must be producer|consumer, got {role!r}")
    address = address or config.address
    # one normalization of the codec knob for every TCP-family branch:
    # ""/"none" -> no negotiation; likewise the tenant hello ("" = the
    # shared default tenant, no capability field on the wire)
    wire_codec = config.wire_codec if config.wire_codec not in ("", "none") else None
    tenant = config.tenant or None

    if address in ("auto", "local"):
        reg = registry or Registry.default()
        from psana_ray_tpu.transport.ring import RingBuffer

        if role == "producer":
            return reg.get_or_create(
                config.namespace,
                config.queue_name,
                lambda: RingBuffer(config.queue_size, name=config.queue_name),
            )
        return reg.resolve(
            config.namespace,
            config.queue_name,
            retries=config.rendezvous_retries,
            interval_s=config.rendezvous_interval_s,
        )

    if address.startswith("shm://"):
        from psana_ray_tpu.transport.shm_ring import ShmRingBuffer

        name = shm_ring_name(config, address)
        if role == "consumer":
            return ShmRingBuffer.attach(
                name,
                retries=config.rendezvous_retries,
                interval_s=config.rendezvous_interval_s,
            )
        # producer: get-or-create, tolerating the create-vs-attach race the
        # reference handles with try-get-first (producer.py:42-48). The
        # native create is O_EXCL, so exactly one creator wins.
        try:
            return ShmRingBuffer.attach(name, retries=0, interval_s=0.01)
        except RendezvousTimeout:
            pass
        try:
            return ShmRingBuffer.create(name, maxsize=config.queue_size)
        except RuntimeError:
            # lost the race — another producer created it just now
            return ShmRingBuffer.attach(
                name,
                retries=config.rendezvous_retries,
                interval_s=config.rendezvous_interval_s,
            )

    if address.startswith("cluster://"):
        from psana_ray_tpu.cluster.client import ClusterClient

        # producers never join consumer groups — a group is a consumer-
        # side partition-ownership construct; a producer in the member
        # list would hold (and starve) partitions it never reads
        group = config.group if role == "consumer" else ""
        return ClusterClient(
            address,
            namespace=config.namespace,
            queue_name=config.queue_name,
            n_partitions=config.cluster_partitions,
            maxsize=config.queue_size,
            group=group or None,
            member_id=config.member_id or None,
            codec=wire_codec,
            tenant=tenant,
            tenant_weight=config.tenant_weight,
        )

    if address.startswith("tcp://"):
        from psana_ray_tpu.transport.tcp import TcpQueueClient

        host, _, port = address[len("tcp://"):].partition(":")
        if not port:
            raise ValueError(f"tcp address needs host:port, got {address!r}")
        # (namespace, queue_name) select a named queue on the server —
        # one queue server per cluster hosts every detector's queue, the
        # role Ray's GCS plays for the reference's named actors
        return TcpQueueClient(
            host,
            int(port),
            namespace=config.namespace,
            queue_name=config.queue_name,
            maxsize=config.queue_size,
            codec=wire_codec,
            tenant=tenant,
            tenant_weight=config.tenant_weight,
        )

    raise ValueError(
        f"unknown address scheme {address!r} (want auto | shm://[name] | "
        f"tcp://host:port | cluster://host:port,host:port,...)"
    )
