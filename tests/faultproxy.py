"""Reusable fault-injection TCP proxies for transport/durability tests.

Grown out of the ad-hoc delay-line proxy test_tcp_stream.py carried
since ISSUE 5 (now imported from here): a recovery test should INJECT
its failure — kill the wire at an exact byte, tear a write in half,
stall a direction — instead of reaching into server internals or
killing sockets it happens to hold. Both proxies listen on an ephemeral
local port and forward to a destination.

:class:`DelayProxy`
    Fixed one-way latency, unlimited bandwidth (per-direction delay
    lines with chunk coalescing) — models RTT, not throughput.

:class:`ThrottleProxy`
    Token-bucket bytes/s cap per direction, zero added latency —
    models BANDWIDTH, not RTT (the wire-compression A/B's honest
    adversary: a 50 MB/s tunnel does not care how many round trips
    you saved). Each direction has its own bucket, like a full-duplex
    link.

:class:`DiskFaultInjector`
    The storage sibling (ISSUE 11): arms the patchable disk-fault hook
    in :mod:`psana_ray_tpu.storage.log` so segment appends/fsyncs raise
    ``OSError`` (default ``ENOSPC``) after N successful ops — a failing
    or full durable disk, injected without touching a real filesystem.
    Context manager; the hook is process-wide, so use it around
    in-process servers only.

:func:`arrival_schedule` / :class:`OpenLoopLoad`
    Open-loop burst generation (ISSUE 12): DETERMINISTIC arrival-time
    schedules (steady / burst / ramp profiles) plus a driver that fires
    per-tenant ``submit`` callbacks at those times regardless of how
    the system under test is coping — an open-loop source keeps
    offering at the configured rate while the server drowns, which is
    exactly the adversary an admission-controlled gateway exists for
    (a closed-loop client would politely back off and hide the
    overload). Used by the gateway tests.

:class:`FaultProxy`
    Byte-counting fault injector. Faults are armed per direction
    (``"up"`` = client->server, ``"down"`` = server->client):

    - ``kill_at(direction, nbytes)`` — forward exactly ``nbytes`` more,
      then sever BOTH sides of every connection (a crash mid-message:
      the peer sees a clean-cut byte stream, exactly what a kill -9 of
      the remote produces on the wire);
    - ``torn_write_at(direction, nbytes, keep)`` — at the trigger,
      forward only ``keep`` bytes of the in-flight chunk, then sever
      (a torn write: the receiver holds a half-record);
    - ``stall_at(direction, nbytes, stall_s)`` — pause forwarding that
      direction for ``stall_s`` (connections stay up: models a wedged
      peer / network brownout, the stall-detector's jurisdiction);
    - ``kill_now()`` — sever everything immediately.

    Counting is cumulative across connections per direction, so "kill
    after the 3rd frame" is ``kill_at("up", 3 * frame_wire_bytes)``
    regardless of reconnects. One fault per direction at a time; re-arm
    after it fires (``fired`` tells you it did).
"""

from __future__ import annotations

import errno
import os
import socket
import threading
import time
from collections import deque


class DiskFaultInjector:
    """Arm the storage layer's patchable disk-fault hook: after
    ``ok_ops`` successful matching ops, every further matching op
    raises ``OSError(err)`` until :meth:`disarm` (or context exit).

    ``ops`` filters which hook sites fault (``"append"``, ``"sync"``).
    The durable stack is expected to degrade LOUDLY — ``disk_fault``
    flight breadcrumb + DURABLE counter + an 'E' answer to the
    producer — and the serving loop must survive (pinned by
    tests/test_replication.py)."""

    def __init__(self, ok_ops: int = 0, err: int = errno.ENOSPC,
                 ops=("append", "sync")):
        self.ok_ops = ok_ops
        self.err = err
        self.ops = tuple(ops)
        self.fired = 0
        self._seen = 0
        self._lock = threading.Lock()
        self._armed = True

    def __call__(self, op: str) -> None:
        with self._lock:
            if not self._armed or op not in self.ops:
                return
            self._seen += 1
            if self._seen <= self.ok_ops:
                return
            self.fired += 1
        raise OSError(self.err, f"{os.strerror(self.err)} (injected, op={op})")

    def disarm(self) -> None:
        with self._lock:
            self._armed = False

    def __enter__(self) -> "DiskFaultInjector":
        from psana_ray_tpu.storage.log import set_disk_fault_hook

        set_disk_fault_hook(self)
        return self

    def __exit__(self, *exc) -> None:
        from psana_ray_tpu.storage.log import set_disk_fault_hook

        set_disk_fault_hook(None)


class DelayProxy:
    """TCP proxy adding a fixed one-way latency WITHOUT limiting
    bandwidth: each received chunk enters a per-direction delay line and
    is released ``delay_s`` later (a sleep-per-chunk pump would serialize
    chunks and model bandwidth, not latency)."""

    def __init__(self, dst_host: str, dst_port: int, delay_s: float):
        self.delay_s = delay_s
        self._dst = (dst_host, dst_port)
        self._stop = threading.Event()
        self._socks = []
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(16)
        self.port = self._lsock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        self._lsock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                dst = socket.create_connection(self._dst, timeout=5.0)
            except OSError:
                conn.close()
                continue
            for s in (conn, dst):
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._socks += [conn, dst]
            self._pipe(conn, dst)
            self._pipe(dst, conn)

    def _pipe(self, src, dst):
        line = deque()  # (deliver_at, chunk)
        cond = threading.Condition()
        eof = [False]

        def rx():
            try:
                while not self._stop.is_set():
                    data = src.recv(1 << 20)  # big chunks: the proxy must
                    # model latency, not become the bandwidth bottleneck
                    if not data:
                        break
                    with cond:
                        line.append((time.monotonic() + self.delay_s, data))
                        cond.notify()
            except OSError:
                pass
            with cond:
                eof[0] = True
                cond.notify()

        def tx():
            try:
                while True:
                    with cond:
                        while not line and not eof[0]:
                            if self._stop.is_set():
                                return
                            cond.wait(timeout=0.2)
                        if not line:
                            break
                        at, data = line.popleft()
                        lag = at - time.monotonic()
                        if lag <= 0:
                            # coalesce every already-ripe chunk into one
                            # send: per-chunk wakeups would quantize the
                            # relay to the scheduler tick and turn the
                            # latency model into a bandwidth bottleneck
                            ripe = [data]
                            now = time.monotonic()
                            while line and line[0][0] <= now:
                                ripe.append(line.popleft()[1])
                            data = b"".join(ripe) if len(ripe) > 1 else data
                            lag = 0.0
                    if lag > 0:
                        time.sleep(lag)
                    dst.sendall(data)
            except OSError:
                pass
            try:
                dst.shutdown(socket.SHUT_WR)
            except OSError:
                pass

        threading.Thread(target=rx, daemon=True).start()
        threading.Thread(target=tx, daemon=True).start()

    def close(self):
        self._stop.set()
        for s in [self._lsock, *self._socks]:
            try:
                s.close()
            except OSError:
                pass


class ThrottleProxy:
    """TCP proxy capping each direction at ``bytes_per_s`` with a token
    bucket (burst = ``burst_s`` seconds of rate): chunks are forwarded
    in bounded slices, each waiting for its tokens — throughput
    converges to the cap from below, with no artificial latency while
    tokens remain. One bucket per direction, shared across every
    proxied connection (the directions of one physical link contend
    with themselves, exactly like a real full-duplex tunnel)."""

    # forwarding granularity: big enough that pacing sleeps are several
    # ms each (sub-ms sleeps on a loaded 2-core box wake late and
    # throttle BELOW the cap — the proxy must model the link, not the
    # scheduler), small enough that the burst bucket still smooths it
    _SLICE = 256 * 1024
    _MIN_SLEEP_S = 0.004  # debts below this accrue in the bucket instead

    def __init__(self, dst_host: str, dst_port: int, bytes_per_s: float, burst_s: float = 0.25):
        self.bytes_per_s = float(bytes_per_s)
        self._burst = self.bytes_per_s * burst_s
        self._dst = (dst_host, dst_port)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._socks = []  # guarded-by: _lock
        now = time.monotonic()
        # direction -> [tokens, last_refill]
        self._bucket = {"up": [self._burst, now], "down": [self._burst, now]}  # guarded-by: _lock
        self._bytes = {"up": 0, "down": 0}  # guarded-by: _lock
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(16)
        self.port = self._lsock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def bytes_forwarded(self, direction: str) -> int:
        with self._lock:
            return self._bytes[direction]

    def _take(self, direction: str, n: int) -> float:
        """Deduct ``n`` tokens; returns how long the caller must sleep
        before forwarding (0 when the bucket covers the chunk)."""
        with self._lock:
            bucket = self._bucket[direction]
            now = time.monotonic()
            bucket[0] = min(
                self._burst, bucket[0] + (now - bucket[1]) * self.bytes_per_s
            )
            bucket[1] = now
            bucket[0] -= n
            wait = -bucket[0] / self.bytes_per_s if bucket[0] < 0 else 0.0
            self._bytes[direction] += n
            return wait

    def _accept(self):
        self._lsock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                dst = socket.create_connection(self._dst, timeout=5.0)
            except OSError:
                conn.close()
                continue
            for s in (conn, dst):
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._socks += [conn, dst]
            threading.Thread(
                target=self._pump, args=(conn, dst, "up"), daemon=True
            ).start()
            threading.Thread(
                target=self._pump, args=(dst, conn, "down"), daemon=True
            ).start()

    def _pump(self, src, dst, direction: str):
        try:
            while not self._stop.is_set():
                data = src.recv(self._SLICE)
                if not data:
                    break
                wait = self._take(direction, len(data))
                if wait >= self._MIN_SLEEP_S:  # smaller debts stay banked
                    time.sleep(wait)
                dst.sendall(data)
        except OSError:
            pass
        for s in (src, dst):
            try:
                s.shutdown(socket.SHUT_WR)
            except OSError:
                pass

    def close(self):
        self._stop.set()
        with self._lock:
            socks, self._socks = self._socks, []
        for s in [self._lsock, *socks]:
            try:
                s.close()
            except OSError:
                pass


def arrival_schedule(
    profile: str,
    rate_hz: float,
    duration_s: float,
    burst_factor: float = 4.0,
    period_s: float = 1.0,
    ramp_to_hz: float = 0.0,
):
    """Deterministic open-loop arrival offsets (seconds from start),
    sorted ascending. ``rate_hz`` is the MEAN rate for every profile,
    so A/B rows at different shapes offer the same total work:

    - ``steady``: uniform spacing at ``rate_hz``;
    - ``burst``: square wave with period ``period_s`` — all of each
      period's arrivals land inside its first ``1/burst_factor``
      fraction (instantaneous rate ``burst_factor * rate_hz``, then
      silence): the queue-dwell adversary;
    - ``ramp``: rate climbs linearly to ``ramp_to_hz`` (default
      ``2 * rate_hz``), starting low enough that the MEAN stays
      ``rate_hz``: the knee-finding shape.
    """
    if rate_hz <= 0 or duration_s <= 0:
        return []
    n = int(rate_hz * duration_s)
    if profile == "steady":
        return [i / rate_hz for i in range(n)]
    if profile == "burst":
        if burst_factor <= 1.0:
            raise ValueError(f"burst_factor must exceed 1, got {burst_factor}")
        if period_s <= 0:
            raise ValueError(f"period_s must be positive, got {period_s}")
        # fractional per-period arithmetic: int() truncation here would
        # realize a different mean rate than documented (and collapse
        # to one arrival/period when rate_hz * period_s < 2)
        per_period = rate_hz * period_s
        on_s = period_s / burst_factor
        out = []
        for i in range(n):
            period_idx = int(i // per_period)
            k = i - period_idx * per_period
            out.append(period_idx * period_s + (k / per_period) * on_s)
        return out
    if profile == "ramp":
        r1 = ramp_to_hz or 2.0 * rate_hz
        # mean rate == rate_hz: start low enough that the ramp averages
        # out (r0 + r1) / 2 == rate_hz
        r0 = max(0.0, 2.0 * rate_hz - r1)
        t_ = duration_s
        out = []
        for i in range(n):
            # invert the cumulative count N(t) = r0 t + (r1-r0) t^2 / 2T
            a = (r1 - r0) / (2.0 * t_)
            if a <= 0:
                out.append(i / rate_hz)
                continue
            # solve a t^2 + r0 t - i = 0 for t >= 0
            t = (-r0 + (r0 * r0 + 4.0 * a * i) ** 0.5) / (2.0 * a)
            out.append(min(t, t_))
        return out
    raise ValueError(f"profile must be steady|burst|ramp, got {profile!r}")


class OpenLoopLoad:
    """Fire per-tenant schedules against ``submit(tenant)`` in real
    time, OPEN-loop: arrivals that fell due while the driver was asleep
    (scheduler jitter on a loaded box) are fired immediately in catch-up
    — the offered count over the run is exactly the schedule's, never
    throttled by the system under test.

    ``schedules`` maps tenant name -> arrival offsets (seconds; from
    :func:`arrival_schedule`). ``run()`` blocks until every schedule
    drains and returns ``{tenant: offered_count}``; ``start()`` +
    ``join()`` split that for concurrent measurement."""

    def __init__(self, submit, schedules: dict):
        self._submit = submit
        self._schedules = {t: sorted(s) for t, s in schedules.items()}
        self._threads = []
        self.offered = {t: 0 for t in schedules}

    def _drive(self, tenant: str, schedule):
        t0 = time.monotonic()
        n = 0
        for off in schedule:
            lag = (t0 + off) - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            self._submit(tenant)
            n += 1
        self.offered[tenant] = n

    def start(self) -> "OpenLoopLoad":
        for tenant, schedule in self._schedules.items():
            t = threading.Thread(
                target=self._drive, args=(tenant, schedule),
                daemon=True, name=f"openloop-{tenant}",
            )
            self._threads.append(t)
            t.start()
        return self

    def join(self, timeout_s: float = 600.0) -> dict:
        deadline = time.monotonic() + timeout_s
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        return dict(self.offered)

    def run(self, timeout_s: float = 600.0) -> dict:
        return self.start().join(timeout_s)


class _Fault:
    __slots__ = ("kind", "at_bytes", "keep", "stall_s", "fired")

    def __init__(self, kind, at_bytes, keep=0, stall_s=0.0):
        self.kind = kind  # "kill" | "torn" | "stall"
        self.at_bytes = at_bytes
        self.keep = keep
        self.stall_s = stall_s
        self.fired = False


class FaultProxy:
    """Byte-counting fault injector — see the module docstring."""

    def __init__(self, dst_host: str, dst_port: int):
        self._dst = (dst_host, dst_port)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._socks = []  # guarded-by: _lock
        self._bytes = {"up": 0, "down": 0}  # guarded-by: _lock
        self._faults = {"up": None, "down": None}  # guarded-by: _lock
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(16)
        self.port = self._lsock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    # -- fault arming ------------------------------------------------------
    def kill_at(self, direction: str, nbytes: int) -> "_Fault":
        return self._arm(direction, _Fault("kill", nbytes))

    def torn_write_at(self, direction: str, nbytes: int, keep: int) -> "_Fault":
        return self._arm(direction, _Fault("torn", nbytes, keep=keep))

    def stall_at(self, direction: str, nbytes: int, stall_s: float) -> "_Fault":
        return self._arm(direction, _Fault("stall", nbytes, stall_s=stall_s))

    def _arm(self, direction: str, fault: _Fault) -> _Fault:
        if direction not in ("up", "down"):
            raise ValueError(f"direction must be up|down, got {direction!r}")
        with self._lock:
            self._faults[direction] = fault
        return fault

    def bytes_forwarded(self, direction: str) -> int:
        with self._lock:
            return self._bytes[direction]

    def kill_now(self) -> None:
        """Sever every proxied connection immediately (both sides)."""
        with self._lock:
            socks, self._socks = self._socks, []
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    # -- plumbing ----------------------------------------------------------
    def _accept(self):
        self._lsock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                dst = socket.create_connection(self._dst, timeout=5.0)
            except OSError:
                conn.close()
                continue
            for s in (conn, dst):
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._socks += [conn, dst]
            threading.Thread(
                target=self._pump, args=(conn, dst, "up"), daemon=True
            ).start()
            threading.Thread(
                target=self._pump, args=(dst, conn, "down"), daemon=True
            ).start()

    def _pump(self, src, dst, direction: str):
        try:
            while not self._stop.is_set():
                data = src.recv(1 << 16)
                if not data:
                    break
                send = data
                fire = None
                stall = 0.0
                with self._lock:
                    fault = self._faults[direction]
                    counted = self._bytes[direction]
                    if fault is not None and not fault.fired and (
                        counted + len(data) >= fault.at_bytes
                    ):
                        fault.fired = True
                        if fault.kind == "kill":
                            send = data[: max(0, fault.at_bytes - counted)]
                            fire = "kill"
                        elif fault.kind == "torn":
                            cut = max(0, fault.at_bytes - counted)
                            send = data[: cut + fault.keep]
                            fire = "kill"  # a torn write severs after it
                        else:  # stall: forward intact, then pause
                            stall = fault.stall_s
                    self._bytes[direction] += len(send)
                if send:
                    dst.sendall(send)
                if fire == "kill":
                    self.kill_now()
                    return
                if stall:
                    time.sleep(stall)
        except OSError:
            pass
        for s in (src, dst):
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def close(self):
        self._stop.set()
        self.kill_now()
        try:
            self._lsock.close()
        except OSError:
            pass
