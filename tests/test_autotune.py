"""Tier-1 driver for the autotune subsystem (ISSUE 15).

Layers:

1. knob registry mechanics — bounds/quantum clamping, live setter
   round-trips, manual pins, the gateway single-writer rule;
2. the hill climber on a SYNTHETIC metric surface, driven tick by tick
   with explicitly-timed store samples — deterministic, no wall-clock;
3. guardrail semantics — an injected shed-rate spike reverts the open
   probe immediately and freezes probing for the episode;
4. observe mode actuates NOTHING (decisions are logged, setters never
   called);
5. live transport knobs — put/stream-window resize and codec
   renegotiation over a real event-loop server, plus the
   ``--wire_codec auto`` probe decision both ways (thresholds forced
   through the env override, no link shaping needed);
6. the ``autotune`` telemetry source shape and the CLI plumb;
7. the zero-copy pins (copies/frame 1.00, pool churn 0) with a LIVE
   controller actuating drain knobs mid-stream.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from psana_ray_tpu.autotune.controller import (
    Guardrail,
    HillClimber,
    Objective,
    default_guardrails,
)
from psana_ray_tpu.autotune.daemon import (
    AutotuneDaemon,
    add_autotune_args,
    configure_autotune_from_args,
)
from psana_ray_tpu.autotune.knobs import (
    GROUP_SERVING,
    Knob,
    KnobRegistry,
    bufpool_retention_knob,
    drain_chunk_knob,
    drain_poll_knob,
    fsync_batch_knob,
    prefetch_depth_knob,
    put_window_knob,
    ram_items_knob,
    stream_window_knob,
    wire_codec_knob,
)
from psana_ray_tpu.infeed.batcher import DrainControl, batches_from_queue
from psana_ray_tpu.obs.flight import FLIGHT
from psana_ray_tpu.obs.timeseries import TimeSeriesStore
from psana_ray_tpu.records import EndOfStream, FrameRecord
from psana_ray_tpu.transport.ring import RingBuffer
from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer
from psana_ray_tpu.utils.bufpool import WIRE, BufferPool


def _rec(i, shape=(2, 16, 16)):
    return FrameRecord(0, i, np.full(shape, i % 251, np.uint16), 9.5)


def _flight_since(n0, kind):
    """Events of ``kind`` recorded after lifetime-count ``n0`` (marks
    are ``FLIGHT.count_of(kind)``) — robust to ring eviction, unlike
    slicing ``events()`` by the lifetime event_count."""
    evs = [e for e in FLIGHT.events() if e["kind"] == kind]
    new = FLIGHT.count_of(kind) - n0
    return evs[-new:] if new > 0 else []


# ---------------------------------------------------------------------------
# 1. knob + registry mechanics
# ---------------------------------------------------------------------------

class TestKnobRegistry:
    def _val_knob(self, val, name="k", group="g", lo=1, hi=64, step=4):
        return Knob(
            name, group, "client", lo=lo, hi=hi, step=step,
            get=lambda: val[0], set=lambda v: val.__setitem__(0, v),
        )

    def test_clamp_quantizes_to_the_step_grid(self):
        k = self._val_knob([8.0])
        assert k.clamp(0) == 1
        assert k.clamp(999) == 64
        assert k.clamp(10.9) == 9  # grid anchored at lo: 1, 5, 9, ...
        assert k.clamp(11.1) == 13
        assert k.neighbor(9, +1) == 13
        assert k.neighbor(1, -1) == 1  # pinned at the bound

    def test_discrete_menu_snaps_and_steps(self):
        val = [1.0]
        k = Knob(
            "codec", "codec", "client", lo=0, hi=1, step=1,
            get=lambda: val[0], set=lambda v: val.__setitem__(0, v),
            values=(0.0, 1.0),
        )
        assert k.clamp(0.7) == 1.0
        assert k.neighbor(1.0, -1) == 0.0
        assert k.neighbor(1.0, +1) == 1.0

    def test_apply_round_trips_through_the_setter(self):
        val = [8.0]
        reg = KnobRegistry()
        reg.register(self._val_knob(val))
        mark = FLIGHT.event_count
        out = reg.apply("k", 14.0)  # quantized to the grid
        assert out == 13 and val[0] == 13
        assert reg.current("k") == 13
        evs = [e for e in FLIGHT.events() if e["kind"] == "autotune_actuate"]
        assert evs and evs[-1]["knob"] == "k" and evs[-1]["to"] == 13
        assert FLIGHT.event_count > mark  # never silent

    def test_pinned_and_excluded_knobs_leave_the_rotation(self):
        reg = KnobRegistry()
        reg.register(self._val_knob([1.0], name="a", group="g1"))
        reg.register(self._val_knob([1.0], name="b", group="g2"))
        reg.register(self._val_knob([1.0], name="c", group=GROUP_SERVING))
        assert reg.eligible() == ["a", "b", "c"]
        reg.pin("a", "--flag set explicitly")
        reg.note_gateway(object())
        assert reg.eligible() == ["b"]
        snap = reg.snapshot()
        assert snap["a"]["pinned"] == 1 and snap["pinned_total"] == 1

    def test_duplicate_registration_refused_and_none_absorbed(self):
        reg = KnobRegistry()
        reg.register(self._val_knob([1.0]))
        assert reg.register(None) is None
        with pytest.raises(ValueError, match="already registered"):
            reg.register(self._val_knob([1.0]))

    def test_observe_mode_never_calls_the_setter(self):
        calls = []
        reg = KnobRegistry(mode="observe")
        reg.register(Knob(
            "k", "g", "client", lo=1, hi=64, step=4,
            get=lambda: 8.0, set=lambda v: calls.append(v),
        ))
        mark = FLIGHT.event_count
        out = reg.apply("k", 12.0)
        assert out == 8.0 and not calls
        obs = _flight_since(0, "autotune_observe")
        assert obs and obs[-1]["would_set"] == 13.0
        assert reg.snapshot()["observed_total"] == 1
        assert FLIGHT.event_count > mark


# ---------------------------------------------------------------------------
# 2-4. the hill climber: convergence, guardrails, observe mode —
# all tick-driven over explicitly-timed synthetic samples
# ---------------------------------------------------------------------------

def _drive(hc, store, val, f, ticks, t0=1000.0, counters=None):
    """Feed one sample per second of FAKE time, tick after each. ``f``
    maps knob value -> instantaneous fps. ``counters`` adds extra
    monotone keys (guardrail counters)."""
    # per-store cumulative counter state so callers can drive in stages
    if not hasattr(store, "_test_cum"):
        store._test_cum = {"fps": 0.0, "t": t0}
    cum = store._test_cum
    for _ in range(ticks):
        cum["fps"] += f(val[0])
        cum["t"] += 1.0
        tree = {"syn": {"frames_total": cum["fps"]}}
        if counters:
            tree.update(counters(cum["t"]))
        store.record(tree, now=cum["t"])
        hc.tick()


class TestHillClimber:
    def _setup(self, start=8.0, guardrails=(), mode="on", **kw):
        store = TimeSeriesStore()
        reg = KnobRegistry(mode=mode)
        val = [start]
        reg.register(Knob(
            "k", "g", "client", lo=1, hi=64, step=4,
            get=lambda: val[0], set=lambda v: val.__setitem__(0, v),
        ))
        kw.setdefault("hold_ticks", 2)
        kw.setdefault("settle_ticks", 3)
        kw.setdefault("cooldown_ticks", 2)
        hc = HillClimber(
            reg, Objective("syn.frames_total", window_s=2.5),
            store=store, guardrails=guardrails, **kw,
        )
        return store, reg, val, hc

    def test_converges_on_a_synthetic_surface_and_holds(self):
        """Deterministic convergence: fps peaks at k=33 (on the quantum
        grid); the climber must walk there and STAY (hysteresis: once
        converged, probes at the peak revert and the knob sits still)."""
        store, reg, val, hc = self._setup()
        _drive(hc, store, val, lambda k: 1000.0 - abs(k - 33.0) * 10.0, 400)
        assert abs(val[0] - 33.0) <= 4.0, val[0]
        # converged: further driving leaves it at the peak
        settled = val[0]
        _drive(hc, store, val, lambda k: 1000.0 - abs(k - 33.0) * 10.0, 80)
        assert abs(val[0] - settled) <= 4.0
        snap = reg.snapshot()
        assert snap["k"]["actuations_total"] > 0
        assert snap["k"]["kept_total"] > 0  # improvements held
        assert snap["k"]["reverts_total"] > 0  # the peak pushes back

    def test_regression_reverts_and_flips_direction(self):
        """On a monotone-DECREASING surface every upward probe is a
        regression: the knob must end at or below its start, and every
        probe must have a matching revert (never silently kept)."""
        store, reg, val, hc = self._setup(start=33.0)
        mark = FLIGHT.count_of("autotune_revert")
        _drive(hc, store, val, lambda k: 2000.0 - k * 10.0, 120)
        assert val[0] <= 33.0
        snap = reg.snapshot()["k"]
        reverts = _flight_since(mark, "autotune_revert")
        assert snap["reverts_total"] == len(reverts) > 0

    def test_guardrail_trip_reverts_the_open_probe(self):
        """An injected shed-rate spike mid-probe reverts IMMEDIATELY
        (not at the end of the hold window), breadcrumbs the trip, and
        freezes probing while the spike lasts."""
        shed_rate = [0.0]

        def counters(t):
            # a counter increasing at shed_rate/s
            c = getattr(counters, "cum", 0.0) + shed_rate[0]
            counters.cum = c
            return {"gateway": {"shed_total": c}}

        store, reg, val, hc = self._setup(
            guardrails=[Guardrail("gateway.shed_total", "rate_above", 1.0)],
        )
        f = lambda k: 1000.0 + k * 50.0  # noqa: E731 — upward probes improve
        _drive(hc, store, val, f, 12, counters=counters)
        probed = val[0]
        assert probed > 8.0  # a probe is open or was kept
        mark = FLIGHT.count_of("autotune_guardrail")
        acts = reg.snapshot()["k"]["actuations_total"]
        shed_rate[0] = 50.0  # spike
        _drive(hc, store, val, f, 20, counters=counters)
        trips = _flight_since(mark, "autotune_guardrail")
        assert trips, "guardrail trip must breadcrumb"
        # probing frozen during the episode: no NEW probes opened (the
        # only actuation allowed after the trip is the revert itself)
        after = reg.snapshot()["k"]
        assert after["actuations_total"] <= acts + 1
        assert hc.guardrail_trips > 0

    def test_observe_mode_logs_decisions_but_never_actuates(self):
        store, reg, val, hc = self._setup(mode="observe")
        mark_obs = FLIGHT.count_of("autotune_observe")
        mark_act = FLIGHT.count_of("autotune_actuate")
        _drive(hc, store, val, lambda k: 1000.0 + k, 60)
        assert val[0] == 8.0  # untouched
        obs = _flight_since(mark_obs, "autotune_observe")
        assert obs, "observe mode must log what it would do"
        assert not _flight_since(mark_act, "autotune_actuate")

    def test_starved_metrics_abort_an_open_probe(self):
        """A store with no fresh samples (objective returns None) must
        abort the probe within max_starved_ticks, restoring the saved
        value — never leave a half-probed knob in place forever."""
        store, reg, val, hc = self._setup(max_starved_ticks=3, settle_ticks=0)
        # the first tick's rate view is still empty (one sample), then
        # two baseline ticks, then the probe opens (hold_ticks=2)
        _drive(hc, store, val, lambda k: 1000.0, 3)
        assert val[0] == 13.0, "probe should be open at the stepped value"
        # starve the objective: swap in an EMPTY store
        hc._store = TimeSeriesStore()
        for _ in range(6):
            hc.tick()
        assert val[0] == 8.0, "probe must revert once metrics starve"


# ---------------------------------------------------------------------------
# single-writer rule: gateway-bound knobs defer to SloPolicy
# ---------------------------------------------------------------------------

class TestSingleWriterWithSloPolicy:
    def test_gateway_bound_serving_knobs_are_never_actuated(self):
        """ISSUE 15 satellite: bind BOTH a serving gateway (SloPolicy
        refining batch choice per dispatch) and an autotune registry
        holding a serving-group knob — the controller must never write
        the batch dial (single-writer), while SloPolicy keeps learning
        from dispatches."""
        from psana_ray_tpu.serving.gateway import ServingGateway
        from psana_ray_tpu.serving.policy import SloPolicy

        policy = SloPolicy(slo_ms=50.0)
        gw = ServingGateway(lambda recs, b: None, policy=policy)
        control = DrainControl(chunk=8, poll_s=0.01)
        set_calls = []
        store = TimeSeriesStore()
        reg = KnobRegistry()
        knob = drain_chunk_knob(control)
        knob.set = lambda v: set_calls.append(v)  # count actuations
        reg.register(knob)
        reg.note_gateway(gw)
        hc = HillClimber(
            reg, Objective("syn.frames_total", window_s=2.5),
            store=store, hold_ticks=2, settle_ticks=1,
        )
        val = [0.0]
        _drive(hc, store, val, lambda k: 1000.0, 60)
        assert not set_calls, "controller wrote a gateway-owned knob"
        assert reg.eligible() == []
        # SloPolicy remains the single writer of batch sizing
        before = policy.snapshot()["service_ms"]["8"]
        policy.observe_service(8, 99.0)
        assert policy.snapshot()["service_ms"]["8"] != before

    def test_without_a_gateway_the_same_knob_is_controlled(self):
        control = DrainControl(chunk=8, poll_s=0.01)
        store = TimeSeriesStore()
        reg = KnobRegistry()
        reg.register(drain_chunk_knob(control))
        hc = HillClimber(
            reg, Objective("syn.frames_total", window_s=2.5),
            store=store, hold_ticks=2, settle_ticks=1,
        )
        val = [0.0]
        _drive(hc, store, val, lambda k: 1000.0 + control.chunk, 40)
        snap = reg.snapshot()["drain_chunk"]
        assert snap["actuations_total"] > 0


# ---------------------------------------------------------------------------
# 5. live transport knobs over a real event-loop server
# ---------------------------------------------------------------------------

class TestLiveTransportKnobs:
    def test_put_window_and_stream_window_resize_live(self):
        srv = TcpQueueServer(RingBuffer(64), host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            c.set_put_window(7)
            assert c.put_window == 7
            c.stream_open(window=4)
            mark = FLIGHT.count_of("stream_resize")
            assert c.set_stream_window(48)
            assert c.stream_window == 48
            # the server observed the resize (breadcrumb from evloop)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if _flight_since(mark, "stream_resize"):
                    break
                time.sleep(0.01)
            evs = _flight_since(mark, "stream_resize")
            assert evs and evs[-1]["window"] == 48 and evs[-1]["old"] == 4
            # ...and the wider window actually carries more frames in
            # flight: push more than the OLD window without acking
            for i in range(12):
                assert c._side_channel().put_wait(_rec(i), timeout=10)
            got = c.get_batch_stream(12, timeout=10)
            deadline = time.monotonic() + 10
            while len(got) < 12 and time.monotonic() < deadline:
                got.extend(c.get_batch_stream(12 - len(got), timeout=0.5))
            assert len(got) == 12  # > the subscribe-time window of 4
            for r in got:
                release = getattr(r, "release", None)
                if release:
                    release()
        finally:
            c.disconnect()
            srv.shutdown()

    def test_stream_window_resize_refused_without_subscription(self):
        srv = TcpQueueServer(RingBuffer(4), host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            with pytest.raises(RuntimeError, match="stream subscription"):
                c.set_stream_window(16)
        finally:
            c.disconnect()
            srv.shutdown()

    def test_renegotiate_codec_flips_compression_live(self):
        srv = TcpQueueServer(RingBuffer(8), host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            assert c.codec_name is None
            assert c.renegotiate_codec(["shuffle-rle"])
            assert c.codec_name == "shuffle-rle"
            rec = _rec(1)
            assert c.put(rec)
            out = c.get()
            assert out.equals(rec)
            out.release()
            assert c.renegotiate_codec(None) is False
            assert c.codec_name is None
            assert c.put(rec)
            out = c.get()
            assert out.equals(rec)
            out.release()
        finally:
            c.disconnect()
            srv.shutdown()

    def test_knob_factories_wrap_the_real_client(self):
        srv = TcpQueueServer(RingBuffer(8), host="127.0.0.1").serve_background()
        c = TcpQueueClient("127.0.0.1", srv.port)
        try:
            k = put_window_knob(c)
            assert k is not None
            k.set(k.clamp(40))
            assert c.put_window == 40
            ck = wire_codec_knob(c)
            assert ck is not None and ck.get() == 0.0
            ck.set(1.0)
            assert ck.get() == 1.0 and c.codec_name is not None
            ck.set(0.0)
            assert ck.get() == 0.0
            # stream knob declines nothing (client supports it), but a
            # bare object without the surface is declined
            assert stream_window_knob(object()) is None
            assert put_window_knob(object()) is None
            assert wire_codec_knob(object()) is None
        finally:
            c.disconnect()
            srv.shutdown()


class TestAutoCodecDecision:
    """``--wire_codec auto`` (ISSUE 15 satellite): one-shot decision at
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

    def test_producer_cli_accepts_auto_with_autotune_off(self):
        """The CLI value works standalone: --wire_codec auto parses and
        rides the config regardless of --autotune (off by default)."""
        from psana_ray_tpu.producer import parse_arguments

        cfg, a = parse_arguments(["--wire_codec", "auto"])
        assert cfg.transport.wire_codec == "auto"
        assert a.autotune == "off"


# ---------------------------------------------------------------------------
# 6. telemetry source shape + CLI plumb
# ---------------------------------------------------------------------------

class TestTelemetryAndCli:
    def test_autotune_source_shape(self):
        reg = KnobRegistry()
        val = [8.0]
        reg.register(Knob(
            "k", "g", "client", lo=1, hi=64, step=4,
            get=lambda: val[0], set=lambda v: val.__setitem__(0, v),
        ))
        hc = HillClimber(
            reg, Objective("syn.frames_total"), store=TimeSeriesStore()
        )
        daemon = AutotuneDaemon(hc, interval_s=5.0)
        snap = daemon.snapshot()
        assert snap["mode"] == "on" and snap["knobs_total"] == 1
        assert snap["interval_s"] == 5.0
        for key in ("current", "lo", "hi", "actuations_total",
                    "reverts_total", "kept_total", "min_actuated",
                    "max_actuated", "pinned"):
            assert key in snap["k"], key
        for key in ("ticks_total", "decisions_total",
                    "guardrail_trips_total", "probe_open"):
            assert key in snap, key
        # numeric leaves flatten for the history sampler / Prometheus
        from psana_ray_tpu.obs.registry import flatten_numeric

        leaves = []
        flatten_numeric(("autotune",), snap, leaves)
        keys = {k for k, _ in leaves}
        assert "autotune.k.current" in keys
        assert "autotune.k.actuations_total" in keys

    def test_add_autotune_args_and_configure(self):
        import argparse

        p = argparse.ArgumentParser()
        add_autotune_args(p)
        a = p.parse_args([])
        assert a.autotune == "off"
        assert configure_autotune_from_args(a, [], Objective("x")) is None

        a = p.parse_args(["--autotune", "observe", "--autotune_interval", "9"])
        val = [8.0]
        knob = Knob(
            "k", "g", "client", lo=1, hi=64, step=4,
            get=lambda: val[0], set=lambda v: val.__setitem__(0, v),
        )
        from psana_ray_tpu.obs.timeseries import (
            default_history,
            stop_default_history,
        )

        had_history = default_history() is not None
        daemon = configure_autotune_from_args(
            a, [knob, None], Objective("syn.frames_total"),
            pinned={"other": "reason"},
        )
        try:
            assert daemon is not None
            assert daemon.interval_s == 9.0
            assert daemon.controller.registry.mode == "observe"
            assert daemon.controller.registry.eligible() == ["k"]
            assert daemon.controller.guardrails  # defaults armed
            # the controller needs measured history: configure started
            # the process sampler when none was running
            assert default_history() is not None
        finally:
            daemon.stop()
            from psana_ray_tpu.obs import MetricsRegistry

            MetricsRegistry.default().unregister("autotune")
            if not had_history:
                # restore process-global state: a leaked sampler would
                # flip test_flight's no-history pin (and register a
                # stray "timeseries" source) for the rest of the run
                stop_default_history()
                MetricsRegistry.default().unregister("timeseries")

    def test_default_guardrails_are_inert_on_missing_keys(self):
        store = TimeSeriesStore()
        for g in default_guardrails():
            assert g.tripped(store) is False

    def test_all_cli_parsers_expose_the_flag(self):
        from psana_ray_tpu.producer import parse_arguments

        _, a = parse_arguments(["--autotune", "observe"])
        assert a.autotune == "observe"
        # consumer / sfx / queue_server wire add_autotune_args in main();
        # source-level pin keeps the wiring from silently rotting
        import inspect

        import psana_ray_tpu.consumer as consumer
        import psana_ray_tpu.queue_server as queue_server
        import psana_ray_tpu.sfx as sfx

        for mod in (consumer, sfx, queue_server):
            assert "add_autotune_args" in inspect.getsource(mod.main), mod


# ---------------------------------------------------------------------------
# 7. zero-copy pins with the controller LIVE
# ---------------------------------------------------------------------------

class TestZeroCopyWithControllerLive:
    def test_streaming_relay_pins_hold_while_controller_actuates(self):
        """ISSUE 15 acceptance: copies/frame == 1.00 and steady-state
        pool churn == 0 with a live controller actuating the drain
        chunk/poll and the stream credit window MID-STREAM (instrumented
        private pool, same harness as test_wire_zero_copy)."""
        pool = BufferPool()
        q = RingBuffer(32)
        srv = TcpQueueServer(q, host="127.0.0.1", pool=pool).serve_background()
        prod = TcpQueueClient("127.0.0.1", srv.port, pool=pool)
        cons = TcpQueueClient("127.0.0.1", srv.port, pool=pool)
        n = 48
        control = DrainControl(chunk=8, poll_s=0.002)
        store = TimeSeriesStore()
        reg = KnobRegistry()
        reg.register(drain_chunk_knob(control))
        reg.register(drain_poll_knob(control))
        hc = HillClimber(
            reg, Objective("syn.frames_total", window_s=3.0),
            store=store, hold_ticks=1, settle_ticks=0, cooldown_ticks=0,
        )
        stop = threading.Event()
        fed = [0.0]

        def controller_loop():
            t = 1000.0
            while not stop.is_set():
                fed[0] += 100.0
                t += 1.0
                store.record({"syn": {"frames_total": fed[0]}}, now=t)
                hc.tick()
                # stream-window knob rides the CONSUMER connection once
                # subscribed — resize it live too
                try:
                    cons.set_stream_window(16 + (int(t) % 3) * 16)
                except RuntimeError:
                    pass  # not subscribed yet
                time.sleep(0.005)

        try:

            def produce():
                for i in range(n):
                    assert prod.put_wait(_rec(i), timeout=30)
                assert prod.put_wait(EndOfStream(total_events=n), timeout=30)

            t = threading.Thread(target=produce, daemon=True)
            ctl = threading.Thread(target=controller_loop, daemon=True)
            c0 = WIRE.stats()
            t.start()
            ctl.start()
            seen = 0
            m0 = None
            for batch in batches_from_queue(
                cons, 8, poll_interval_s=0.002, control=control
            ):
                if m0 is None:
                    m0 = pool.stats()  # steady state: after first batch
                seen += batch.num_valid
            t.join(timeout=30)
            stop.set()
            ctl.join(timeout=5)
            assert seen == n
            assert cons._stream is not None  # the drain streamed
            d = WIRE.stats()
            copies = d["copies_total"] - c0["copies_total"]
            assert copies == n, f"expected 1 copy/frame, got {copies}/{n}"
            m1 = pool.stats()
            churn = m1["churn_misses"] - m0["churn_misses"]
            assert churn == 0, f"controller-live path churned {churn} allocs"
            # the controller actually actuated mid-stream
            snap = reg.snapshot()
            acted = sum(
                snap[k]["actuations_total"]
                for k in ("drain_chunk", "drain_poll_s")
            )
            assert acted > 0, "controller never actuated during the drain"
        finally:
            stop.set()
            prod.disconnect()
            cons.disconnect()
            srv.shutdown()
            from psana_ray_tpu.transport.ring import EMPTY as _EMPTY

            while True:
                item = q.get()
                if item is _EMPTY:
                    break
                release = getattr(item, "release", None)
                if release is not None:
                    release()


# ---------------------------------------------------------------------------
# storage / infeed / pool knob round-trips
# ---------------------------------------------------------------------------

class TestOtherKnobTargets:
    def test_fsync_and_ram_items_knobs(self, tmp_path):
        from psana_ray_tpu.storage import DurableRingBuffer, SegmentLog

        log = SegmentLog(str(tmp_path / "q"), segment_bytes=1 << 20)
        q = DurableRingBuffer(log, maxsize=16, ram_items=8)
        try:
            fk = fsync_batch_knob(log)
            assert fk is not None
            fk.set(fk.clamp(128))
            assert log.fsync_batch_n == 128
            rk = ram_items_knob(q)
            assert rk is not None
            rk.set(rk.clamp(24))
            assert q.ram_items == 24
            assert fsync_batch_knob(object()) is None
            assert ram_items_knob(object()) is None
        finally:
            q.close()
            log.close()

    def test_bufpool_retention_knob(self):
        pool = BufferPool()
        k = bufpool_retention_knob(pool)
        assert k is not None
        k.set(9)
        assert pool.min_per_class == 9

    def test_prefetch_depth_resizes_live(self):
        from psana_ray_tpu.infeed.pipeline import DevicePrefetcher

        batches = iter([])
        pf = DevicePrefetcher(batches, prefetch_depth=2, to_device=lambda b: b)
        try:
            k = prefetch_depth_knob(pf)
            assert k is not None
            k.set(5)
            assert pf.prefetch_depth == 5
            assert pf._buf.maxsize == 5
        finally:
            pf.close()

    def test_infeed_pipeline_clips_depth_to_the_arena_bound(self):
        from psana_ray_tpu.infeed.pipeline import InfeedPipeline

        q = RingBuffer(4)
        pipe = InfeedPipeline(
            q, batch_size=2, prefetch_depth=2, place_on_device=False,
            batcher_buffers=8,
        )
        try:
            # 8 arenas => depth may never exceed 8 - 4 = 4
            assert pipe.set_prefetch_depth(99) == 4
            assert pipe.prefetch_depth == 4
            assert pipe.set_prefetch_depth(1) == 1
        finally:
            pipe.close()
            q.close()

    def test_drain_control_dials_are_honored(self):
        """The drain loop re-reads chunk/poll per iteration: with
        chunk=1 every pop returns at most one record."""
        q = RingBuffer(32)
        for i in range(6):
            q.put(_rec(i))
        q.put(EndOfStream(total_events=6))
        control = DrainControl(chunk=1, poll_s=0.001)
        pops = []
        real_get_batch = q.get_batch

        def spying_get_batch(max_items, timeout=None):
            pops.append(max_items)
            return real_get_batch(max_items, timeout=timeout)

        q.get_batch = spying_get_batch
        seen = 0
        for batch in batches_from_queue(q, 4, control=control):
            seen += batch.num_valid
        assert seen == 6
        assert pops and all(p == 1 for p in pops)
