"""The program's account of its own start (ISSUE 55): ``obs/jitwatch.py``
hears what JAX traced, lowered, loaded from the persistent cache and
compiled; the tracer spools it, the registry counts it, the serving loops
log it once and flag what comes later, and the benchmark's
``startup_spans`` reader cuts it to set-up, less the sandbox's stops."""

import json
import logging
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.readers import startup_spans  # noqa: E402
from benchmark.trace_reduce import union  # noqa: E402
from psana_ray_tpu.obs import jitwatch, trace_merge  # noqa: E402
from psana_ray_tpu.obs.flight import FLIGHT  # noqa: E402
from psana_ray_tpu.obs.jitwatch import (  # noqa: E402
    KIND_CACHE_LOAD, KIND_COMPILE, KIND_LOWER, KIND_TRACE, JitWatch,
)
from psana_ray_tpu.obs.registry import MetricsRegistry  # noqa: E402
from psana_ray_tpu.obs.tracing import Tracer  # noqa: E402

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"


def timed(w: JitWatch, event: str, seconds: float, fun: str, inside=()):
    """One timed region as JAX reports it: the start's scalar, whatever
    ran ``inside`` it, then the duration."""
    w._on_start(event, time.time(), fun_name=fun)
    for step in inside:
        step()
    w._on_duration(event, seconds, fun_name=fun)


def compiled(w: JitWatch, seconds: float, fun: str, hit=None):
    """A ``backend_compile`` as JAX reports it: the cache asked (unless
    ``hit`` is None: a request that does not use it), answering or not."""
    def cache():
        if hit is not None:
            w._on_event(ASKED)
        if hit:
            w._on_event(HIT)
            w._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", seconds / 2)
    timed(w, BACKEND, seconds, f"jit({fun})", inside=[cache])


@pytest.fixture
def watch():
    return JitWatch()


# -- the listener against JAX itself ----------------------------------------------

START = """
import json, sys
import jax, jax.numpy as jnp
from psana_ray_tpu.utils.jaxenv import configure_compile_cache
from psana_ray_tpu.obs.jitwatch import WATCH
assert configure_compile_cache() == sys.argv[1]
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
step = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
step(jnp.ones((8, 8))).block_until_ready()
rows, dropped = WATCH.record()
print(json.dumps({"snap": WATCH.snapshot(), "kinds": sorted({r[0] for r in rows}),
                  "dropped": dropped, "installed": WATCH.installed}))
"""


@pytest.fixture(scope="module")
def two_starts(tmp_path_factory):
    """The same program started twice over ONE fresh cache directory."""
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": cache,
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    out = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", START, cache], env=env, cwd=ROOT,
                              capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        out.append(json.loads(done.stdout.strip().splitlines()[-1]))
    return out


def test_the_first_start_over_a_fresh_cache_compiles(two_starts):
    first = two_starts[0]
    assert first["installed"] and first["dropped"] == 0
    assert first["snap"]["jit_cache_misses_total"] > 0
    assert first["snap"]["jit_compile_seconds_total"] > 0
    assert first["snap"]["jit_cache_hits_total"] == 0
    assert first["snap"]["jit_trace_seconds_total"] > 0 and first["snap"]["jit_lower_seconds_total"] > 0
    assert KIND_COMPILE in first["kinds"] and KIND_CACHE_LOAD not in first["kinds"]


def test_the_second_start_loads_what_the_first_compiled(two_starts):
    second = two_starts[1]
    assert second["snap"]["jit_cache_misses_total"] == 0
    assert second["snap"]["jit_compile_seconds_total"] == 0
    assert second["snap"]["jit_cache_hits_total"] > 0
    assert second["snap"]["jit_cache_load_seconds_total"] > 0
    # a warm start still traces and lowers: that is what it pays
    assert second["snap"]["jit_trace_seconds_total"] > 0 and second["snap"]["jit_lower_seconds_total"] > 0
    assert KIND_CACHE_LOAD in second["kinds"] and KIND_COMPILE not in second["kinds"]


def test_an_inner_jit_called_fifty_times_in_an_outer_one_is_counted_once(watch):
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def inner(x, k):  # a new trace for every k, each with its own nested sin and add
        for _ in range(4):
            x = jnp.sin(x) + k
        return x

    @jax.jit
    def outer(x):
        for k in range(50):
            x = inner(x, k)
        return x

    x = jnp.ones((4, 4))
    watch.install()
    try:
        t0 = time.monotonic()
        outer.trace(x)
        wall = time.monotonic() - t0
    finally:
        rows, dropped = watch.record()
        snap, by_fun = watch.snapshot(), watch.by_function()
        watch.uninstall()
    traces = [r for r in rows if r[0] == KIND_TRACE]
    assert dropped == 0 and sum(1 for r in traces if r[1] == "inner") == 50
    assert snap["jit_trace_seconds_total"] <= wall
    assert sum(r[3] - r[2] for r in traces) > wall  # the durations, summed, count inner twice
    # the rows' own union says the same as the counter, and all is on one clock
    spans = union((r[2], r[3]) for r in traces)
    assert sum(b - a for a, b in spans) == pytest.approx(snap["jit_trace_seconds_total"], rel=0.02)
    assert all(t0 - 1e-3 <= r[2] <= r[3] <= t0 + wall + 1e-3 for r in traces)
    # outer's OWN seconds leave out the fifty inner traces directly inside it
    n, total, own = by_fun[(KIND_TRACE, "outer")]
    inner_total = by_fun[(KIND_TRACE, "inner")][1]
    assert n == 1 and own == pytest.approx(total - inner_total, abs=1e-6) and own < 0.5 * total


def test_install_twice_registers_once(watch):
    from jax._src import monitoring

    before = len(monitoring.get_event_duration_listeners())
    try:
        assert watch.install() is watch.install()
        assert monitoring.get_event_duration_listeners().count(watch._on_duration) == 1
        assert monitoring.get_event_listeners().count(watch._on_event) == 1
        assert monitoring.get_scalar_listeners().count(watch._on_start) == 1
    finally:
        watch.uninstall()
    assert len(monitoring.get_event_duration_listeners()) == before
    watch.uninstall()  # and taking it back twice is no error


def test_the_module_s_install_is_the_registry_s_source_jit():
    w = jitwatch.install()
    assert w is jitwatch.WATCH and jitwatch.install() is w and w.installed
    compiled(w, 0.25, "served_step", hit=False)
    text = MetricsRegistry.default().render_prometheus()
    assert 'psana_ray_jit_cache_misses_total{source="jit"} 1' in text
    assert 'psana_ray_jit_compile_seconds_total{source="jit"} 0.25' in text
    assert "# TYPE psana_ray_jit_spans_dropped_total counter" in text


def test_configure_compile_cache_installs_the_listener(monkeypatch, tmp_path):
    from psana_ray_tpu.utils import jaxenv

    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert not jitwatch.WATCH.installed
    locations = jax.config.jax_include_full_tracebacks_in_locations
    try:
        jaxenv.configure_compile_cache()
        jaxenv.configure_compile_cache()
    finally:  # the call also sets how kernels are named, for good: put that back
        jax.config.update("jax_include_full_tracebacks_in_locations", locations)
    assert jitwatch.WATCH.installed and "jit" in MetricsRegistry.default().sources()


# -- what an event is counted as ----------------------------------------------------

def test_a_backend_compile_after_a_hit_is_a_load_and_after_a_miss_a_compile(watch):
    compiled(watch, 0.5, "a", hit=True)
    compiled(watch, 2.0, "b", hit=False)
    compiled(watch, 1.0, "c", hit=None)  # a request that does not use the cache
    compiled(watch, 0.25, "a", hit=True)  # the flag of b's miss does not outlive it
    rows, _ = watch.record()
    assert [(r[0], r[1]) for r in rows] == [
        (KIND_CACHE_LOAD, "a"), (KIND_COMPILE, "b"), (KIND_COMPILE, "c"), (KIND_CACHE_LOAD, "a")]
    snap = watch.snapshot()
    assert snap["jit_cache_hits_total"] == 2 and snap["jit_cache_misses_total"] == 2
    assert snap["jit_cache_load_seconds_total"] == pytest.approx(0.75)
    assert snap["jit_compile_seconds_total"] == pytest.approx(3.0)


def test_a_row_is_on_the_monotonic_clock_and_names_its_function_plainly(watch):
    timed(watch, LOWER, 0.125, "jit(keye_step)")
    now = time.monotonic()
    (kind, fun, t0, t1, thread), = watch.record()[0]
    assert (kind, fun) == (KIND_LOWER, "keye_step")
    assert t1 - t0 == pytest.approx(0.125) and now - 0.05 <= t1 <= now
    import threading
    assert thread == threading.get_ident()


def test_a_kernel_s_body_is_named_with_the_function_it_was_traced_in(watch):
    # pallas_call traces a kernel's body under its own jit, which JAX knows as "wrapped"
    body = [lambda: timed(watch, TRACE, 1.5, "wrapped", inside=[lambda: timed(watch, TRACE, 0.25, "wrapped")])]
    timed(watch, TRACE, 2.0, "keye_step", inside=[
        lambda: timed(watch, TRACE, 1.75, "select_keys", inside=body),
        lambda: timed(watch, TRACE, 0.125, "<lambda>")])
    timed(watch, TRACE, 0.5, "<lambda>")  # nobody around it: as JAX names it
    by_fun = watch.by_function()
    assert by_fun[(KIND_TRACE, "select_keys/wrapped")] == (2, pytest.approx(1.75), pytest.approx(1.5))
    assert by_fun[(KIND_TRACE, "keye_step/<lambda>")][0] == 1 and by_fun[(KIND_TRACE, "<lambda>")][0] == 1
    assert [r[1] for r in watch.record()[0]] == [
        "select_keys/wrapped", "select_keys/wrapped", "select_keys", "keye_step/<lambda>", "keye_step", "<lambda>"]


def test_other_events_of_jax_are_not_rows(watch):
    watch._on_duration("/jax/compilation_cache/compile_time_saved_sec", 3.0)
    watch._on_start("/jax/some/other_scalar", 1.0)
    watch._on_event("/jax/compilation_cache/tasks_using_cache")
    assert watch.record() == ([], 0) and watch.by_function() == {}


def test_nested_kinds_are_each_a_union_and_own_seconds_leave_the_nested_out(watch):
    # a step's trace of 1.0 s holds an eager op's trace 0.1, lower 0.2 and
    # compile 0.3, and an inner jit's trace 0.25 with a primitive's 0.05 in it
    eager = [lambda: timed(watch, TRACE, 0.1, "iota"), lambda: timed(watch, LOWER, 0.2, "jit(iota)"),
             lambda: compiled(watch, 0.3, "iota", hit=False)]
    inner = [lambda: timed(watch, TRACE, 0.25, "inner", inside=[lambda: timed(watch, TRACE, 0.05, "add")])]
    timed(watch, TRACE, 1.0, "step", inside=eager + inner)
    snap, by_fun = watch.snapshot(), watch.by_function()
    assert snap["jit_trace_seconds_total"] == pytest.approx(1.0)  # not 1.0 + 0.1 + 0.25 + 0.05
    assert snap["jit_lower_seconds_total"] == pytest.approx(0.2)
    assert snap["jit_compile_seconds_total"] == pytest.approx(0.3)
    assert by_fun[(KIND_TRACE, "step")] == (1, pytest.approx(1.0), pytest.approx(1.0 - 0.1 - 0.2 - 0.3 - 0.25))
    assert by_fun[(KIND_TRACE, "inner")] == (1, pytest.approx(0.25), pytest.approx(0.2))
    assert sum(v[2] for v in by_fun.values()) == pytest.approx(1.0)  # own seconds share out the wall


def test_two_threads_each_have_their_own_nesting(watch):
    import threading

    def work():
        timed(watch, TRACE, 0.5, "worker_step", inside=[lambda: timed(watch, TRACE, 0.125, "mul")])

    t = threading.Thread(target=work)
    timed(watch, TRACE, 1.0, "main_step", inside=[t.start, t.join])
    by_fun = watch.by_function()
    assert by_fun[(KIND_TRACE, "main_step")][2] == pytest.approx(1.0)  # the other thread's is not nested in it
    assert watch.snapshot()["jit_trace_seconds_total"] == pytest.approx(1.5)
    assert len({r[4] for r in watch.record()[0]}) == 2


def test_an_end_without_its_start_is_a_region_of_its_own(watch):
    watch._on_duration(TRACE, 0.5, fun_name="heard_late")  # installed while JAX was inside it
    timed(watch, LOWER, 0.25, "jit(next)")
    assert watch.snapshot()["jit_trace_seconds_total"] == pytest.approx(0.5)
    assert watch.by_function()[(KIND_LOWER, "next")] == (1, pytest.approx(0.25), pytest.approx(0.25))


def test_the_bound_drops_and_counts(watch):
    small = JitWatch(max_rows=5)
    for i in range(8):
        timed(small, TRACE, 0.01, f"f{i}")
    rows, dropped = small.record()
    assert len(rows) == 5 and dropped == 3 and [r[1] for r in rows] == [f"f{i}" for i in range(5)]
    snap = small.snapshot()
    assert snap["jit_spans_dropped_total"] == 3
    assert small.dropped_from() == pytest.approx(time.monotonic() - 0.01, abs=0.05) and watch.dropped_from() is None
    first_kept_end = rows[-1][3]
    assert small.dropped_from() >= first_kept_end - 0.011  # whole up to there: rows are kept in the order they end
    assert snap["jit_trace_seconds_total"] == pytest.approx(0.08)  # the sums are not bounded
    assert len(small.by_function()) == 8


# -- into the tracing that is there ------------------------------------------------

def test_configure_after_the_events_puts_them_in_the_spool_and_trace_merge_lists_them(watch, tmp_path):
    timed(watch, TRACE, 0.5, "keye_step")
    compiled(watch, 0.25, "keye_step", hit=True)
    tracer = Tracer(jit_watch=watch)
    tracer.configure(str(tmp_path), sample_every=1, process="consumer")
    try:
        compiled(watch, 1.5, "late_step", hit=False)  # after the spool's start: straight in
        assert tracer.snapshot()["phase_spans_total"] == 3
    finally:
        tracer.close()
    timed(watch, TRACE, 0.125, "after_the_close")  # nobody spools any more: no error, no row there
    spool = trace_merge.load_spool(tracer.spool_path)
    jit = [(s["n"], s["f"], round(s["b"] - s["a"], 6)) for s in spool["spans"]]
    assert jit == [(KIND_TRACE, "keye_step", 0.5), (KIND_CACHE_LOAD, "keye_step", 0.25),
                   (KIND_COMPILE, "late_step", 1.5)]
    assert spool["spans"][0]["a"] < spool["meta"]["start_mono"]  # from before the spool, same clock
    doc = trace_merge.merge([str(tmp_path)])
    listed = [(e["name"], e["args"]["fun"]) for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert sorted(listed) == sorted((n, f) for n, f, _ in jit)
    assert not [e for e in doc["traceEvents"] if e.get("cat") == "flow"]  # no frame's journey
    assert doc["otherData"]["tracks"][0]["spans"] == 3
    assert len(watch.record()[0]) == 4  # the listener keeps its own record either way


def test_a_tracer_of_a_test_s_own_spools_no_one_s_compiles(tmp_path):
    timed(jitwatch.WATCH, TRACE, 0.5, "someone_s_step")
    tracer = Tracer()
    tracer.configure(str(tmp_path), sample_every=1, process="t")
    tracer.close()
    assert trace_merge.load_spool(tracer.spool_path)["spans"] == []


# -- the operator's line, and what comes after it ------------------------------------

def test_a_compile_after_the_first_drained_batch_leaves_one_flight_event(watch, caplog):
    before = FLIGHT.count_of("recompile")
    watch.installed = True  # as after install(): there is an account to give
    timed(watch, TRACE, 0.5, "served_step")
    compiled(watch, 2.0, "served_step", hit=False)  # the start's own: no event
    assert FLIGHT.count_of("recompile") == before and not watch.serving
    with caplog.at_level(logging.INFO, logger="psana_ray_tpu.obs.jitwatch"):
        watch.first_result("InfeedPipeline.run")
        watch.first_result("InfeedPipeline.run")  # once a process
    assert watch.serving
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1
    assert "InfeedPipeline.run: first result" in lines[0] and "after the process started" in lines[0]
    assert "trace 0.500" in lines[0] and "compile 2.000" in lines[0] and "0 hits, 1 misses" in lines[0]
    assert "trace served_step 0.500" in lines[0] and "compiled here: served_step" in lines[0]
    timed(watch, TRACE, 0.125, "other_shape")  # a trace alone is no recompile
    compiled(watch, 1.5, "other_shape", hit=False)
    assert FLIGHT.count_of("recompile") == before + 1
    last = [e for e in FLIGHT.events() if e["kind"] == "recompile"][-1]
    assert (last["fun_name"], last["seconds"], last["hit"]) == ("other_shape", 1.5, False)
    compiled(watch, 0.25, "third_shape", hit=True)  # a load in mid-stream is one too, and says so
    assert [e for e in FLIGHT.events() if e["kind"] == "recompile"][-1]["hit"] is True


def test_the_infeed_loop_gives_the_account_once_and_flags_a_later_compile(caplog):
    import jax
    import jax.numpy as jnp

    from psana_ray_tpu.infeed import InfeedPipeline
    from psana_ray_tpu.records import EndOfStream, FrameRecord
    from psana_ray_tpu.transport import RingBuffer

    w = jitwatch.install()
    q = RingBuffer(maxsize=16)
    for i in range(12):
        q.put(FrameRecord(0, i, np.full((2, 8, 16), float(i), np.float32), 9.0))
    q.put(EndOfStream(total_events=12))
    first = jax.jit(lambda x: jnp.sum(x * 2.0), )
    other = jax.jit(lambda x: jnp.sum(jnp.cos(x) - 3.0))
    seen = []

    def step(batch):
        seen.append(batch.batch_id)
        return (first if len(seen) < 3 else other)(batch.frames)  # the third batch meets a new program

    before = FLIGHT.count_of("recompile")
    with caplog.at_level(logging.INFO, logger="psana_ray_tpu.obs.jitwatch"):
        assert InfeedPipeline(q, batch_size=4, poll_interval_s=0.001).run(step, block_until_ready=True) == 12
    lines = [r.getMessage() for r in caplog.records if "first result" in r.getMessage()]
    assert len(lines) == 1 and lines[0].startswith("InfeedPipeline.run: first result")
    assert w.serving and FLIGHT.count_of("recompile") > before
    names = {e["fun_name"] for e in FLIGHT.events() if e["kind"] == "recompile"}
    assert "<lambda>" in names


def test_without_the_listener_the_loops_first_result_says_nothing(watch, caplog):
    with caplog.at_level(logging.INFO, logger="psana_ray_tpu.obs.jitwatch"):
        watch.first_result("SfxPipeline.run")
    assert watch.serving and not caplog.records


# -- the benchmark's reader ------------------------------------------------------------

# set-up from 100 to 120 (the window's start); warm_up is [110, 118); the
# sandbox stood still over [104.0, 104.5) and [112.0, 113.0)
GAPS = [(104.0, 0.5), (112.0, 1.0), (125.0, 0.2)]
ROWS = [
    (KIND_TRACE, "iota", 103.75, 104.75, 1),            # 1.0 s, half of it stopped
    (KIND_TRACE, "step", 110.5, 113.5, 1),              # 3.0 s, a stop of 1.0 across it
    (KIND_TRACE, "inner", 111.0, 111.5, 1),             # nested in step: counted once
    (KIND_LOWER, "step", 113.5, 114.0, 1),
    (KIND_CACHE_LOAD, "step", 114.0, 114.75, 1),
    (KIND_TRACE, "watcher", 114.25, 115.0, 2),          # another thread, at the same time
    (KIND_COMPILE, "odd_one", 115.0, 115.5, 1),
    (KIND_COMPILE, "served_late", 119.5, 121.0, 1),     # ends inside the window: cut at 120
    (KIND_TRACE, "check", 160.0, 170.0, 1),             # after the window: the check's, not set-up's
]


def setup_ctx(gaps=GAPS):
    return types.SimpleNamespace(
        t_process=100.0, window=(120.0, 150.0), stops=gaps,
        phases={"build": (105.0, 110.0), "warm_up": (110.0, 118.0), "lead": (118.0, 120.0)})


def test_a_kind_is_its_union_in_set_up_less_the_stops_across_it():
    ctx = setup_ctx()
    # iota 1.0 - 0.5 stopped; step 3.0 - 1.0 stopped (inner inside it); the watcher's 0.75
    assert startup_spans.account(ROWS, ctx, kind=KIND_TRACE) == pytest.approx(0.5 + 2.0 + 0.75)
    assert startup_spans.account(ROWS, ctx, kind=KIND_LOWER) == pytest.approx(0.5)
    assert startup_spans.account(ROWS, ctx, kind=KIND_CACHE_LOAD) == pytest.approx(0.75)
    assert startup_spans.account(ROWS, ctx, kind=KIND_COMPILE) == pytest.approx(0.5 + 0.5)
    assert startup_spans.account(ROWS, setup_ctx([]), kind=KIND_TRACE) == pytest.approx(1.0 + 3.0 + 0.75)


def test_the_misses_are_the_compiles_that_end_in_set_up():
    assert startup_spans.account(ROWS, setup_ctx(), kind=KIND_COMPILE, count=True) == 1
    assert startup_spans.account(ROWS[:4], setup_ctx(), kind=KIND_COMPILE, count=True) == 0


def test_the_rest_is_warm_up_less_its_stops_less_every_kind_inside_it():
    ctx = setup_ctx()
    # warm_up 8.0 - 1.0 stopped = 7.0; all kinds cover [110.5, 115.5) whatever the thread: 5.0 - 1.0
    assert startup_spans.account(ROWS, ctx, rest_of="warm_up") == pytest.approx(7.0 - 4.0)
    assert startup_spans.account(ROWS, ctx, rest_of="no_such_phase") is None
    assert startup_spans.account([], ctx, rest_of="warm_up") == pytest.approx(7.0)


@pytest.fixture
def heard(monkeypatch):
    """The process's listener as after a start that heard ``ROWS``."""
    w = JitWatch()
    w.installed = True
    w._rows = list(ROWS)
    monkeypatch.setattr(jitwatch, "WATCH", w)
    return w


def test_the_six_metric_files_read_through_the_reader(heard):
    from benchmark import run as bench_run

    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {e["name"]: e for e in manifest["per_layer"] if e["name"].startswith("startup_")}
    assert sorted(entries) == ["startup_cache_load_s", "startup_cache_misses", "startup_compile_s",
                               "startup_lower_s", "startup_rest_s", "startup_trace_s"]
    assert all("workloads" not in e and e["moves"] == "setup_s" and e["layer"] == "device program"
               for e in entries.values())
    got = {name: bench_run.read_metric(setup_ctx(), e) for name, e in entries.items()}
    assert got == {"startup_trace_s": pytest.approx(3.25), "startup_lower_s": pytest.approx(0.5),
                   "startup_cache_load_s": pytest.approx(0.75), "startup_compile_s": pytest.approx(1.0),
                   "startup_cache_misses": 1, "startup_rest_s": pytest.approx(3.0)}


def test_a_record_that_dropped_a_row_of_set_up_is_not_read(heard, capsys):
    heard._dropped, heard._dropped_from = 2, 119.0  # the earliest dropped row began before the window
    assert startup_spans.read(setup_ctx(), kind=KIND_TRACE) is None
    assert startup_spans.read(setup_ctx(), rest_of="warm_up") is None
    assert "dropped 2 rows" in capsys.readouterr().err


def test_rows_dropped_after_the_window_opened_take_nothing_from_the_start(heard, capsys):
    heard._dropped, heard._dropped_from = 4000, 171.5  # the reference check's own compiles overran the bound
    assert startup_spans.read(setup_ctx(), kind=KIND_TRACE) == pytest.approx(3.25)
    assert startup_spans.read(setup_ctx(), kind=KIND_COMPILE, count=True) == 1
    assert capsys.readouterr().err == ""


def test_a_program_without_the_listener_gives_nothing(heard, monkeypatch):
    heard.installed = False  # configure_compile_cache() never ran
    assert startup_spans.read(setup_ctx(), kind=KIND_TRACE) is None
    heard.installed = True
    ctx = setup_ctx()
    ctx.stops = None  # and a run without the sleeping child's record has no set-up to cut
    assert startup_spans.read(ctx, kind=KIND_TRACE) is None
    monkeypatch.setitem(sys.modules, "psana_ray_tpu.obs.jitwatch", None)  # the parent's package
    assert startup_spans.read(setup_ctx(), kind=KIND_TRACE) is None
