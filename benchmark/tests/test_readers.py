"""The benchmark's readings of PR 33, held to data whose answers are known.

    python3 -m pytest benchmark/tests -q        # CPU only, no JAX, seconds

The mid-mean that ``latency_p50_ms`` is (against the plain median it was),
``setup_s`` as wall clock less the sleeping child's stops, and the
child's gap finder. (The tier-1 suite runs ``check_manifest.py`` on the
tree: ``tests/test_phases.py``.)"""

import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stops  # noqa: E402
from benchmark.readers import (  # noqa: E402
    latency_midmean, latency_quantile, phase_time, setup_seconds, stopped_time,
)

RATE, BATCH, FRAMES = 120.0, 16, 3600


def paced_run(late_batch=None, late_s=0.110, seed=5):
    """A paced stream as the SFX loop serves it: frame ``i`` due at
    ``i / 120``, a batch's 16 results appended together ``hold`` after its
    last frame was due (143.7 ms, jitter 0.5 ms): 16 clusters of 225
    latencies, 8.33 ms apart."""
    rng = np.random.default_rng(seed)
    due = np.arange(FRAMES) / RATE
    hold = 0.1437 + 0.0005 * rng.standard_normal(FRAMES // BATCH)
    if late_batch is not None:
        hold[late_batch] += late_s
    done = np.repeat(due[BATCH - 1::BATCH] + hold, BATCH)
    ctx = types.SimpleNamespace()
    ctx.results = (np.zeros(FRAMES, np.int64), np.arange(FRAMES), done)
    ctx.generated = {"due": due}
    ctx.window = (0.0, FRAMES / RATE)
    return ctx


def test_synthetic_run_has_the_clusters_the_cell_has():
    ctx = paced_run()
    lat = np.sort(ctx.results[2] - ctx.generated["due"])
    steps = np.diff(lat)
    assert int(np.sum(steps > 0.004)) == BATCH - 1  # 16 clusters
    centres = lat.reshape(BATCH, FRAMES // BATCH).mean(axis=1)
    assert np.diff(centres) == pytest.approx(np.full(BATCH - 1, 1.0 / RATE), abs=1e-4)


@pytest.mark.parametrize("late_batch", [3, 100, 111, 200])
def test_midmean_stands_where_the_median_jumps(late_batch):
    """One batch 110 ms late (a stop on its launch): its lower-half frames
    cross the middle. The plain median moves by a good part of the cluster
    distance, the mid-mean by 8 / 360 of it."""
    calm, hit = paced_run(), paced_run(late_batch=late_batch)
    mid = [latency_midmean.read(c, lo=0.45, hi=0.55) for c in (calm, hit)]
    med = [latency_quantile.read(c, q=0.5) for c in (calm, hit)]
    assert abs(mid[1] - mid[0]) < 0.3
    assert abs(med[1] - med[0]) > 2.0
    # both aim at the same value: 62.5 ms + the mean hold
    assert mid[0] == pytest.approx(62.5 + 143.7, abs=0.3)
    assert med[0] == pytest.approx(mid[0], abs=0.3)


def test_p95_is_left_alone_by_the_same_late_batch():
    calm, hit = paced_run(), paced_run(late_batch=100)
    assert abs(latency_quantile.read(hit, q=0.95) - latency_quantile.read(calm, q=0.95)) < 1.0


def test_midmean_knows_nothing_of_batches():
    ctx = paced_run()
    flat = types.SimpleNamespace(
        results=(ctx.results[0], ctx.results[1], ctx.generated["due"] + np.linspace(0.1, 0.2, FRAMES)),
        generated=ctx.generated, window=ctx.window)
    assert latency_midmean.read(flat, lo=0.45, hi=0.55) == pytest.approx(150.0, abs=0.05)
    flat.window = (1e6, 2e6)  # no frame due: nothing to read
    assert latency_midmean.read(flat, lo=0.45, hi=0.55) is None


def test_midmean_counts_only_frames_due_in_the_window():
    ctx = paced_run()
    late = ctx.results[2].copy()
    late[:BATCH * 10] += 5.0  # the lead's backlog, before the window
    ctx.results = (ctx.results[0], ctx.results[1], late)
    ctx.window = (BATCH * 10 / RATE, FRAMES / RATE)
    assert latency_midmean.read(ctx, lo=0.45, hi=0.55) == pytest.approx(62.5 + 143.7, abs=0.3)


# -- setup_s = wall clock less stops ------------------------------------------

GAPS = [(101.0, 0.110), (104.0, 6.5), (119.95, 0.120), (125.0, 0.115), (140.0, 0.3)]


def setup_ctx(gaps=GAPS):
    # the TPU opens from 103.0 to 112.0; the long stop lies inside that call
    return types.SimpleNamespace(
        t_process=100.0, window=(120.0, 150.0), stops=gaps,
        phases={"imports": (100.0, 103.0), "device_open": (103.0, 112.0),
                "build": (112.0, 120.0)})


def test_setup_is_wall_less_the_stops_before_the_window():
    ctx = setup_ctx()
    # 0.110 + 6.5 whole; the stop that straddles the window's start is cut
    # there (0.05 of its 0.120); those after it are the window's
    want_stopped = 0.110 + 6.5 + 0.05
    assert setup_seconds.read(ctx) == pytest.approx(20.0 - want_stopped)
    assert stopped_time.read(ctx, span="setup") == pytest.approx(want_stopped)
    assert setup_seconds.read(ctx) + stopped_time.read(ctx, span="setup") == pytest.approx(20.0)
    assert stopped_time.read(ctx, span="window", scale=1000.0) == pytest.approx(70.0 + 115.0 + 300.0)


def test_no_stop_is_a_reading_and_no_record_is_none():
    assert setup_seconds.read(setup_ctx([])) == pytest.approx(20.0)
    assert stopped_time.read(setup_ctx([]), span="window", scale=1000.0) == 0.0
    assert setup_seconds.read(setup_ctx(None)) is None
    assert stopped_time.read(setup_ctx(None), span="setup") is None


def test_work_moved_into_setup_still_shows():
    base, more = setup_ctx(), setup_ctx()
    more.window = (123.0, 153.0)  # three more seconds of set-up, same stops
    assert setup_seconds.read(more) - setup_seconds.read(base) == pytest.approx(
        3.0 - 0.07)  # the straddling stop is now whole inside set-up


def test_stops_inside_cuts_and_places():
    assert stops.inside(GAPS, 120.0, 150.0) == [
        pytest.approx([0.0, 0.07]), pytest.approx([5.0, 0.115]), pytest.approx([20.0, 0.3])]
    assert stops.overlap_s(GAPS, 0.0, 1e9) == pytest.approx(sum(g[1] for g in GAPS))


# -- the child's gap finder ----------------------------------------------------

def recorded_ticks():
    """A tick series as the child records it on the chip machine: 2.1-2.6
    ms a tick, one oversleep of 12 ms (no stop), one stop of 112 ms and one
    of 6.4 s."""
    rng = np.random.default_rng(9)
    steps = 0.0021 + 0.0005 * rng.random(4000)
    steps[700] = 0.012
    steps[1500] = 0.112 + stops.TICK_S
    steps[2600] = 6.4 + stops.TICK_S
    return 500.0 + np.concatenate([[0.0], np.cumsum(steps)])


def test_gap_finder_on_a_recorded_series():
    ticks = recorded_ticks()
    gaps = stops.find_gaps(ticks)
    assert len(gaps) == 2
    (a0, s0), (a1, s1) = gaps
    assert s0 == pytest.approx(0.112) and s1 == pytest.approx(6.4)
    assert a0 == pytest.approx(ticks[1500] + stops.TICK_S)
    assert a1 == pytest.approx(ticks[2600] + stops.TICK_S)
    assert stops.gap_of(10.0, 10.0 + stops.GAP_S) is None  # at the threshold: no stop
    assert stops.find_gaps(ticks[:1]) == []


def test_the_child_itself_sees_a_stop():
    """The real child, stopped by a signal for 0.25 s: it notes one gap of
    that length, prints one JSON line at SIGTERM and exits 0."""
    import json
    import signal
    import subprocess
    import time

    child = subprocess.Popen(
        [sys.executable, "-S", os.path.join(ROOT, "benchmark", "stops.py")],
        stdin=subprocess.DEVNULL, stdout=subprocess.PIPE, text=True)
    try:
        time.sleep(0.4)
        child.send_signal(signal.SIGSTOP)
        time.sleep(0.25)
        child.send_signal(signal.SIGCONT)
        time.sleep(0.2)
        child.terminate()
        out, _ = child.communicate(timeout=10)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    assert child.returncode == 0
    record = json.loads(out)
    long_gaps = [g for g in record["gaps"] if g[1] > 0.2]
    assert len(long_gaps) == 1 and long_gaps[0][1] == pytest.approx(0.25, abs=0.05)
    assert record["ticks"] > 50 and record["last"] > record["first"]


def test_the_device_open_is_left_out_and_the_three_sum_to_the_wall():
    """As ``metrics/setup_s.json`` has it: the call that opens the TPU goes,
    frozen part (in ``setup_stopped_s``) and the rest (``device_open_s``)."""
    ctx = setup_ctx()
    stopped = stopped_time.read(ctx, span="setup")
    opened = phase_time.read(ctx, phase="device_open")
    assert opened == pytest.approx(9.0 - 6.5)
    setup = setup_seconds.read(ctx, leave_out=["device_open"])
    assert setup == pytest.approx(20.0 - stopped - opened)
    assert setup + stopped + opened == pytest.approx(20.0)
    # a freeze that is longer, inside the same call, moves neither
    longer = setup_ctx([(101.0, 0.110), (104.0, 7.9), (119.95, 0.120)])
    assert setup_seconds.read(longer, leave_out=["device_open"]) == pytest.approx(setup)
    # a phase the harness did not mark: nothing to read
    assert setup_seconds.read(ctx, leave_out=["no_such_phase"]) is None
    assert phase_time.read(ctx, phase="no_such_phase") is None


def test_the_manifests_setup_metric_reads_through_its_data_file():
    import json

    with open(os.path.join(ROOT, "benchmark", "metrics", "setup_s.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "setup_seconds"
    assert setup_seconds.read(setup_ctx(), **spec["args"]) == pytest.approx(20.0 - 6.66 - 2.5)
