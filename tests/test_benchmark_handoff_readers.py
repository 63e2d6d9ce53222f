"""The benchmark's readers of the hand-off between host and device
(ISSUE 40), held to a trace and a spool written by hand, small enough to
work out on paper: ``span_time_share`` (a thread's account from the
spool's phase spans), ``trace_idle_under`` (the device's idle time under
named host regions), ``trace_lead`` (launch -> the step's start), and the
thirteen metric files through their readers. The cases of
``benchmark/tests/test_readers.py`` (PR 33's readings) run from here too:
tier-1 collects ``tests/`` alone."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import span_time_share, trace_idle_under, trace_lead  # noqa: E402
from benchmark.tests.test_readers import (  # noqa: E402,F401  (collected here)
    test_gap_finder_on_a_recorded_series,
    test_midmean_counts_only_frames_due_in_the_window,
    test_midmean_knows_nothing_of_batches,
    test_midmean_stands_where_the_median_jumps,
    test_no_stop_is_a_reading_and_no_record_is_none,
    test_p95_is_left_alone_by_the_same_late_batch,
    test_setup_is_wall_less_the_stops_before_the_window,
    test_stops_inside_cuts_and_places,
    test_synthetic_run_has_the_clusters_the_cell_has,
    test_the_child_itself_sees_a_stop,
    test_the_device_open_is_left_out_and_the_three_sum_to_the_wall,
    test_the_manifests_setup_metric_reads_through_its_data_file,
    test_work_moved_into_setup_still_shows,
)
from psana_ray_tpu.utils.metrics import PipelineMetrics  # noqa: E402

MS = 1e6  # the profiler's clock is in ns


def hand_trace():
    """Traced window 0..1000 ms, one chip. The step runs at [15,200),
    [400,600) and [813,1000): the chip is idle in [0,15), [200,400) and
    [600,813).

    Serving thread: ``launch`` at 1 (14 ms before its run), 387 (13 before)
    and 797 (16 before); ``device_wait`` [300,500) covers HALF of the gap
    [200,400), the other half lies under nothing of that thread;
    ``infeed_wait`` [600,650). A second serving thread (fan-in):
    ``device_wait`` [750,850), of which [750,813) is idle.
    Prefetch thread: ``device_put`` [150,250) and [-50,5) (clipped at the
    window's edge); its watcher: ``h2d_tail`` [240,320) and [700,900)."""
    step = [("jit_step(7)", 15 * MS, 185 * MS), ("jit_step(7)", 400 * MS, 200 * MS),
            ("jit_step(7)", 813 * MS, 187 * MS)]
    return tr.Trace(
        device={0: {"XLA Ops": [("%op = f32[8]{0} fusion(x)", s, d) for _, s, d in step],
                    "XLA Modules": step + [("jit_other(1)", 2 * MS, 1 * MS)]}},
        host={
            "serving": [("bench.window", 0.0, 1000 * MS),
                        ("stage.launch", 1 * MS, 2.3 * MS), ("stage.device_wait", 300 * MS, 200 * MS),
                        ("stage.launch", 387 * MS, 2 * MS), ("stage.infeed_wait", 600 * MS, 50 * MS),
                        ("stage.launch", 797 * MS, 2 * MS)],
            "serving#2": [("stage.device_wait", 750 * MS, 100 * MS)],
            "prefetch": [("stage.device_put", -50 * MS, 55 * MS), ("stage.device_put", 150 * MS, 100 * MS)],
            "watcher": [("stage.h2d_tail", 240 * MS, 80 * MS), ("stage.h2d_tail", 700 * MS, 200 * MS)],
        },
    )


def trace_ctx():
    trace = hand_trace()
    return types.SimpleNamespace(
        trace=trace, trace_window=tr.window_of(trace, "bench.window"),
        cfg={"trace_names": {"step": "jit_step"}})


class TestIdleUnder:
    def test_the_gap_half_under_device_wait_counts_half(self):
        # [300,400) of the gap [200,400), and the second thread's [750,813)
        assert trace_idle_under.read(trace_ctx(), regions=["stage.device_wait"]) == pytest.approx(
            (100 + 63) / 1000 * 100)

    def test_one_threads_region_alone(self):
        assert trace_idle_under.read(trace_ctx(), regions=["stage.infeed_wait"]) == pytest.approx(5.0)

    def test_two_names_are_a_union_and_the_windows_edge_clips(self):
        # device_put [0,5) + ([200,250) U [240,320)) = [200,320) + h2d_tail [700,813)
        assert trace_idle_under.read(
            trace_ctx(), regions=["stage.device_put", "stage.h2d_tail"]
        ) == pytest.approx((5 + 120 + 113) / 1000 * 100)

    def test_by_name_a_gap_under_two_threads_counts_for_both(self):
        # the serving threads' 163 ms and the transfers' 238 overlap in [300,320) and [750,813)
        ctx = trace_ctx()
        both = trace_idle_under.read(
            ctx, regions=["stage.device_wait", "stage.device_put", "stage.h2d_tail"])
        assert both == pytest.approx((163 + 238 - 20 - 63) / 1000 * 100)

    def test_the_shares_never_pass_the_idle_share(self):
        from benchmark.readers import trace_idle_share

        ctx = trace_ctx()
        idle = trace_idle_share.read(ctx)
        assert idle == pytest.approx((15 + 200 + 213) / 1000 * 100)
        launched = trace_idle_under.read(ctx, regions=["stage.device_wait"])
        unfed = trace_idle_under.read(ctx, regions=["stage.infeed_wait"])
        assert launched + unfed <= idle

    def test_a_trace_without_one_of_the_regions_gives_nothing(self):
        ctx = trace_ctx()
        assert trace_idle_under.read(ctx, regions=["stage.device_put", "stage.no_such"]) is None
        ctx.trace = None
        assert trace_idle_under.read(ctx, regions=["stage.device_wait"]) is None


class TestLead:
    def test_each_run_against_the_latest_launch_before_it(self):
        # 14, 13 and 16 ms: the median is the first run's
        assert trace_lead.read(trace_ctx(), step="@step", launch="stage.launch") == pytest.approx(14.0)

    def test_a_run_before_any_launch_or_cut_by_the_window_is_left_out(self):
        ctx = trace_ctx()
        ctx.trace.device[0]["XLA Modules"].append(("jit_step(7)", 0.5 * MS, 0.1 * MS))  # no launch yet
        ctx.trace.device[0]["XLA Modules"].append(("jit_step(7)", 990 * MS, 20 * MS))  # cut
        ctx.trace.device[0]["XLA Modules"].sort(key=lambda e: e[1])
        assert trace_lead.read(ctx, step="@step", launch="stage.launch") == pytest.approx(14.0)

    def test_no_launch_region_no_reading(self):
        assert trace_lead.read(trace_ctx(), step="@step", launch="stage.no_such") is None


# -- the spool -------------------------------------------------------------------

def span(name, a, b, **more):
    return {"t": "s", "id": more.pop("id", 1), "n": name, "a": a, "b": b, **more}


def hand_spool(tmp_path, tally=None):
    """Window 100..110 s. The prefetch thread: ``queue_wait`` [98,101)
    (1 s inside), [105,106), [109.5,112) (0.5 inside) and [120,121)
    (none); ``copy`` [101,103); ``decode`` [103,103.5); ``device_put``
    [103.5,104.5); ``prefetch_full`` [104.5,105) and [106,109.5). The
    watcher's ``h2d`` of batch 5: [103.5,104.55), 50 ms longer than the call. A frame's hop span
    called ``batch`` must not count anywhere."""
    rows = [
        {"t": "m", "process": "t"},
        span("stage.queue_wait", 98.0, 101.0, k=3), span("stage.copy", 101.0, 103.0, k=3, y=12),
        span("stage.decode", 103.0, 103.5, k=3), span("stage.device_put", 103.5, 104.5, id=5, k=4),
        span("h2d", 103.5, 104.55, id=5, k=4), span("stage.h2d_tail", 104.5, 104.55, id=5, k=4),
        span("stage.prefetch_full", 104.5, 105.0, id=5, k=4), span("stage.queue_wait", 105.0, 106.0, k=1),
        span("stage.prefetch_full", 106.0, 109.5, id=6, k=4), span("stage.queue_wait", 109.5, 112.0, k=1),
        span("stage.queue_wait", 120.0, 121.0, k=1), span("batch", 100.0, 109.0, id=77, j=5),
        tally or {"t": "d", "spans": 1, "dropped": 0, "phase_spans": 11, "phase_dropped": 0},
    ]
    path = tmp_path / "bench.trace.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return types.SimpleNamespace(spool_path=str(path), window=(100.0, 110.0))


class TestSpanTimeShare:
    def test_spans_are_clipped_to_the_window_not_counted_by_their_end(self, tmp_path):
        ctx = hand_spool(tmp_path)
        assert span_time_share.read(ctx, spans=["stage.queue_wait"]) == pytest.approx(25.0)

    def test_a_threads_three_accounts_add_up(self, tmp_path):
        ctx = hand_spool(tmp_path)
        starved = span_time_share.read(ctx, spans=["stage.queue_wait"])
        busy = span_time_share.read(ctx, spans=["stage.decode", "stage.copy", "stage.device_put"])
        blocked = span_time_share.read(ctx, spans=["stage.prefetch_full"])
        assert (starved, busy, blocked) == pytest.approx((25.0, 35.0, 40.0))
        assert span_time_share.read(ctx, spans=["stage.copy"]) == pytest.approx(20.0)

    def test_a_spool_without_one_of_the_names_gives_nothing(self, tmp_path):
        ctx = hand_spool(tmp_path)
        assert span_time_share.read(ctx, spans=["stage.copy", "stage.no_such"]) is None
        ctx.spool_path = None
        assert span_time_share.read(ctx, spans=["stage.copy"]) is None

    def test_a_dropped_phase_span_is_refused_and_said(self, tmp_path, capsys):
        ctx = hand_spool(tmp_path, tally={"t": "d", "spans": 1, "dropped": 7, "phase_spans": 11,
                                          "phase_dropped": 2})
        assert span_time_share.read(ctx, spans=["stage.queue_wait"]) is None
        assert "dropped 2 phase spans" in capsys.readouterr().err

    def test_dropped_frame_spans_alone_do_not_refuse(self, tmp_path):
        ctx = hand_spool(tmp_path, tally={"t": "d", "spans": 1, "dropped": 7, "phase_spans": 11,
                                          "phase_dropped": 0})
        assert span_time_share.read(ctx, spans=["stage.queue_wait"]) == pytest.approx(25.0)


# -- the thirteen metric files, each through its reader ------------------------------

def everything(tmp_path):
    ctx = hand_spool(tmp_path)
    for k, v in vars(trace_ctx()).items():
        setattr(ctx, k, v)
    m = PipelineMetrics()
    for _ in range(4):
        m.observe_batch(16, 0.06)
    m.drained_ahead.add(3)
    m.frames_staged_ahead.add(60)  # 15 of each batch's 16
    for s in (0.0015, 0.0017, 0.0021):
        m.stages.observe("append", s)
    ctx.metrics = m
    return ctx


@pytest.mark.parametrize("metric,want", [
    ("h2d_ms.hit", 1050.0),
    ("prefetch_starved_share.hit", 25.0),
    ("prefetch_busy_share.hit", 35.0),
    ("prefetch_copy_share.hit", 20.0),
    ("prefetch_blocked_share.hit", 40.0),
    ("idle_launched_share.hit", 16.3),
    ("idle_unfed_share.hit", 5.0),
    ("idle_in_h2d_share.hit", 23.8),
    ("launch_lead_ms.paced", 14.0),
    ("ready_lag_ms.paced", 125.0),  # step ends 200 -> device_wait 300; 600 -> 750: median
    ("drained_ahead_share.paced", 75.0),
    ("staged_ahead_share.paced", 93.75),  # PR 43: 60 of 64 frames were put before their launch
    ("append_ms", 1.7),
    ("append_ms.paced", 1.7),
])
def test_each_new_metric_file_reads_the_hand_made_run(tmp_path, metric, want):
    from benchmark import run as bench

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["per_layer"] if e["name"] == metric)
    assert bench.read_metric(everything(tmp_path), entry) == pytest.approx(want)


def test_a_program_without_the_staging_counter_leaves_its_share_out(tmp_path):
    """PR 43's parent counts no ``frames_staged_ahead_total``: the share's
    reader finds nothing there, returns nothing and does not raise, and
    the counter's neighbour reads as it did."""
    from benchmark import run as bench

    ctx = everything(tmp_path)
    snapshot = ctx.metrics.snapshot()
    del snapshot["frames_staged_ahead_total"]
    ctx.metrics = type("Parent", (), {"snapshot": lambda self: snapshot})()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {e["name"]: e for e in json.load(f)["per_layer"]}
    assert bench.read_metric(ctx, entries["staged_ahead_share.paced"]) is None
    assert bench.read_metric(ctx, entries["drained_ahead_share.paced"]) == pytest.approx(75.0)


def test_the_parent_has_nothing_to_read_and_nothing_raises(tmp_path):
    """A program without the watcher, the two phase names and the tally
    line (this PR's parent): the readers of what it lacks leave their
    metrics out, the others read."""
    from benchmark import run as bench

    ctx = everything(tmp_path)
    rows = [json.loads(s) for s in open(ctx.spool_path)]
    keep = [r for r in rows if r["t"] != "d" and r.get("n") not in (
        "h2d", "stage.h2d_tail", "stage.copy", "stage.decode")]
    with open(ctx.spool_path, "w") as f:
        f.write("\n".join(json.dumps(r) for r in keep) + "\n")
    del ctx.trace.host["watcher"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {e["name"]: e for e in json.load(f)["per_layer"]}
    got = {name: bench.read_metric(ctx, entries[name]) for name in (
        "h2d_ms.hit", "prefetch_busy_share.hit", "prefetch_copy_share.hit",
        "idle_in_h2d_share.hit", "prefetch_starved_share.hit", "idle_launched_share.hit")}
    assert got == {
        "h2d_ms.hit": None, "prefetch_busy_share.hit": None, "prefetch_copy_share.hit": None,
        "idle_in_h2d_share.hit": None, "prefetch_starved_share.hit": pytest.approx(25.0),
        "idle_launched_share.hit": pytest.approx(16.3)}
