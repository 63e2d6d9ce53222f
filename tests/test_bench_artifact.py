"""bench.py artifact + watchdog machinery regression tests.

Two real failures drove these defenses and must never come back:

- Round 4's driver artifact was unparseable (``parsed=null``) because
  the final JSON line outgrew the driver's tail window — the compact
  final line is now hard-capped and self-checked.
- Two round-5 full-bench runs were forfeited by one-stage section
  watchdogs ``os._exit``-ing on transient multi-minute backend stalls —
  a section overrun now soft-cancels (async ``SectionTimeout`` into the
  main thread) so later sections still run, with the hard exit reserved
  for stalls that outlive the grace period.

These tests run the REAL machinery (real Watchdog thread, real
``run_section``) on fake sections; no jax/TPU involved. ``bench_full.json``
writes land in the repo root but the file is gitignored and regenerated
by every bench run.
"""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


@pytest.fixture
def fresh_final():
    """Snapshot/restore the module-global artifact dict around each test."""
    snap = dict(bench._FINAL)
    yield bench._FINAL
    bench._FINAL.clear()
    bench._FINAL.update(snap)


def test_compact_line_parseable_and_capped_under_adversarial_growth(fresh_final):
    """The r4 regression: no matter how large the extras dict grows, the
    final emitted line must parse and stay under the hard cap."""
    f = bench._FINAL
    f["value"] = 28000.5
    f["vs_baseline"] = 44.8
    for i in range(500):  # ~50 KB of junk keys — far past the cap
        f[f"device_bulk_diag_{i}"] = [round(i * 0.1, 3)] * 40
    line = bench._compact_line()
    d = json.loads(line)  # must parse
    assert len(line) <= bench._COMPACT_CAP + 1  # +1: trailing newline
    # the four headline fields always survive
    assert d["metric"] == bench._FINAL["metric"]
    assert d["value"] == 28000.5
    assert d["unit"] == "frames/s"
    assert d["vs_baseline"] == 44.8


def test_compact_line_prefers_judged_keys_over_bulk(fresh_final):
    f = bench._FINAL
    for i in range(500):
        f[f"device_bulk_diag_{i}"] = [i] * 40
    # priority keys added AFTER the junk must still make the line
    f["device_resnet50_accuracy"] = 1.0
    f["device_unet_recall"] = 0.99
    d = json.loads(bench._compact_line())
    assert d["device_resnet50_accuracy"] == 1.0
    assert d["device_unet_recall"] == 0.99
    assert not any(k.startswith("device_bulk_diag_") for k in d)


def test_stalled_section_soft_cancels_and_later_sections_run(fresh_final):
    """The r5 stall scenario: a section blocked past its budget in
    resumable work is cancelled in place; the sections after it run and
    the cancel is recorded in the artifact."""
    wd = bench.Watchdog()
    hit = {}

    def stalls():
        for _ in range(600):  # a 60 s "stall" in interruptible slices
            time.sleep(0.1)
        raise AssertionError("watchdog never cancelled the stall")

    def later():
        hit["later"] = True

    t0 = time.monotonic()
    assert bench.run_section(wd, "fake-stall", stalls, budget_s=1.5) is False
    assert time.monotonic() - t0 < 30.0  # cancelled, not run to completion
    assert bench.run_section(wd, "fake-later", later, budget_s=30.0) is False
    assert hit.get("later") is True
    assert "fake-stall" in bench._FINAL["sections_soft_cancelled"]
    assert "fake-later" not in bench._FINAL.get("sections_soft_cancelled", "")


def test_near_deadline_completion_does_not_poison_next_section(fresh_final):
    """A section finishing right around its deadline must not leave a
    stale cancel that aborts the (healthy, in-budget) next section."""
    wd = bench.Watchdog()
    ran = {}

    def near_deadline():
        time.sleep(1.4)  # budget 1.5 s, watchdog polls every 0.5 s

    def healthy():
        ran["healthy"] = True

    bench.run_section(wd, "fake-near", near_deadline, budget_s=1.5)
    bench.run_section(wd, "fake-healthy", healthy, budget_s=30.0)
    assert ran.get("healthy") is True
    assert "fake-healthy" not in bench._FINAL.get("sections_soft_cancelled", "")


def test_section_exception_is_contained(fresh_final):
    """A failing diagnostic never sinks the artifact or later sections
    (reference behavior: errors become recorded skips, not stalls)."""
    wd = bench.Watchdog()
    ran = {}

    def boom():
        raise RuntimeError("diagnostic broke")

    def later():
        ran["later"] = True

    assert bench.run_section(wd, "fake-boom", boom, budget_s=30.0) is False
    bench.run_section(wd, "fake-after-boom", later, budget_s=30.0)
    assert ran.get("later") is True


def test_soft_cancel_grace_adapts_to_global_headroom(fresh_final):
    """The r5 outage lesson: with global budget to spare, the
    post-soft-cancel grace rides out the stall (up to the cap) instead
    of exiting at the fixed floor; with the global deadline near, it
    stays at the floor so the clean exit still beats the global fire."""
    watchdogs = []

    def stalls():
        for _ in range(600):
            time.sleep(0.1)

    try:
        wd = bench.Watchdog()
        watchdogs.append(wd)
        bench.run_section(wd, "fake-grace-rich", stalls, budget_s=1.0)
        # fresh watchdog: the full global budget of headroom
        assert wd._grace_s == bench.ADAPTIVE_GRACE_CAP_S

        wd2 = bench.Watchdog()
        watchdogs.append(wd2)
        # headroom below floor + margin -> the floor wins, not ~80 s
        wd2._global_deadline = time.monotonic() + 200.0
        bench.run_section(wd2, "fake-grace-poor", stalls, budget_s=1.0)
        assert wd2._grace_s == bench.SOFT_CANCEL_GRACE_S
    finally:
        # leaked poller threads live until process exit; push their
        # global deadlines out so none can os._exit(0) mid-suite and
        # silently truncate a green pytest run
        for w in watchdogs:
            w._global_deadline = time.monotonic() + 10**9


# ---------------------------------------------------------------------------
# Baseline regression gate (ISSUE 13): synthetic artifact pair
# ---------------------------------------------------------------------------

def test_compare_baseline_flags_regressed_key_rows():
    baseline = {
        "value": 28197.1,
        "host_passthrough_fps": 100.0,
        "device_resnet50_fps": 1750.0,
        "host_datapath_copies_per_frame": 1.0,
        "host_datapath_allocs_per_frame": 0.0,
        "serving": {"gateway_p99_ms": 290.0},
        "wire_compression_best_ratio": 3.19,
        "replication_kill_lost": 0,
    }
    current = dict(baseline)
    current.update(
        {
            "host_passthrough_fps": 70.0,           # -30% fps: regression
            "device_resnet50_fps": 1745.0,          # -0.3%: within noise
            "host_datapath_copies_per_frame": 1.5,  # zero-copy pin broken
            "serving": {"gateway_p99_ms": 500.0},   # p99 blown
            "wire_compression_best_ratio": 3.1,     # -3%: within noise
            "replication_kill_lost": 2,             # lost frames: always
        }
    )
    regs = bench.compare_baseline(current, baseline)
    by_key = {r["key"]: r for r in regs}
    assert set(by_key) == {
        "host_passthrough_fps",
        "host_datapath_copies_per_frame",
        "serving.gateway_p99_ms",
        "replication_kill_lost",
    }
    assert by_key["host_passthrough_fps"]["rule"] == "fps"
    assert by_key["host_passthrough_fps"]["change_pct"] == -30.0
    assert by_key["serving.gateway_p99_ms"]["rule"] == "latency_ms"
    assert by_key["host_datapath_copies_per_frame"]["rule"] == "copies_per_frame"
    assert by_key["replication_kill_lost"]["rule"] == "lost_frames"


def test_compare_baseline_model_counterexamples_zero_tolerance():
    # ISSUE 18: one counterexample is a protocol bug, not noise — and a
    # fleet that stopped exhausting its bounds proves nothing
    baseline = {"lint": {"model": {"counterexamples": 0,
                                   "exhausted_all": True,
                                   "states": 1917}}}
    current = {"lint": {"model": {"counterexamples": 1,
                                  "exhausted_all": False,
                                  "states": 1917}}}
    by_key = {r["key"]: r for r in bench.compare_baseline(current, baseline)}
    assert by_key["lint.model.counterexamples"]["rule"] == \
        "model_counterexamples"
    assert by_key["lint.model.exhausted_all"]["rule"] == "model_exhausted"
    # states is informational, not gated
    assert "lint.model.states" not in by_key
    assert bench.compare_baseline(dict(baseline), dict(baseline)) == []


def test_compare_baseline_clean_pair_is_empty():
    art = {"host_passthrough_fps": 100.0, "value": 5.0,
           "serving": {"gateway_p99_ms": 290.0}}
    assert bench.compare_baseline(dict(art), dict(art)) == []
    # improvements are never regressions
    better = {"host_passthrough_fps": 140.0, "value": 9.0,
              "serving": {"gateway_p99_ms": 150.0}}
    assert bench.compare_baseline(better, art) == []


def test_load_baseline_accepts_driver_round_and_full_artifact(tmp_path):
    rnd = tmp_path / "BENCH_r99.json"
    rnd.write_text(json.dumps({"n": 99, "parsed": {"value": 1.0}}))
    assert bench.load_baseline_artifact(str(rnd)) == {"value": 1.0}
    full = tmp_path / "bench_full.json"
    full.write_text(json.dumps({"value": 2.0}))
    assert bench.load_baseline_artifact(str(full)) == {"value": 2.0}


def test_apply_baseline_gate_embeds_regressions(fresh_final, tmp_path):
    base = tmp_path / "b.json"
    base.write_text(json.dumps({"host_passthrough_fps": 100.0}))
    extras = bench._FINAL
    extras["host_passthrough_fps"] = 50.0
    bench.apply_baseline_gate(extras, str(base))
    assert extras["baseline_compared"]["regression_count"] == 1
    assert extras["regressions"][0]["key"] == "host_passthrough_fps"
    # the gate is data, never an exception — even on garbage input
    bench.apply_baseline_gate(extras, str(tmp_path / "missing.json"))
    assert "baseline_error" in extras
