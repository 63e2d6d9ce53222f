#!/usr/bin/env python3
"""Hold ``BENCHMARK.json`` to the rules, and its bounds to the record.

    python3 benchmark/check_manifest.py            # exit 0 = hand it in

Two parts. (1) The manifest's form: exact keys, the character sets and
lengths of every name, unit and free-text field, files under ``paths``,
every cell with ``setup_s``, another end-to-end metric and a per-layer
metric, each ``moves`` reported wherever its metric is, at most a quarter
of the cells (or one) on four chips. (2) The bounds: from the proof runs
kept under ``benchmark/proof/<cell>.jsonl`` (two sets of runs, the same
seeds in both, every line a real TPU run's final line) it recomputes each
end-to-end metric's spread per cell — the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) over the median,
the wider of the two sets — and derives what a bound may be:

  never under 1%; at most 8 x the widest spread over the metric's cells,
  or 1% if that is more (above it the driver calls the bound too loose);
  at least 2 x every cell's spread (under it, too tight; judged as the
  driver does, on the mean of the two sets' spreads with each set's run
  farthest from its median left out); a share of the median, never an
  absolute amount, and at most 0.1.

``setup_s`` is exempt from the spread rule (the driver judges its median
only): its bound is 0.1. Since PR 33 it is process start -> window start
LESS the seconds in between during which a sleeping child beside the run
(``stops.py``) saw the sandbox stand still, and LESS what is left of the
call that opens the TPU: the freeze while the chip opens was 2-9 s of
18-28, the rest of that call a spin as long as the freeze was short, and
between them they made the medians wander by more than the bound with
nothing changed; what is left out is the per-layer ``setup_stopped_s``
and ``device_open_s``. The saturated cells' rates have a name and a bound
each, ``fps`` (``sfx_epix_saturated``, the device's cell) and ``fps.hit``
(``hit_epix_saturated``, the host path's, several times as noisy), each
from its own cell's spread by the rule above. ``latency_p50_ms`` is the
mean of the latencies between the 45th and 55th percentile
(``readers/latency_midmean.py``): the plain median of 16 clusters of
latencies stood on the edge between two of them, and one batch landing
late moved it by more than its bound (PERF.md, PR 24 finding 4). A bound outside its limits, a metric or cell
without proof runs, a proof line that is not a correct TPU run, or a
second set whose median is worse than the first's by more than the bound
fails the check; so does a proof run whose ``memory_peak_bytes`` is under
the floor ``peaks.json`` records (25% of a chip) or whose window is not
``run_seconds`` long. It prints, for each metric, the bound it recommends —
5 x the widest spread, cut to a tenth of a percent, at least 1%, at most
0.1 — and a bound typed in above what it prints fails too.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}

problems = []


def bad(msg: str) -> None:
    problems.append(msg)
    print(f"FAIL  {msg}")


def text_ok(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def check_form(m: dict) -> None:
    if set(m) != TOP_KEYS:
        bad(f"top-level keys {sorted(m)} are not exactly {sorted(TOP_KEYS)}")
    size = os.path.getsize(os.path.join(ROOT, "BENCHMARK.json"))
    if size > 64 * 1024:
        bad(f"BENCHMARK.json is {size} bytes, over 64 KiB")
    if not (1 <= len(m["command"]) <= 32 and all(text_ok(w) for w in m["command"])):
        bad("command is not 1..32 words of 1..200 characters")
    for w in m["command"]:
        if w.startswith("/") or ".." in w.split("/"):
            bad(f"command word {w!r} is absolute or leads out of the repo")
    if not (1 <= len(m["paths"]) <= 16 and all(PATH.match(p) for p in m["paths"])):
        bad("paths is not 1..16 relative paths of allowed characters")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        bad("run_seconds is not a whole number from 1 to 51")
    for p in m["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                rel = os.path.relpath(os.path.join(dirpath, fn), ROOT)
                if not PATH.match(rel) and not fn.endswith(".pyc"):
                    bad(f"file name {rel!r} has a character outside letters, digits, _ . - /")

    names = set()

    def name_ok(n, what):
        if not (isinstance(n, str) and NAME.match(n)):
            bad(f"{what} name {n!r} is not 1..64 of letters, digits, _ . - starting with "
                f"a letter, digit or _")

    def unique(n, what):
        if (what, n) in names:
            bad(f"two {what}s are called {n!r}")
        names.add((what, n))

    configs = {}
    if not 1 <= len(m["configs"]) <= 24:
        bad("configs is not 1..24 entries")
    files = set()
    for c in m["configs"]:
        if set(c) != CONFIG_KEYS:
            bad(f"config {c.get('name')!r} has keys {sorted(c)}, not {sorted(CONFIG_KEYS)}")
        name_ok(c["name"], "config")
        unique(c["name"], "config")
        configs[c["name"]] = c
        if not text_ok(c["source"]) or not text_ok(c["why"]):
            bad(f"config {c['name']!r}: source and why must be 1..200 characters on one line")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in m["paths"]):
            bad(f"config {c['name']!r}: file {c['file']!r} is not under paths")
        if c["file"] in files or not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad(f"config {c['name']!r}: file {c['file']!r} is missing or another config's")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            bad(f"config {c['name']!r}: more than 16 keys reduced")
        for k in c["reduced"]:
            name_ok(k, "reduced key")

    cells = {}
    if not 1 <= len(m["workloads"]) <= 24:
        bad("workloads is not 1..24 cells")
    pairs = set()
    for w in m["workloads"]:
        if set(w) != CELL_KEYS:
            bad(f"cell {w.get('name')!r} has keys {sorted(w)}, not {sorted(CELL_KEYS)}")
        name_ok(w["name"], "cell")
        name_ok(w["traffic"], "traffic")
        unique(w["name"], "cell")
        cells[w["name"]] = w
        if w["config"] not in configs:
            bad(f"cell {w['name']!r} names no configuration of the manifest")
        if (w["config"], w["traffic"]) in pairs:
            bad(f"cell {w['name']!r} repeats a pair of configuration and traffic")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad(f"cell {w['name']!r}: chips must be 1 or 4")
        if not text_ok(w["why"]):
            bad(f"cell {w['name']!r}: why must be 1..200 characters on one line")
        if not os.path.isfile(os.path.join(HERE, "traffic", w["traffic"] + ".json")):
            bad(f"cell {w['name']!r}: no traffic file benchmark/traffic/{w['traffic']}.json")
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad(f"{four} cells ask for 4 chips; at most {max(1, len(cells) // 4)} may")
    for c in configs:
        if not any(w["config"] == c for w in m["workloads"]):
            bad(f"config {c!r} is used by no cell")

    def metric_cells(e):
        return list(e["workloads"]) if "workloads" in e else list(cells)

    e2e = {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        bad("end_to_end is not 1..16 metrics")
    for e in m["end_to_end"]:
        if set(e) - {"workloads"} != E2E_KEYS:
            bad(f"end-to-end metric {e.get('name')!r} has keys {sorted(e)}")
        name_ok(e["name"], "metric")
        unique(e["name"], "metric")
        e2e[e["name"]] = e
        if not UNIT.match(str(e["unit"])):
            bad(f"metric {e['name']!r}: unit {e['unit']!r} is not 1..16 of letters, digits, _ / % . -")
        if e["better"] not in ("lower", "higher"):
            bad(f"metric {e['name']!r}: better must be lower or higher")
        if e["source"] not in ("host_clock", "device_trace"):
            bad(f"end-to-end metric {e['name']!r}: source must be host_clock or device_trace")
        b = e["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool) and 0 < b <= 0.1):
            bad(f"metric {e['name']!r}: bound {b!r} is not a share of the median in (0, 0.1] "
                f"(an absolute amount is not a bound)")
        for w in metric_cells(e):
            if w not in cells:
                bad(f"metric {e['name']!r} lists an unknown cell {w!r}")
    if "setup_s" not in e2e:
        bad("no end-to-end metric is called setup_s")
    elif "workloads" in e2e["setup_s"]:
        bad("setup_s must be reported by every cell (no workloads key)")

    if not 1 <= len(m["per_layer"]) <= 128:
        bad("per_layer is not 1..128 metrics")
    for e in m["per_layer"]:
        if set(e) - {"workloads"} != LAYER_KEYS:
            bad(f"per-layer metric {e.get('name')!r} has keys {sorted(e)}")
        name_ok(e["name"], "metric")
        unique(e["name"], "metric")
        if not UNIT.match(str(e["unit"])):
            bad(f"metric {e['name']!r}: unit {e['unit']!r} is not 1..16 of letters, digits, _ / % . -")
        if e["better"] not in ("lower", "higher"):
            bad(f"metric {e['name']!r}: better must be lower or higher")
        if e["source"] not in SOURCES:
            bad(f"metric {e['name']!r}: source {e['source']!r}")
        if not text_ok(e["layer"]):
            bad(f"metric {e['name']!r}: layer must be 1..200 characters on one line")
        target = e2e.get(e["moves"])
        if target is None:
            bad(f"metric {e['name']!r} moves {e['moves']!r}, which is no end-to-end metric")
        else:
            missing = set(metric_cells(e)) - set(metric_cells(target))
            if missing:
                bad(f"metric {e['name']!r} moves {e['moves']!r}, not reported in {sorted(missing)}")
        for w in metric_cells(e):
            if w not in cells:
                bad(f"metric {e['name']!r} lists an unknown cell {w!r}")
    for e in m["end_to_end"] + m["per_layer"]:
        if not os.path.isfile(os.path.join(HERE, "metrics", e["name"] + ".json")):
            bad(f"metric {e['name']!r} has no data file benchmark/metrics/{e['name']}.json")
    for w in cells:
        others = [e for e in m["end_to_end"] if e["name"] != "setup_s" and w in metric_cells(e)]
        layers = [e for e in m["per_layer"] if w in metric_cells(e)]
        if not others or not layers:
            bad(f"cell {w!r} needs setup_s, another end-to-end metric and a per-layer metric")


# ---------------------------------------------------------------------------
# bounds from the record
# ---------------------------------------------------------------------------

def spread(values) -> float:
    """Distance between the first and third quartile over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values) -> list:
    """The set without its run farthest from the median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def load_proof(cell: str, peaks: dict, run_seconds: int) -> dict:
    """``{set: {metric: [values in seed order]}}`` from the cell's proof
    lines; every line must be a correct, complete TPU run."""
    path = os.path.join(HERE, "proof", cell + ".jsonl")
    if not os.path.isfile(path):
        bad(f"cell {cell!r} has no proof runs ({os.path.relpath(path, ROOT)})")
        return {}
    sets, seeds = {}, {}
    with open(path, encoding="utf-8") as f:
        for n, line in enumerate(f, 1):
            rec = json.loads(line)
            res = rec.get("result")
            where = f"{cell} proof line {n}"
            if not res or rec.get("rc") != 0:
                bad(f"{where}: the run failed")
                continue
            dev = res["device"]
            if res.get("rehearsal") or dev["platform"] != "tpu" or dev["kind"] not in peaks:
                bad(f"{where}: not a run on a TPU of the peak table ({dev})")
                continue
            if not res["correct"] or res["failed"] != 0 or res.get("trace") != 0:
                bad(f"{where}: correct={res['correct']} failed={res['failed']} trace={res.get('trace')}")
                continue
            floor = peaks[dev["kind"]].get("cell_memory_floor_bytes", 0)
            if dev["memory_peak_bytes"] < floor:
                bad(f"{where}: memory_peak_bytes {dev['memory_peak_bytes']} is under the floor "
                    f"{floor} (25% of a chip): the cell is too small to stand for a deployment")
                continue
            if res.get("seconds") != run_seconds:
                bad(f"{where}: ran {res.get('seconds')} s, the manifest's run_seconds is {run_seconds}")
                continue
            seeds.setdefault(rec["set"], []).append(rec["seed"])
            for name, mv in res["metrics"].items():
                sets.setdefault(rec["set"], {}).setdefault(name, []).append(mv["value"])
    if len(sets) < 2:
        bad(f"cell {cell!r}: proof needs two sets of runs, has {len(sets)}")
    elif len({tuple(sorted(s)) for s in seeds.values()}) != 1:
        bad(f"cell {cell!r}: the sets do not share their seeds: {seeds}")
    return sets


def check_bounds(m: dict) -> None:
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)["devices"]
    cells = [w["name"] for w in m["workloads"]]
    proof = {c: load_proof(c, peaks, m["run_seconds"]) for c in cells}
    print(f"\n{'metric':<18}{'cell':<26}{'n':>4}{'median 1':>12}{'median 2':>12}"
          f"{'spread 1':>10}{'spread 2':>10}{'trimmed':>10}")
    for e in m["end_to_end"]:
        name, b = e["name"], e["bound"]
        widest, tight_need = 0.0, 0.0
        for c in (e.get("workloads") or cells):
            sets = proof.get(c) or {}
            runs = [sets[k][name] for k in sorted(sets) if name in sets[k]]
            if len(runs) < 2 or min(len(r) for r in runs) < 3:
                bad(f"{name} in {c}: needs two sets of at least 3 runs, has {[len(r) for r in runs]}")
                continue
            s = [spread(r) for r in runs[:2]]
            t = statistics.mean(spread(trimmed(r)) for r in runs[:2])
            med = [statistics.median(r) for r in runs[:2]]
            print(f"{name:<18}{c:<26}{len(runs[0]):>4}{med[0]:>12.4f}{med[1]:>12.4f}"
                  f"{s[0]:>10.4%}{s[1]:>10.4%}{t:>10.4%}")
            widest = max(widest, max(s))
            tight_need = max(tight_need, 2.0 * t)
            worse = (med[1] - med[0]) / med[0] * (1 if e["better"] == "lower" else -1)
            if worse > b:
                bad(f"{name} in {c}: the second set's median is worse than the first's by "
                    f"{worse:.2%}, over the bound {b:.2%}")
            if name != "setup_s" and abs(worse) > b:
                bad(f"{name} in {c}: the two sets' medians differ by {abs(worse):.2%}, over "
                    f"the bound {b:.2%}, on the same code")
        if name == "setup_s":
            print(f"  -> setup_s: bound {b} (exempt from the spread rule; 0.1 is the rule)")
            if b != 0.1:
                bad("setup_s: the bound is 0.1")
            continue
        loose_limit = max(0.01, 8.0 * widest)
        recommended = min(0.1, max(0.01, int(5.0 * widest * 1000) / 1000))
        print(f"  -> {name}: widest spread {widest:.4%}; bound may be "
              f"{max(0.01, tight_need):.4%} .. {loose_limit:.4%}; recommended {recommended}; "
              f"manifest has {b}")
        if b < 0.01:
            bad(f"{name}: bound {b} is under 1%")
        if b > recommended + 1e-12:
            bad(f"{name}: bound {b} is above the recommended {recommended} (5 x the widest "
                f"spread, at most 0.1)")
        if b > loose_limit + 1e-12:
            bad(f"{name}: bound {b:.2%} is over 8 x the widest spread ({widest:.4%}), or 1% "
                f"if that is more: {loose_limit:.4%} — too loose")
        if b < tight_need:
            bad(f"{name}: bound {b:.2%} is under twice a cell's spread ({tight_need / 2:.4%}) "
                f"— too tight")


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        m = json.load(f)
    check_form(m)
    if not problems:
        check_bounds(m)
    print(f"\n{'FAILED: ' + str(len(problems)) + ' problem(s)' if problems else 'OK'}")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
