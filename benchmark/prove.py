#!/usr/bin/env python3
"""Run one cell several times in one call and keep every result line.

    python3 benchmark/prove.py --workload <cell> --seeds 1,23,456 --sets 2 --seconds 20

Each run is the benchmark's own command in a new process (one process
holds the chip at a time). Every final line goes, with its set, seed and
exit code, to ``chiprun_out/proof/<cell>.jsonl`` (appended); the builder
copies the lines it stands by into ``benchmark/proof/``, where
``check_manifest.py`` reads them. ``--trace 1`` makes traced runs instead
(kept in ``<cell>.trace.jsonl``: per-layer numbers, no bound). Every
``[bench]`` line of every run is kept beside them in ``<cell>.log`` (a
call's output is cut to its end). ``--turn 1`` runs every second set's
seeds in the opposite order, so that a run's place in the call can be
told from its seed."""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--turn", type=int, default=0, help="1: even sets run the seeds backwards")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "proof"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    suffix = ".trace.jsonl" if args.trace else ".jsonl"
    path = os.path.join(args.out, args.workload + suffix)
    bad = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    log_path = os.path.join(args.out, args.workload + ".log")
    place = 0
    for k in range(1, args.sets + 1):
        for seed in (seeds[::-1] if args.turn and k % 2 == 0 else seeds):
            place += 1
            t0 = time.monotonic()
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(args.seconds), "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True,
            )
            wall = time.monotonic() - t0
            lines = proc.stdout.strip().splitlines()
            with open(log_path, "a", encoding="utf-8") as f:
                f.write(f"== set {k} seed {seed} place {place} trace {args.trace} "
                        f"rc {proc.returncode}\n")
                for ln in lines[:-1]:
                    if ln.startswith("[bench]"):
                        print(ln)
                        f.write(ln + "\n")
            try:
                result = json.loads(lines[-1]) if proc.returncode == 0 else None
            except (IndexError, ValueError):
                result = None
            if result is None:
                bad += 1
                print(f"RUN FAILED rc={proc.returncode}\n{proc.stdout[-3000:]}\n{proc.stderr[-6000:]}")
            with open(path, "a", encoding="utf-8") as f:
                f.write(json.dumps({"set": k, "seed": seed, "place": place,
                                    "rc": proc.returncode, "wall_s": wall,
                                    "result": result}) + "\n")
            if result is not None:
                vals = {n: m["value"] for n, m in result["metrics"].items()}
                print(f"== {args.workload} set {k} seed {seed} wall {wall:.1f}s correct="
                      f"{result['correct']} attempted={result['attempted']} failed="
                      f"{result['failed']} mem={result['device'].get('memory_peak_bytes')} {vals}")
                if "breakdown" in result:
                    print(json.dumps(result["breakdown"]))
                    print({k2: v for k2, v in result["device"].items()})
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
