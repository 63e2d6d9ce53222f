#!/usr/bin/env python3
"""A stop made on purpose, on the chip: what does it do to a reading?

    python3 benchmark/tests/induced_stops.py --workload sfx_epix_paced \
        --seeds 5,6,7 --at 28.0,35.3,43.1 --stop-ms 110 --seconds 30

Runs the benchmark's command in a process group of its own and freezes
the whole group (the program, its generator and the sleeping child) with
SIGSTOP for ``--stop-ms`` at each ``--at`` (seconds after the start of the
process; the paced cell's window opens about 22 s in). To the processes
that is what a stop of the sandbox is, except that the device runs on.
The sleeping child must report each one (``stops.window`` on the final
line), and the line shows what the stop did to every metric. Lines go to
``chiprun_out/induced/<cell>.jsonl``. A tool for a builder, not a proof:
nothing reads its output."""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--at", default="", help="comma-separated seconds after process start")
    ap.add_argument("--stop-ms", type=float, default=110.0)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "induced")
    os.makedirs(out_dir, exist_ok=True)
    at = sorted(float(a) for a in args.at.split(",") if a)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(args.seconds), "--trace", "0"],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            start_new_session=True,
        )
        try:
            for a in at:
                time.sleep(max(0.0, t0 + a - time.monotonic()))
                if proc.poll() is None:
                    os.killpg(proc.pid, signal.SIGSTOP)
                    time.sleep(args.stop_ms / 1e3)
                    os.killpg(proc.pid, signal.SIGCONT)
            out, _ = proc.communicate(timeout=600)
        finally:
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
        lines = out.strip().splitlines()
        for ln in lines[:-1]:
            if "plain median" in ln or ln.startswith("[bench] stops:") or "stalls" in ln:
                print(ln[:400])
        result = json.loads(lines[-1]) if proc.returncode == 0 and lines else None
        with open(os.path.join(out_dir, args.workload + ".jsonl"), "a", encoding="utf-8") as f:
            f.write(json.dumps({"seed": seed, "induced_at": at, "stop_ms": args.stop_ms,
                                "rc": proc.returncode, "result": result}) + "\n")
        if result:
            print(f"== {args.workload} seed {seed} induced {at} correct={result['correct']} "
                  f"{ {n: m['value'] for n, m in result['metrics'].items()} } window stops "
                  f"{result['stops']['window']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
