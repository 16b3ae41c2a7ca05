#!/usr/bin/env python3
"""Run one cell several times, one new process per run as the driver does,
and print each end-to-end metric's spread: the distance between the first
and third quartile (statistics.quantiles(values, n=4)) as a share of the
median. This process never touches JAX, so each child gets the chip.

    python3 benchmark/tools/sets.py --workload <name> --seeds 1,2,3,4,5,6 \
        [--seconds N] [--trace 0] [--out chiprun_out/<file>.jsonl] [--budget-s S] \
        [-- --control truncate_opening]

Each run's last line goes to --out with its seed, return code and wall; with
--budget-s the tool stops starting runs once that many seconds have passed
since it started. Not run by the driver.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _text(b) -> str:
    return b.decode(errors="replace") if isinstance(b, bytes) else (b or "")


def spread(values) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def summarize(lines: list[dict]) -> dict:
    by_metric: dict[str, list[float]] = {}
    for ln in lines:
        for name, m in (ln.get("metrics") or {}).items():
            by_metric.setdefault(name, []).append(float(m["value"]))
    out = {}
    for name, vals in by_metric.items():
        out[name] = {
            "n": len(vals),
            "median": statistics.median(vals),
            "spread": spread(vals) if len(vals) >= 2 else None,
            "values": vals,
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--budget-s", type=float, default=None)
    ap.add_argument("--run-timeout", type=float, default=1500)
    ap.add_argument("extra", nargs="*", help="further arguments for run.py")
    opts = ap.parse_args(argv)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seconds = opts.seconds or bench["run_seconds"]
    t_start = time.time()
    lines = []
    for seed in [s for s in opts.seeds.split(",") if s]:
        if opts.budget_s is not None and time.time() - t_start > opts.budget_s:
            print(f"sets: budget spent before seed {seed}", file=sys.stderr)
            break
        cmd = bench["command"] + [
            "--workload", opts.workload, "--seed", seed,
            "--seconds", str(seconds), "--trace", str(opts.trace),
        ] + opts.extra
        t0 = time.time()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=opts.run_timeout)
            rc, out, err = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc, out, err = 124, _text(e.stdout), _text(e.stderr)
        wall = time.time() - t0
        last = out.strip().splitlines()[-1] if out.strip() else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = {}
        rec = {"workload": opts.workload, "seed": int(seed), "rc": rc,
               "wall_s": wall, "trace": opts.trace, **line}
        for ln in out.splitlines():
            if ln.startswith("control {"):  # the control's own verdict
                rec["control"] = json.loads(ln[len("control "):])
        lines.append(rec)
        print(json.dumps(rec), flush=True)
        if opts.out:
            os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
            with open(opts.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            with open(opts.out + ".log", "a") as f:
                f.write(f"==== seed {seed} rc {rc} wall {wall:.1f}\n")
                f.write("\n".join(out.strip().splitlines()[-40:]) + "\n---- stderr\n")
                f.write("\n".join(
                    ln for ln in err.splitlines() if ln.startswith("[bench")
                    or "Error" in ln or "Traceback" in ln or "rror:" in ln
                )[-6000:] + "\n")
    good = [ln for ln in lines if ln.get("rc") == 0]
    print("summary " + json.dumps(summarize(good)), flush=True)
    bad = [ln["seed"] for ln in lines if ln.get("rc") != 0 or not ln.get("correct")
           or ln.get("control", {}).get("correct")]
    if bad:
        print(f"sets: runs not correct or failed: {bad}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
