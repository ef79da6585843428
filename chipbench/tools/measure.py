"""Runs of one cell, one fresh process each, and their spread.

``python3 -m chipbench.tools.measure runs <cell> --seeds 1,2,3 [--sets 2]
[--seconds S] [--trace 0|1]`` writes every result line to
``chiprun_out/<cell>.runs.jsonl`` and prints, for each metric, the median
and the spread (interquartile range over the median, by
``statistics.quantiles(n=4)``) of each set. This process never touches
JAX: a chip belongs to the run.

``... trace-info <cell>`` prints what the last traced run's profile
holds: planes, lines, and the operations that took most time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from chipbench.harness import spec

OUT = os.path.join(os.path.dirname(spec.ROOT), "chiprun_out")


def one_run(cell, seed, seconds, trace):
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    row = {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
           "wall_s": round(time.time() - t0, 1)}
    if proc.returncode == 0 and lines:
        row["result"] = json.loads(lines[-1])
        row["notes"] = [json.loads(x) for x in lines[:-1]
                        if x.startswith("{")]
    else:
        row["stdout"] = proc.stdout[-3000:]
        row["stderr"] = proc.stderr[-6000:]
    return row


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def runs(args):
    os.makedirs(OUT, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    seconds = args.seconds or spec.run_seconds()
    path = os.path.join(OUT, f"{args.cell}.runs.jsonl")
    sets = []
    with open(path, "a") as f:
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                row = one_run(args.cell, seed, seconds, args.trace)
                row["set"] = k
                f.write(json.dumps(row) + "\n")
                f.flush()
                rows.append(row)
                brief = {key: row.get(key) for key in ("seed", "rc", "wall_s")}
                if "result" in row:
                    r = row["result"]
                    brief.update(correct=r["correct"], attempted=r["attempted"],
                                 failed=r["failed"],
                                 peak=r["device"]["memory_peak_bytes"],
                                 **{m: v["value"] for m, v in
                                    r["metrics"].items()})
                    brief["checks"] = [(k, v["value"]) for k, v in
                                       r.get("compared", {}).items()]
                    brief["readings"] = [n["readings"] for n in row["notes"]
                                         if "readings" in n]
                else:
                    brief["stderr"] = row["stderr"][-1500:]
                print(json.dumps(brief), flush=True)
            sets.append(rows)
    for k, rows in enumerate(sets):
        good = [r["result"] for r in rows if "result" in r]
        for name in (good[0]["metrics"] if good else ()):
            vals = [g["metrics"][name]["value"] for g in good
                    if name in g["metrics"]]
            # the first run of a checkout compiles: its set-up stands apart
            if name == "setup_s" and k == 0:
                vals = vals[1:]
            if not vals:
                continue
            line = {"set": k, "metric": name, "n": len(vals),
                    "median": statistics.median(vals)}
            if len(vals) >= 2:
                line["spread"] = spread(vals)
            print(json.dumps(line), flush=True)
    return 0 if all("result" in r for rows in sets for r in rows) else 1


def trace_info(args):
    from chipbench.harness import trace_reduce

    trace_dir = os.path.join(os.path.dirname(spec.ROOT), "chipbench_out",
                             f"trace.{args.cell}")
    events = trace_reduce.read_events(trace_reduce.find_xplane(trace_dir))
    print(json.dumps({"lines": events["lines"]}))
    reduced = trace_reduce.reduce_events(events, top=40)
    print(json.dumps({k: reduced[k] for k in
                      ("busy_s", "window_s", "longest_gap_s", "device_ops",
                       "idle_gaps")}))
    os.makedirs(OUT, exist_ok=True)
    # a slice of the events, small enough to keep as a test's fixture
    for dev, ops in events["devices"].items():
        events["devices"][dev] = sorted(ops, key=lambda o: o[1])[:args.keep]
    events["spans"] = events["spans"][:50]
    with open(os.path.join(OUT, f"{args.cell}.trace_events.json"), "w") as f:
        json.dump(events, f)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("runs")
    r.add_argument("cell")
    r.add_argument("--seeds", required=True)
    r.add_argument("--sets", type=int, default=1)
    r.add_argument("--seconds", type=float)
    r.add_argument("--trace", type=int, default=0)
    r.set_defaults(fn=runs)
    t = sub.add_parser("trace-info")
    t.add_argument("cell")
    t.add_argument("--keep", type=int, default=600)
    t.set_defaults(fn=trace_info)
    args = ap.parse_args()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
