"""``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell on the chips this machine holds.

The last line of standard output is the result as one JSON object; any
failure raises and the process exits non-zero without one. Without a TPU
(or with fewer chips than the cell asks for) it fails before any work.
"""

from __future__ import annotations

import time

PROC_START, PROC_START_PERF = time.time(), time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from chipbench.harness import device, readers, spec, trace_reduce  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(spec.ROOT), "chipbench_out")


def runner_for(kind: str):
    if kind == "train_job":
        from chipbench.harness import train_runner
        return train_runner.run
    if kind in ("closed_loop", "open_loop"):
        from chipbench.harness import serve_runner
        return serve_runner.run
    raise ValueError(f"unknown traffic kind {kind!r}")


def make_ctx(devices, out_dir: str) -> dict:
    """What a runner is handed besides the cell: the process's start on
    both clocks, where to write, the devices, a count of compiles."""
    os.makedirs(out_dir, exist_ok=True)
    return {"proc_start": PROC_START, "proc_start_perf": PROC_START_PERF,
            "out_dir": out_dir, "devices": devices,
            "compile_log": device.CompileLog()}


def execute(cell: dict, seed: int, seconds: float, trace: bool, devices,
            out_dir: str = OUT_DIR) -> dict:
    """One run of a resolved cell on ``devices``; returns the result
    line's object. The caller has made sure of the chip (or, in a CPU
    rehearsal, knows that it has not)."""
    ctx = make_ctx(devices, out_dir)
    shutil.rmtree(os.path.join(out_dir, f"trace.{cell['name']}"),
                  ignore_errors=True)
    run = runner_for(cell["traffic_spec"]["kind"])(
        cell, seed, seconds, trace, ctx)
    # every plain number the runner read, beside the metrics proper
    print(json.dumps({"compile": ctx["compile_log"].since((0, 0, 0)),
                      "readings": {k: v for k, v in run.items()
                                   if isinstance(v, (int, float))}}),
          flush=True)
    reduced = None
    if trace:
        reduced = trace_reduce.reduce_events(
            trace_reduce.read_events(trace_reduce.find_xplane(
                run["trace_dir"])), default_span=run["default_span"])
    specs = cell["per_layer_specs"] if trace else cell["end_to_end_specs"]
    metrics = {}
    for m in specs:
        value = (readers.read(m, run, cell, reduced) if trace
                 else run.get(m["name"]))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": run["verdict"].correct,
              "attempted": run["attempted"], "failed": run["failed"],
              "metrics": metrics, "device": run["device"]}
    recorded = {m["name"]: {"value": float(v), "unit": m["unit"]}
                for m in cell["recorded_specs"]
                if (v := readers.read(m, run, cell, reduced)) is not None}
    if recorded:
        result["recorded"] = recorded
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    # each number compared beside its limit: last in the line, and the
    # last lines of standard error
    result["compared"] = run["verdict"].compared()
    run["verdict"].print()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    device.enable_compile_cache()
    try:
        devices = device.require_tpu(cell["chips"])
    except device.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
