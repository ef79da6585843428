"""The control of "How correct is decided", on the chip at a cell's own
size: ``python3 -m chipbench.tools.control <cell> --seeds 1,2,3
[--seconds S] [--variant int8cache] [--sample N]``. One fresh process per
seed runs the cell for a short window, then reads, beside the program's
numbers, what the reference in int8 gives in the program's place (it has
to come out as not correct) and, for training, the faults the loss limits
are there to catch. ``--variant int8cache`` runs the engine's own lower
path (``cache_dtype="int8"``) as the program; ``--sample N`` keeps every
compared token's gap and margin of N requests, which is what the serving
limits were set from. Lines go to ``chiprun_out/<cell>.control.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from chipbench.harness import spec

OUT = os.path.join(os.path.dirname(spec.ROOT), "chiprun_out")


def one(cell_name: str, seed: int, seconds: float, variant: str = "program",
        sample: int = 0) -> dict:
    """In this process (it takes the chip): the cell's runner, then the
    control's readings."""
    import numpy as np

    from chipbench import run as entry
    from chipbench.harness import device

    cell = spec.cell(cell_name)
    reference = spec.reference(cell["config_spec"])
    low = cell["config_spec"]["precision"]["control"]
    if low not in reference.PRECISIONS:
        raise ValueError(f"configs/{cell['config']}: control precision "
                         f"{low!r} not among {reference.PRECISIONS}")
    if variant == "int8cache":  # the program's own lower-precision path
        cell["config_spec"]["model"]["cache_dtype"] = "int8"
    device.enable_compile_cache()
    devices = device.require_tpu(cell["chips"])
    ctx = entry.make_ctx(devices, entry.OUT_DIR)
    kind = cell["traffic_spec"]["kind"]
    run = entry.runner_for(kind)(cell, seed, seconds, False, ctx)
    out = {"cell": cell_name, "seed": seed, "variant": variant,
           "program": run["verdict"].rows, "correct": run["verdict"].correct}
    if kind == "train_job":
        from chipbench.harness import train_runner

        control = train_runner.check(*run["check_args"], precision=low)
        _, cfg, _, corpus, losses = run["check_args"]
        B = cfg["trainer"]["batch_size"]
        batches = np.asarray(corpus).reshape(-1, B, corpus.shape[1])[
            :train_runner.CHECK_STEPS]
        want = [r["reference"] for r in run["verdict"].rows
                if "reference" in r]
        start = reference.make_params(cfg, seed)
        rows = [reference.row_losses(cfg, start, b) for b in batches]
        # the faults the loss limits are there to catch, read off the
        # reference: a quarter of the batch left out; a step that returns
        # its state unchanged (every loss stays at the start's weights);
        # an update mis-scaled by a tenth
        out["fault_part_of_batch_left_out"] = [
            abs(np.mean(r[:-1]) - np.mean(r)) / np.mean(r) for r in rows]
        out["fault_state_unchanged"] = [
            abs(np.mean(r) - w) / w for r, w in zip(rows, want)]
        sched = cfg["trainer"]["schedule"]
        slow = dict(cfg, trainer=dict(cfg["trainer"], schedule=dict(
            sched, init=0.9 * sched["init"], peak=0.9 * sched["peak"])))
        scaled, _ = reference.train_losses(slow, start, batches)
        out["fault_update_scaled_by_0.9"] = [
            abs(s - w) / w for s, w in zip(scaled, want)]
    else:
        from chipbench.harness import serve_runner

        control = serve_runner.check(*run["check_args"], precision=low)
        if sample:  # every compared token's readings, for setting limits
            _, cfg, _, params, finished = run["check_args"]
            limits = dict(cell["limits"], sample_requests=sample,
                          sample_requests_max=sample)
            gap, margin = serve_runner.sample_readings(
                cfg, params, finished, seed, limits)
            control_gap = (serve_runner.sample_readings(
                cfg, params, finished, seed, limits, low)[0]
                if variant == "program" else [np.zeros(0)])
            os.makedirs(OUT, exist_ok=True)
            np.savez(os.path.join(
                OUT, f"{cell_name}.tokens.{variant}.{seed}.npz"),
                gap=np.concatenate(gap), margin=np.concatenate(margin),
                control_gap=np.concatenate(control_gap),
                lens=[len(g) for g in gap], finished=len(finished))
    out["control"] = control.rows
    out["control_correct"] = control.correct
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--variant", default="program",
                    choices=("program", "int8cache"),
                    help="int8cache: the engine with cache_dtype='int8'")
    ap.add_argument("--sample", type=int, default=0,
                    help="serving: write every compared token's gap and "
                         "margin of this many requests to chiprun_out")
    ap.add_argument("--one", action="store_true",
                    help="run the one given seed in this process")
    args = ap.parse_args()
    if args.one:
        print(json.dumps(one(args.cell, int(args.seeds), args.seconds,
                             args.variant, args.sample)), flush=True)
        return 0
    os.makedirs(OUT, exist_ok=True)
    rc = 0
    with open(os.path.join(OUT, f"{args.cell}.control.jsonl"), "a") as f:
        for seed in args.seeds.split(","):
            proc = subprocess.run(
                [sys.executable, "-m", "chipbench.tools.control", args.cell,
                 "--seeds", seed, "--seconds", str(args.seconds),
                 "--variant", args.variant, "--sample", str(args.sample),
                 "--one"],
                capture_output=True, text=True)
            lines = proc.stdout.strip().splitlines()
            if proc.returncode == 0 and lines:
                print(lines[-1], flush=True)
                f.write(lines[-1] + "\n")
            else:
                rc = 1
                print(json.dumps({"seed": seed, "rc": proc.returncode,
                                  "stderr": proc.stderr[-3000:]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
