"""A serving configuration's further controls, on the chip at the cell's
own size: ``python3 -m chipbench.tools.second_control <cell> --seeds 1,2
[--seconds S] [--precisions dense,int8]``. ``chipbench.tools.control``
reads one control from the configuration (``precision.control``); a
reference may be able to compute others (its ``PRECISIONS``), such as
``"dense"``, a learned selection switched off. One fresh process per
seed runs the cell for a short window and then, on that same run, reads
the verdict of the program's own tokens and of what each named precision
of the reference puts in their place: every one of those has to come out
as not correct. Default: ``precision.second_control`` of the
configuration. Lines go to ``chiprun_out/<cell>.second_control.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from chipbench.harness import spec

OUT = os.path.join(os.path.dirname(spec.ROOT), "chiprun_out")


def one(cell_name: str, seed: int, seconds: float, precisions) -> dict:
    """In this process (it takes the chip): the cell's runner, then each
    control's verdict on the same finished requests."""
    from chipbench import run as entry
    from chipbench.harness import device, serve_runner

    cell = spec.cell(cell_name)
    reference = spec.reference(cell["config_spec"])
    for low in precisions:
        if low == "f32" or low not in reference.PRECISIONS:
            raise ValueError(f"{low!r} is no lower precision of "
                             f"{reference.__name__}: {reference.PRECISIONS}")
    device.enable_compile_cache()
    devices = device.require_tpu(cell["chips"])
    ctx = entry.make_ctx(devices, entry.OUT_DIR)
    run = serve_runner.run(cell, seed, seconds, False, ctx)
    out = {"cell": cell_name, "seed": seed, "serve_tok_s": run["serve_tok_s"],
           "finished": len(run["finished"]), "check_s": run["check_s"],
           "program": run["verdict"].rows, "correct": run["verdict"].correct,
           "controls": {}}
    for low in precisions:
        t0 = time.time()
        control = serve_runner.check(*run["check_args"], precision=low)
        out["controls"][low] = {"rows": control.rows,
                                "correct": control.correct,
                                "seconds": time.time() - t0}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--precisions", default=None,
                    help="comma-separated; default: the configuration's "
                         "precision.second_control")
    ap.add_argument("--one", action="store_true",
                    help="run the one given seed in this process")
    args = ap.parse_args()
    precisions = (args.precisions.split(",") if args.precisions else [
        spec.cell(args.cell)["config_spec"]["precision"]["second_control"]])
    if args.one:
        print(json.dumps(one(args.cell, int(args.seeds), args.seconds,
                             precisions)), flush=True)
        return 0
    os.makedirs(OUT, exist_ok=True)
    rc = 0
    with open(os.path.join(OUT, f"{args.cell}.second_control.jsonl"),
              "a") as f:
        for seed in args.seeds.split(","):
            proc = subprocess.run(
                [sys.executable, "-m", "chipbench.tools.second_control",
                 args.cell, "--seeds", seed, "--seconds", str(args.seconds),
                 "--precisions", ",".join(precisions), "--one"],
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
