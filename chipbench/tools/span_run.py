"""``python3 -m chipbench.tools.span_run --workload <cell> --seed <n>
--seconds <s>``: one traced run of a cell, as ``chipbench.run --trace 1``
makes it, whose line also holds the per-layer metrics that wait for the
cell: the files under ``layer_metrics/`` that name the cell under
``"cells"`` and that the cell's own ``per_layer`` list does not hold.

A cell lists its metrics in its own file and ``spec.cell`` reads nothing
else, so a PR that may edit no file the benchmark has can bring a
metric's file and its reader, and cannot make ``chipbench.run`` print it.
Such a metric is no part of ``BENCHMARK.json`` and the driver never sees
it; this is how a builder or an operator reads it meanwhile. A
``benchmark`` PR takes it up by appending its name to the ``per_layer``
list of each cell it names.
"""

from __future__ import annotations

from chipbench import run  # first: it stamps the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench.harness import device, spec  # noqa: E402


def waiting(cell_name: str, root: str = spec.ROOT) -> list:
    """Names of the metric files that name this cell under ``"cells"``."""
    return [n for n in spec.names("layer_metrics", root)
            if cell_name in spec.load("layer_metrics", n, root).get(
                "cells", ())]


def cell_with_waiting(name: str, root: str = spec.ROOT) -> dict:
    """``spec.cell`` with the waiting metrics after the cell's own, each
    held to what ``spec.cell`` holds a listed metric to."""
    c = spec.cell(name, root)
    for n in waiting(name, root):
        if n in c["per_layer"]:
            continue
        m = spec.metric("layer_metrics", n, root)
        spec._line(m["layer"], f"layer_metrics/{n} layer")
        if m["moves"] not in c["end_to_end"]:
            raise ValueError(f"layer_metrics/{n} moves {m['moves']!r}, "
                             f"which cell {name} does not report")
        c["per_layer"].append(n)
        c["per_layer_specs"].append(m)
    return c


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cell_with_waiting(args.workload)
    device.enable_compile_cache()
    try:
        devices = device.require_tpu(cell["chips"])
    except device.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(run.execute(cell, args.seed, args.seconds, True,
                                 devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
