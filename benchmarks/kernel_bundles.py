"""Static schedule of the training attention's three launches, without a
chip: compile them for a described v5e with the TPU compiler's LLO dumps
on, and print each code region's bundle count and slot use.

A bundle is one VLIW issue of the core; a region is one basic block of
the kernel (an init, a tile body, a finalize; the pipeline's own code
between them). The counts are the compiler's schedule, not a time: they
say which unit a body waits on (PR 45: 2 086 XLU slots in 1 876 bundles
of the forward's tile were cross-lane broadcasts of ``[block, 1]``
columns; over whole vregs the same tile schedules in 1 147), and they
miss what the schedule cannot know (a grid step's fixed cost, DMA
waits). Rank ideas here, measure them on the chip.

Usage: python benchmarks/kernel_bundles.py [--shape NAME] [--block N]
           [--launch fwd|dq|dkv] [--min-bundles 200]
The compile runs in a child process: with the dumps on, libtpu aborts
at exit over a report template it does not ship, after the files this
reads are written.
"""

from __future__ import annotations

import argparse
import collections
import glob
import math
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.pallas_block_sweep import SHAPES  # noqa: E402


def compile_launch(shape: str, block: int, launch: str):
    """In the child: one launch, lowered for a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from distkeras_tpu.ops import pallas_attention as pa

    pa._interpret = lambda: False
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    B, T, H, Hk, hd, window = SHAPES[shape]
    scale = 1 / math.sqrt(hd)

    def arg(heads, last=hd, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((B * heads, T, last), dtype,
                                    sharding=chip)

    def run(q3, k3, v3, o3, lse, do3):
        if launch == "fwd":
            return pa._fwd(q3, k3, v3, block, scale, window)
        dq, dk, dv = pa._bwd(q3, k3, v3, o3, lse, do3, block, scale, window)
        return dq if launch == "dq" else (dk, dv)

    jax.jit(run).lower(arg(H), arg(Hk), arg(Hk), arg(H),
                       arg(H, pa.LSE_LANES, jnp.float32), arg(H)).compile()


def regions(dump: str, min_bundles: int):
    """Yield ``(kernel, first, last, slot use, commonest operations)``
    for the regions of every dumped Mosaic kernel."""
    for bundles in sorted(glob.glob(f"{dump}/*-final_bundles.txt")):
        if "schedule-analysis" in bundles:
            continue
        name = re.sub(r"^\d+-|-\d+-final_bundles\.txt$", "",
                      os.path.basename(bundles))
        use = glob.glob(f"{dump}/*-{name}-*-final_hlo-static-per-bundle-"
                        "utilization.txt")
        text = open(bundles).read()
        if "vmatmul" not in text or not use:
            continue  # an XLA fusion beside the kernels
        lines = open(use[0]).read().split("\n")
        slots = lines[1].replace(",", "").split()
        used = [list(map(int, l.split())) for l in lines[4:] if l.strip()]
        rows = [l for l in text.split("\n")
                if re.match(r"\s*(0x[0-9a-f]+|\d+)\s+:", l)]
        cuts = {0, len(rows)}
        for n, row in enumerate(rows):
            if "Start region" in row:
                cuts.add(n)
            if "End region" in row:
                cuts.add(n + 1)
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            if b - a < min_bundles:
                continue
            total = {s: sum(u[c] for u in used[a:b])
                     for c, s in enumerate(slots)}
            ops = collections.Counter(re.findall(
                r"= (v[a-z0-9_.]+)", "\n".join(rows[a:b])))
            yield name, a, b, total, ops.most_common(12)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES),
                    default="train-moe-seq8k-window")
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--launch", choices=["fwd", "dq", "dkv"], default="fwd")
    ap.add_argument("--min-bundles", type=int, default=200)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return compile_launch(args.shape, args.block, args.launch)
    with tempfile.TemporaryDirectory() as dump:
        env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
            f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true"))
        subprocess.run([sys.executable, __file__, "--child", "--shape",
                        args.shape, "--block", str(args.block), "--launch",
                        args.launch], env=env, capture_output=True)
        found = False
        for name, a, b, total, ops in regions(dump, args.min_bundles):
            found = True
            print(f"{name}: bundles {a}-{b} ({b - a}) {total}")
            print("    " + ", ".join(f"{op} {n}" for op, n in ops))
        if not found:
            sys.exit("no kernel was dumped: did the compile fail?")


if __name__ == "__main__":
    main()
