"""Long-context flagship sweep: tokens/sec + exact MFU per (T, B, remat).

Runs each config in a SUBPROCESS — benching several flagship-size configs
in one process leaks device buffers across configs and OOMs spuriously.
A chip belongs to one process at a time, so THIS parent must stay free
of JAX (it imports nothing that imports it): each child takes the chip,
runs its config and gives it back before the next starts. Prints one
JSON line per config; the summary table feeds BASELINE.md's
long-context rows.

Usage: python benchmarks/lm_scan.py [--quick]
"""

import argparse
import json
import os
import subprocess
import sys

CONFIGS = [
    # (T, B, remat) — the B=8@4096 and B=2@16384 no-remat rows became
    # trainable in r5 when the fused CE removed the [B, T, V] logits
    (2048, 8, "none"),
    (4096, 4, "none"),
    (4096, 8, "none"),
    (8192, 2, "none"),
    (8192, 4, "block"),
    (16384, 1, "none"),
    (16384, 2, "none"),
    (16384, 2, "block"),
]

CHILD = """
import json, sys
sys.path.insert(0, {root!r})
import bench
from distkeras_tpu.utils import compile_cache
compile_cache.enable()
out = bench.lm_bench(T={T}, B={B}, remat={remat!r}, calls=2)
print("LMSCAN " + json.dumps(out))
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="first three configs only")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    configs = CONFIGS[:3] if args.quick else CONFIGS
    for T, B, remat in configs:
        code = CHILD.format(root=root, T=T, B=B, remat=remat)
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True, timeout=900,
            )
        except subprocess.TimeoutExpired:
            # a hung config (the OOM/stall case the isolation exists
            # for) records its error and the sweep continues
            print(json.dumps({"T": T, "B": B, "remat": remat,
                              "error": "timeout after 900s"}))
            continue
        line = next(
            (ln for ln in proc.stdout.splitlines()
             if ln.startswith("LMSCAN ")), None,
        )
        if proc.returncode != 0 or line is None:
            print(json.dumps({
                "T": T, "B": B, "remat": remat,
                "error": (proc.stderr or proc.stdout)[-300:],
            }))
            continue
        print(json.dumps({"T": T, "B": B, "remat": remat,
                          **json.loads(line[len("LMSCAN "):])}))


if __name__ == "__main__":
    main()
