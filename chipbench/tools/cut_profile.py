"""``python3 -m chipbench.tools.cut_profile <trace dir> <out.json>
(--from-op <regex> | --from-span <name>) [--nth K] [--count N]``: a
slice of a recorded profile, small enough to keep as a test's fixture,
in the plain form ``span_reduce.read_profile`` returns. The slice runs
from the start of the K-th device operation whose name matches (or the
K-th span of that name) to the start of the (K+N)-th: whole steps or
ticks when the pattern names something that runs once in each."""

from __future__ import annotations

import argparse
import json
import re
import sys

from chipbench.harness import span_reduce, trace_reduce


def cut(profile: dict, start_ns: int, end_ns: int) -> dict:
    """The operations that start inside ``[start_ns, end_ns)``, the
    spans that intersect it, and the scopes of the operations kept."""
    devices = {d: [op for op in ops if start_ns <= op[1] < end_ns]
               for d, ops in profile["devices"].items()}
    kept = {op[0].split(" ")[0] for ops in devices.values() for op in ops}
    return {
        "devices": devices,
        "spans": [s for s in profile["spans"]
                  if s[1] < end_ns and s[1] + s[2] > start_ns],
        "scopes": {k: v for k, v in profile["scopes"].items() if k in kept},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    where = ap.add_mutually_exclusive_group(required=True)
    where.add_argument("--from-op")
    where.add_argument("--from-span")
    ap.add_argument("--nth", type=int, default=1)
    ap.add_argument("--count", type=int, default=1)
    args = ap.parse_args(argv)
    profile = span_reduce.read_profile(
        trace_reduce.find_xplane(args.trace_dir))
    if args.from_span:
        starts = [s[1] for s in profile["spans"] if s[0] == args.from_span]
    else:
        rx = re.compile(args.from_op)
        starts = sorted(o[1] for o in next(iter(
            profile["devices"].values())) if rx.search(o[0]))
    piece = cut(profile, starts[args.nth], starts[args.nth + args.count])
    with open(args.out, "w") as f:
        json.dump(piece, f, separators=(",", ":"))
    print(json.dumps({
        "operations": sum(len(v) for v in piece["devices"].values()),
        "spans": len(piece["spans"]), "scopes": len(piece["scopes"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
