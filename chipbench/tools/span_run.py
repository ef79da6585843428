"""``python3 -m chipbench.tools.span_run --workload <cell> --seed <n>
--seconds <s>`` is ``python3 -m chipbench.run`` with ``--trace 1``, and
nothing else: since PR 27 ``spec.cell`` gathers the metric files that
name a cell, so the driver's command prints what this tool was for.

It stays as a forwarder because ``ROADMAP.md`` and the verify skill
still give this command, and a ``benchmark`` PR may edit neither. The PR
that rewrites those lines deletes this file.
"""

import sys

from chipbench import run


def main(argv=None) -> int:
    return run.main([*(sys.argv[1:] if argv is None else argv),
                     "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
