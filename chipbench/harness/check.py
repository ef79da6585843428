"""How ``correct`` is decided: every number compared is printed beside
its limit, and one number over its limit makes the run not correct."""

from __future__ import annotations

import json
import math
import sys


class Verdict:
    def __init__(self):
        self.rows = []

    def hold(self, name: str, value: float, limit: float):
        """``value`` has to be finite and at most ``limit``."""
        ok = math.isfinite(value) and value <= limit
        self.rows.append({"compared": name, "value": value, "limit": limit,
                          "ok": ok})
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def compared(self) -> dict:
        """``{name: {"value", "limit"}}`` of every number compared."""
        return {r["compared"]: {"value": r["value"], "limit": r["limit"]}
                for r in self.rows}

    def print(self):
        """Every row, with what else it recorded, on standard error."""
        for row in self.rows:
            print(json.dumps({"check": row}), file=sys.stderr, flush=True)


def served_gaps(ref_logits, served):
    """For each served token, how far its reference logit lies below the
    reference's best at its position (0 where the served token is the
    reference's own), and that position's margin: the reference's best
    over its second. ``ref_logits [n, V]`` are the reference's logits at
    the positions that chose ``served``."""
    import numpy as np

    rows = np.asarray(ref_logits, np.float32)
    chosen = rows[np.arange(len(served)), np.asarray(served)]
    second, best = np.partition(rows, -2, axis=-1)[:, -2:].T
    return best - chosen, best - second
