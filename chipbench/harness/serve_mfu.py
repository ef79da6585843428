"""A serving cell's share of the chip's peak (PR 38; the ``derived``
reader calls it as ``fn(cell, run, peaks)``): useful operations of the
ticks the engine's flight ring holds (``chipbench/kernels/
deepseek_v32.py``, from each record's own counters) over the seconds
those ticks span on the engine's clock, over the peak. ``stats()``'s
totals run from the engine's start through the ramp and cannot be cut
to a window; the ring holds the last 512 ticks, which end with the
window. ``None`` where the records lack the counters, as another
model's have not: the metric is then left out of the line."""

from __future__ import annotations

from chipbench.harness.span_metrics import _ticks
from chipbench.kernels import deepseek_v32


def serve_mfu_pct(cell: dict, run: dict, peaks: dict):
    ticks = _ticks(run, "keys_selected")
    if len(ticks) < 2 or ticks[-1]["t"] <= ticks[0]["t"]:
        return None
    model = cell["config_spec"]["model"]
    # a record is written when its tick has been read: the first one's
    # stamp opens the span and its work lies before it
    flops = sum(deepseek_v32.tick_flops(model, t) for t in ticks[1:])
    seconds = ticks[-1]["t"] - ticks[0]["t"]
    return 100.0 * flops / (seconds * run["device"]["count"]
                            * peaks["flops_bf16"])
