"""Per-layer metrics of a model whose attention layers differ in kind
(PR 34's scopes ``full_attend`` and ``window_attend``; the ``derived``
reader calls each as ``fn(cell, run, peaks)``). Each returns ``None``
where the program has no such scope, as a model of one kind of layer
has not: the metric is then left out of the line."""

from __future__ import annotations

from chipbench.harness.span_metrics import _scope_pct
from chipbench.harness.sparse_moe_metrics import _sum


def window_attend_device_pct(cell: dict, run: dict, peaks: dict):
    """The window layers' attend over their rings (plain XLA: no kernel,
    so no roofline), over busy."""
    return _scope_pct(run, "window_attend")


def attend_device_pct(cell: dict, run: dict, peaks: dict):
    """Both kinds' attends, the full layers' kernel launches with the
    reshapes about them and the window layers' XLA, over busy."""
    return _sum(_scope_pct(run, "full_attend"),
                _scope_pct(run, "window_attend"))
