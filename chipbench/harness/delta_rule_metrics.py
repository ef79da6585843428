"""Per-layer metrics of a model with recurrent-state layers (PR 36's
scopes ``delta_step`` and ``delta_chunk`` and its counters; the
``derived`` reader calls each as ``fn(cell, run, peaks)``). Each returns
``None`` where the program has no such scope or counter, as a model
without these layers has not: the metric is then left out of the line."""

from __future__ import annotations

from chipbench.harness import span_reduce
from chipbench.harness.span_metrics import _scope_pct
from chipbench.harness.sparse_moe_metrics import _ratio_pct, _sum
from chipbench.kernels import solar_open2

SCOPES = ("delta_step", "delta_chunk")


def delta_rule_device_pct(cell: dict, run: dict, peaks: dict):
    """The recurrent step and the chunk form, over busy."""
    return _sum(*(_scope_pct(run, s) for s in SCOPES))


def delta_state_roofline(cell: dict, run: dict, peaks: dict):
    """The least seconds for the state traffic the traced ticks need
    (:func:`chipbench.kernels.solar_open2.state_least_seconds`) over
    the device seconds under the two scopes."""
    profile = span_reduce.profile_of(run)
    if not profile:
        return None
    seconds = sum(span_reduce.seconds_where(
        profile, span_reduce.under_scope(s))[0] for s in SCOPES)
    least = solar_open2.state_least_seconds(cell, run, peaks)
    if not seconds or least is None:
        return None
    return 100.0 * least / seconds


def delta_chunk_useful_pct(cell: dict, run: dict, peaks: dict):
    """Tokens of the rows that fed more than one over the positions the
    chunk form ran for them."""
    return _ratio_pct(run, "chunk_positions_live_total",
                      "chunk_positions_computed_total")
