"""Per-layer metrics of a model with a learned selection over a latent
cache and routed experts (PR 28's scopes and counters; the ``derived``
reader calls each as ``fn(cell, run, peaks)``). Every one returns
``None`` where the program has no such scope or counter, as a model
without these mechanisms has not: the metric is then left out of the
line.

The mechanisms run as plain XLA under named scopes (``index_score``,
``index_select``, ``mla_attend``, ``moe_experts``): their share of the
device's busy seconds is read from the profile, and none has a roofline
(no kernel of this PR's to count operations and bytes for).
"""

from __future__ import annotations

from chipbench.harness.span_metrics import _scope_pct


def _sum(*parts):
    return None if all(p is None for p in parts) else sum(
        p or 0.0 for p in parts)


def index_device_pct(cell: dict, run: dict, peaks: dict):
    """The indexer's score over the held positions and the top-k
    threshold search, over busy."""
    return _sum(_scope_pct(run, "index_score"),
                _scope_pct(run, "index_select"))


def mla_attend_device_pct(cell: dict, run: dict, peaks: dict):
    return _scope_pct(run, "mla_attend")


def moe_experts_device_pct(cell: dict, run: dict, peaks: dict):
    return _scope_pct(run, "moe_experts")


def _ratio_pct(run: dict, over: str, under: str):
    stats = run.get("engine_stats", {})
    if not stats.get(under) or over not in stats:
        return None
    return 100.0 * stats[over] / stats[under]


def kv_selected_pct(cell: dict, run: dict, peaks: dict):
    """Positions the live queries' attends were allowed (``min(t + 1,
    index_topk)`` a query) over the causal positions a dense attend
    would have seen (``t + 1`` a query): both counted per query, so the
    share cannot pass 100 %. (``key_positions`` counts a row's positions
    once per row whatever the number of its queries, and is no
    denominator for a per-query count.)"""
    return _ratio_pct(run, "keys_selected_total", "attended_tokens_total")


def expert_rows_useful_pct(cell: dict, run: dict, peaks: dict):
    """(token, expert) pairs of live tokens sent to the experts held
    here over the rows the grouped matmul ran for them, its tiles'
    padding included."""
    return _ratio_pct(run, "routed_here_total",
                      "expert_rows_computed_total")
