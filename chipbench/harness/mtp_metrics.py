"""Per-layer metrics of a model that drafts with its own
multi-token-prediction module (PR 41's scope ``mtp_draft`` and the
verify tick's counters; the ``derived`` reader calls each as ``fn(cell,
run, peaks)``). Every one returns ``None`` where the program has no such
scope or counter, as a model without a module has not: the metric is
then left out of the line."""

from __future__ import annotations

from chipbench.harness.span_metrics import _scope_pct
from chipbench.kernels import glm4_moe_lite


def _work(run: dict):
    """The verify ticks' sums in ``stats()``, under the names
    ``chipbench/kernels/glm4_moe_lite.py`` reads (``None``: an engine
    that runs no such tick). They run from the engine's start and the
    device clock from ``mark_steady``: the two warm-up requests (70
    prompt tokens and 4 emitted each, beside a run's ~400 000
    positions) are in the one and not in the other. The flight
    ring will not do: a traced run's ring holds the ticks of the drain
    behind the window, nearly empty (PR 41 read 4.84 % useful there
    against 65 untraced)."""
    stats = run.get("engine_stats", {})
    if not stats.get("window_positions_total"):
        return None
    return {"window_positions": stats["window_positions_total"],
            "draft_tokens": stats["draft_tokens_total"],
            "accepted_tokens": stats["accepted_tokens_total"],
            "overrun_tokens": stats["overrun_tokens"],
            "emitted_tokens": stats["tokens_generated"],
            "attended_tokens": stats["attended_tokens_total"]}


def spec_accept_pct(cell: dict, run: dict, peaks: dict):
    """Drafts the model kept over drafts put into verify windows."""
    stats = run.get("engine_stats", {})
    if not stats.get("draft_tokens_total"):
        return None
    return 100.0 * stats["accepted_tokens_total"] / stats[
        "draft_tokens_total"]


def mtp_draft_device_pct(cell: dict, run: dict, peaks: dict):
    """Device seconds under the scope ``mtp_draft`` (the module's
    projection, layer, norm and head; its layer's own ``mla_attend`` and
    ``moe_experts`` seconds lie under both scopes) over busy seconds."""
    return _scope_pct(run, "mtp_draft")


def tick_useful_pct(cell: dict, run: dict, peaks: dict):
    """Positions that entered a stream over the query positions the
    ticks' per-token layers ran, since the engine's start."""
    work = _work(run)
    if work is None:
        return None
    return 100.0 * glm4_moe_lite.kept_positions(work) / run[
        "engine_stats"]["query_positions_total"]


def serve_mfu_pct(cell: dict, run: dict, peaks: dict):
    """Useful operations of the verify ticks
    (``chipbench/kernels/glm4_moe_lite.py``) over the seconds the
    engine's device clock spans since ``mark_steady`` (ramp, window and
    what ran until ``stats()`` was read), over the chip's peak."""
    work = _work(run)
    seconds = run.get("engine_stats", {}).get("device_clock_span_ms", 0.0
                                              ) / 1e3
    if work is None or not seconds:
        return None
    flops = glm4_moe_lite.useful_flops(cell["config_spec"]["model"], work)
    return 100.0 * flops / (seconds * run["device"]["count"]
                            * peaks["flops_bf16"])
