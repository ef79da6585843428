"""Per-layer metrics read from the program's own spans, scopes and
counters (the ``derived`` reader calls each as ``fn(cell, run,
peaks)``). Every one returns ``None`` where the program has nothing of
the kind to read, as a commit from before PR 25 has not: the metric is
then left out of the line."""

from __future__ import annotations

import re
import statistics

from chipbench.harness import span_reduce, trace_reduce
from chipbench.kernels import causal_attention

# the engine thread working while the device has nothing queued (a
# pipelined loop hides this part), and the two crossings of the
# host-device boundary (only fewer or lighter transfers shrink that)
HOST_WORK = ("ctrl", "admit", "plan", "stream", "record")
HANDOFF = ("upload", "dispatch", "wait")


def _ticks(run: dict, field: str):
    return [t for t in run.get("flight", {}).get("ticks", [])
            if t.get("kind") == "tick" and field in t]


# -- engine loop --------------------------------------------------------------


def loop_host_ms(cell: dict, run: dict, peaks: dict):
    """Median per tick of what the engine thread does outside the
    blocking read: the period less the read (and less dozing, which is
    not doing)."""
    ticks = _ticks(run, "loop_ms")
    if not ticks:
        return None
    return statistics.median(
        t["loop_ms"] - t["device_wait_ms"] - t.get("idle_ms", 0.0)
        for t in ticks)


def _idle_pct(run: dict, phases):
    profile = span_reduce.profile_of(run)
    idle = span_reduce.idle_by_phase(profile) if profile else {}
    if not idle or not idle["by_phase"]:
        return None
    return 100.0 * sum(idle["by_phase"].get(p, 0.0) for p in phases) / \
        idle["window_s"]


def idle_host_work_pct(cell: dict, run: dict, peaks: dict):
    return _idle_pct(run, HOST_WORK)


def idle_handoff_pct(cell: dict, run: dict, peaks: dict):
    return _idle_pct(run, HANDOFF)


# -- tick programs ------------------------------------------------------------


def tick_useful_pct(cell: dict, run: dict, peaks: dict):
    """Decode and fed tokens over the query positions the mixed ticks
    computed, over the ticks the flight ring holds."""
    ticks = _ticks(run, "query_positions")
    if not ticks:
        return None
    return 100.0 * sum(t["decode_tokens"] + t["prefill_tokens"]
                       for t in ticks) / sum(t["query_positions"]
                                             for t in ticks)


# -- train step ---------------------------------------------------------------


def _scope_pct(run: dict, scope: str):
    profile = span_reduce.profile_of(run)
    if not profile:
        return None
    seconds, calls = span_reduce.seconds_where(
        profile, span_reduce.under_scope(scope))
    busy = span_reduce.busy_seconds(profile)
    return 100.0 * seconds / busy if calls and busy else None


def optimizer_device_pct(cell: dict, run: dict, peaks: dict):
    return _scope_pct(run, "optimizer_update")


def fused_ce_device_pct(cell: dict, run: dict, peaks: dict):
    return _scope_pct(run, "fused_ce")


# -- kernels ------------------------------------------------------------------


def _attention_roofline(cell, run, peaks, backward: bool):
    """The training attention calls' share of their roofline, the
    forward call apart from the two backward calls (dq; dk with dv).
    All three are named ``CausalSelfAttention_0``; the backward ones sit
    under ``transpose(jvp(...))`` in their scope. One forward call makes
    one group and two backward calls the other."""
    profile = span_reduce.profile_of(run)
    if not profile or not profile["scopes"]:
        return None
    kernel = re.compile(causal_attention.PATTERN)

    def keep(name, scope):
        return (kernel.match(trace_reduce.base_name(name)) is not None
                and ("transpose(" in scope) == backward)

    seconds, calls = span_reduce.seconds_where(profile, keep)
    if not calls:
        return None
    model = cell["config_spec"]["model"]
    H = model["num_heads"]
    shape = (cell["config_spec"]["trainer"]["batch_size"],
             cell["traffic_spec"]["seq_len"], H, model["d_model"] // H)
    if backward:
        least = calls / 2 * causal_attention.roofline_seconds(
            *causal_attention.backward(*shape), peaks)
    else:
        least = calls * causal_attention.roofline_seconds(
            *causal_attention.forward(*shape), peaks)
    return 100.0 * least / seconds


def attention_fwd_roofline(cell: dict, run: dict, peaks: dict):
    return _attention_roofline(cell, run, peaks, backward=False)


def attention_bwd_roofline(cell: dict, run: dict, peaks: dict):
    return _attention_roofline(cell, run, peaks, backward=True)
