"""One reader per kind of per-layer metric. A metric's own file
(``layer_metrics/<name>.json``) names its reader and the reader's
parameters; a reader that finds nothing to read returns ``None`` and the
metric is left out of the line.

``run`` is what a runner returned, ``cell`` the resolved cell, ``trace``
the reduced profiler trace (``trace_reduce.reduce_events``) or ``None``.
"""

from __future__ import annotations

import statistics

from chipbench.harness import device, traffic, trace_reduce
from chipbench.harness.spec import named


def trace_idle(spec, run, cell, trace):
    """The share of the traced window in which no operation ran."""
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def trace_kernel(spec, run, cell, trace):
    """A kernel's share of its roofline: the least seconds the chip
    could take for the calls the trace holds (operations and bytes from
    the function ``spec["counts"]`` names, on the cell's shapes) over
    the seconds the events named ``spec["pattern"]`` took."""
    if trace is None:
        return None
    seconds = trace_reduce.kernel_seconds(trace, spec["pattern"])
    if seconds <= 0:
        return None
    least = named(spec["counts"])(
        cell, run, trace, device.peaks(run["device"]["kind"]))
    return None if least is None else 100.0 * least / seconds


def engine_stats(spec, run, cell, trace):
    value = run.get("engine_stats", {}).get(spec["key"])
    if value is None:
        return None
    if "over_engine_arg" in spec:
        value = value / cell["config_spec"]["engine"][spec["over_engine_arg"]]
    return value * spec.get("scale", 1.0)


def flight(spec, run, cell, trace):
    """From the engine's flight recorder (host clock inside the engine's
    loop): the median of one field over the recorded ticks, or the host's
    share of the tick, (plan + stream) over (plan + device + stream)."""
    ticks = [t for t in run.get("flight", {}).get("ticks", [])
             if t.get("kind") == "tick"]
    if not ticks:
        return None
    if spec["reduce"] == "host_share":
        host = sum(t["plan_ms"] + t["stream_ms"] for t in ticks)
        return 100.0 * host / sum(t["tick_ms"] for t in ticks)
    return statistics.median(t[spec["field"]] for t in ticks)


def tracer_span(spec, run, cell, trace):
    """A percentile of the lengths of the program's own spans of one
    name, for the requests the window counted."""
    ms = [s["ms"] for s in run.get("tracer_spans", [])
          if s["span"] == spec["span"]]
    return traffic.percentile(ms, spec["percentile"]) if ms else None


def client(spec, run, cell, trace):
    values = run.get(spec["field"])
    return traffic.percentile(values, spec["percentile"]) if values else None


def derived(spec, run, cell, trace):
    return named(spec["function"])(
        cell, run, device.peaks(run["device"]["kind"]))


READERS = {f.__name__: f for f in (trace_idle, trace_kernel, engine_stats,
                                   flight, tracer_span, client, derived)}


def read(spec: dict, run: dict, cell: dict, trace):
    return READERS[spec["reader"]](spec, run, cell, trace)
