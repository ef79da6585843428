"""From a profiler trace to numbers, with ``jax.profiler.ProfileData``
alone: device busy seconds, time by operation name, the longest idle
gaps and what the benchmark's own spans say the host was doing in them.

Two steps, so that the second can be held to a small recorded fixture:
:func:`read_events` turns an ``.xplane.pb`` into plain lists, and
:func:`reduce_events` does the arithmetic.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PALLAS = " (tpu_custom_call)"  # appended to a Pallas kernel's short name
BENCH_SPAN = "bench:"  # the benchmark's own TraceAnnotations


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(event_name: str) -> str:
    """The profiler names a device operation by its whole HLO text
    (``%splash_prefill.3 = (bf16[...]) custom-call(...),
    custom_call_target="tpu_custom_call"``); keep the instruction's name,
    and mark a Pallas kernel as one."""
    name = event_name.split(" = ")[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in event_name:
        name += PALLAS
    return name


def read_events(xplane_path: str) -> dict:
    """``{"devices": {id: [[name, start_ns, dur_ns], ...]}, "spans":
    [[name, start_ns, dur_ns], ...], "lines": {plane: [line names]}}``:
    each device's operations under their short names, and the
    benchmark's spans from the host threads."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: dict = {}
    spans, lines = [], {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                devices.setdefault(int(dev.group(1)), []).extend(
                    [short_name(ev.name), int(ev.start_ns),
                     int(ev.duration_ns)] for ev in line.events)
            elif not dev:
                spans.extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events
                    if ev.name.startswith(BENCH_SPAN))
    return {"devices": devices, "spans": spans, "lines": lines}


def _union(intervals):
    """Merged ``[start, end]`` pairs of ``(start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def base_name(op: str) -> str:
    """``splash_prefill.3 (tpu_custom_call)`` -> ``splash_prefill
    (tpu_custom_call)``; ``fusion.12`` -> ``fusion``: an operation's
    short name without the compiler's numbering."""
    return re.sub(r"(\.\d+)+(?=$| \()", "", op)


def reduce_events(events: dict, top: int = 10,
                  default_span: str = "no_bench_span") -> dict:
    """Busy seconds (the union of the operations' intervals, averaged
    over the devices), the window (first operation's start to the last
    one's end, over all devices), seconds and calls by operation name,
    and idle seconds by the benchmark span that covers each gap's middle
    (``default_span`` where none does: a span that began before the
    trace is not in it)."""
    devices = {d: ops for d, ops in events["devices"].items() if ops}
    if not devices:
        raise ValueError("the trace holds no device operation")
    first = min(s for ops in devices.values() for _, s, _ in ops)
    last = max(s + d for ops in devices.values() for _, s, d in ops)
    busy_ns, by_name, calls, gaps = 0, {}, {}, []
    for ops in devices.values():
        merged = _union((s, s + d) for _, s, d in ops)
        busy_ns += sum(e - s for s, e in merged)
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        # a while loop's event covers its body's: count leaves only, the
        # events that hold no other event of the same device
        for name, dur in _leaves(ops):
            by_name[name] = by_name.get(name, 0) + dur
            calls[name] = calls.get(name, 0) + 1
    idle_by_span: dict = {}
    spans = sorted((s, s + d, n) for n, s, d in events["spans"])
    for start, end in gaps:
        mid = (start + end) // 2
        label = default_span
        for s, e, n in spans:
            if s <= mid < e:
                label = n  # the innermost: later start wins
        idle_by_span[label] = idle_by_span.get(label, 0) + (end - start)
    n = len(devices)

    def ranked(table):
        return [[k, v / 1e9 / n] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / 1e9 / n, "window_s": (last - first) / 1e9,
            "op_seconds": {k: v / 1e9 / n for k, v in by_name.items()},
            "op_calls": {k: v / n for k, v in calls.items()},
            "device_ops": ranked(by_name), "idle_gaps": ranked(idle_by_span),
            "longest_gap_s": max((e - s for s, e in gaps), default=0) / 1e9}


def _leaves(ops):
    """``(base name, ns)`` of each operation that contains no other."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (name, start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < start + dur and \
                nxt[1] + nxt[2] <= start + dur and dur > 0:
            continue  # the next event starts and ends inside this one
        out.append((base_name(name), dur))
    return out


def kernel_seconds(reduced: dict, pattern: str, table="op_seconds") -> float:
    """Summed device seconds (or, from ``op_calls``, calls) of the
    operations whose base name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced[table].items() if rx.search(k))
