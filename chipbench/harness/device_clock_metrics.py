"""The engine's device clock held to the device trace of the same run
(the ``derived`` reader calls ``device_clock_gap_pct(cell, run,
peaks)``).

Since PR 39 the ``engine.record`` span of a tick carries what the
program's own clock says of it: ``program``, ``device_tick_ms`` and, of
the time before the tick in which the device had nothing queued,
``device_starved_ms`` (the host was late) and ``device_unasked_ms``
(the loop dozed: nothing to run). The span is on the device trace's
clock, so the estimate and the device's operations are read on the same
run and the profiler's slowdown cancels. A program from before PR 39
puts no such argument on the span: every function here then returns
``None`` and the metric is left out of the line.
"""

from __future__ import annotations

from chipbench.harness import span_reduce, trace_reduce

MODULES_LINE = "XLA Modules"


def said_gaps(profile: dict):
    """``[(start_ns, end_ns)]``: where the program says the device had
    nothing queued, one interval a tick that had such a gap. A tick's
    gap ends where its jitted call begins (the head of its
    ``engine.dispatch`` span: the call enqueues the program there and
    may return milliseconds later) and is ``device_starved_ms +
    device_unasked_ms`` long. ``None`` where no ``engine.record`` span
    carries the clock's arguments."""
    dispatched, gaps, clocked = {}, [], False
    for name, start, dur, args in profile["spans"]:
        if name == "engine.dispatch" and "tick" in args:
            dispatched[int(args["tick"])] = start
    for name, _, _, args in profile["spans"]:
        if name != "engine.record" or "device_starved_ms" not in args:
            continue
        clocked = True
        gap_ns = 1e6 * (float(args["device_starved_ms"])
                        + float(args["device_unasked_ms"]))
        end = dispatched.get(int(args["tick"]))
        if gap_ns > 0 and end is not None:
            gaps.append((end - gap_ns, end))
    return gaps if clocked else None


def gap_pct(profile: dict):
    """100 x |what the program says the device waited - what the trace
    says it idled| over the traced window, both over the part of the
    window the engine thread's spans cover (the host's side of a
    profile starts later and stops earlier than the device's)."""
    gaps = said_gaps(profile)
    if gaps is None:
        return None
    idle = span_reduce.idle_by_phase(profile)
    if not idle:
        return None
    spans = [(s, s + d) for n, s, d, _ in profile["spans"]
             if n.startswith("engine.") and d > 0]
    ops = [(s, s + d) for dev in profile["devices"].values()
           for _, s, d in dev]
    lo = max(min(s for s, _ in spans), min(s for s, _ in ops))
    hi = min(max(e for _, e in spans), max(e for _, e in ops))
    said_s = sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in gaps) / 1e9
    traced_s = idle["idle_s"] - idle["outside_spans_s"]
    return 100.0 * abs(said_s - traced_s) / idle["window_s"]


def device_clock_gap_pct(cell: dict, run: dict, peaks: dict):
    profile = span_reduce.profile_of(run)
    return gap_pct(profile) if profile else None


# -- a finding's tools, not a metric ------------------------------------------


def said_tick_ms(profile: dict) -> dict:
    """``{program: [device_tick_ms, ...]}`` off the ``engine.record``
    spans of a profile."""
    out: dict = {}
    for name, _, _, args in profile["spans"]:
        if name == "engine.record" and "device_tick_ms" in args:
            out.setdefault(str(args["program"]), []).append(
                float(args["device_tick_ms"]))
    return out


def start_in_dispatch(profile: dict, least_gap_ns: int = 100_000):
    """Where in its ``engine.dispatch`` span the device took a tick up,
    for the ticks the device was idle before: ``[(fraction of the span,
    ms from the span's end)]``, one a device gap of ``least_gap_ns`` or
    more that ends inside a dispatch span. The clock stamps the head of
    the span: the device starts when the call has enqueued the
    program, which a v5e's profile puts 5-19 % into the span."""
    spans = sorted((s, s + d) for n, s, d, _ in profile["spans"]
                   if n == "engine.dispatch" and d > 0)
    out = []
    for ops in profile["devices"].values():
        merged = trace_reduce._union((s, s + d) for _, s, d in ops)
        k = 0
        for (_, a), (b, _) in zip(merged, merged[1:]):
            if b - a < least_gap_ns:
                continue
            while k < len(spans) and spans[k][1] < b:
                k += 1
            if k < len(spans) and spans[k][0] <= b:
                lo, hi = spans[k]
                out.append(((b - lo) / (hi - lo), (hi - b) / 1e6))
    return out


def traced_program_ms(xplane_path: str) -> dict:
    """``{module name: [ms, ...]}``: each run of each compiled program
    on the device plane's own per-program line, where the file has one
    (under ``"lines"``: the names of the lines the device planes hold)."""
    from jax.profiler import ProfileData

    out: dict = {"lines": []}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out["lines"].append(line.name)
            if line.name == MODULES_LINE:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(ev.duration_ns / 1e6)
    return out


if __name__ == "__main__":
    import json
    import statistics
    import sys

    path = trace_reduce.find_xplane(sys.argv[1])
    prof = span_reduce.read_profile(path)

    def summary(table):
        return {k: {"n": len(v), "median_ms": statistics.median(v),
                    "mean_ms": statistics.fmean(v)} if k != "lines" else v
                for k, v in table.items()}

    took = start_in_dispatch(prof)
    print(json.dumps({"device_clock_gap_pct": gap_pct(prof),
                      "start_in_dispatch": {
                          "n": len(took),
                          "fraction_quartiles": statistics.quantiles(
                              [f for f, _ in took], n=4),
                          "ms_before_the_end_quartiles": statistics.quantiles(
                              [m for _, m in took], n=4)} if len(took) > 3
                      else None,
                      "said": summary(said_tick_ms(prof)),
                      "traced": summary(traced_program_ms(path)),
                      "idle": span_reduce.idle_by_phase(prof)}))
