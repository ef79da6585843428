"""The program's own spans against the device's operations, from one
``.xplane.pb``: which phase of the engine's loop (or of the trainer's)
each idle gap of the device falls under, and the device's seconds by
``jax.named_scope``.

Two steps, as in :mod:`trace_reduce`, so that the arithmetic can be held
to a small recorded fixture: :func:`read_profile` turns the file into
plain lists, the other functions reduce those.

What a v5e trace carries (PR 25): the device plane's ``XLA Ops`` line
names each event by its HLO instruction and gives it no statistic but
its time; the name scope of an instruction (``.../optimizer_update/mul``)
is in the compiled module, which the profiler keeps whole in the
``/host:metadata`` plane. ``jax.profiler.ProfileData`` shows planes,
lines and events but not a plane's metadata table, so :func:`op_scopes`
reads that one table from the file's bytes (:func:`_fields`, some
twenty lines of protobuf wire format) and has jaxlib print the module.
The program's spans (``jax.profiler.TraceAnnotation``) are events on
their thread's line of a host plane, their keyword arguments the
event's statistics.
"""

from __future__ import annotations

import functools
import re

from chipbench.harness import trace_reduce

PROGRAM_SPANS = ("engine.", "lm_trainer.")


@functools.lru_cache(maxsize=2)
def read_profile(xplane_path: str) -> dict:
    """``{"spans": [[name, start_ns, dur_ns, {argument: value}], ...],
    "devices": {id: [[instruction, start_ns, dur_ns], ...]},
    "scopes": {instruction: name scope path}}``: the program's spans
    from the host threads, each device's operations under their
    instruction names (``fusion.12``; a Pallas kernel marked as in
    :func:`trace_reduce.short_name`), and the scope of each instruction
    where the file holds the compiled modules."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    spans, devices = [], {}
    for plane in data.planes:
        dev = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == trace_reduce.OPS_LINE:
                devices.setdefault(int(dev.group(1)), []).extend(
                    [trace_reduce.short_name(ev.name), int(ev.start_ns),
                     int(ev.duration_ns)] for ev in line.events)
            elif not dev:
                spans.extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns),
                     dict(ev.stats)] for ev in line.events
                    if ev.name.startswith(PROGRAM_SPANS))
    spans.sort(key=lambda s: s[1])
    return {"spans": spans, "devices": devices,
            "scopes": op_scopes(xplane_path)}


def profile_of(run: dict):
    """The profile of a runner's traced run, or ``None`` where the run
    was not traced."""
    if not run.get("trace_dir"):
        return None
    return read_profile(trace_reduce.find_xplane(run["trace_dir"]))


# -- the compiled modules' name scopes ---------------------------------------


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for a length-delimited field (fixed-width
    fields are skipped; nothing read here has one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


METADATA_PLANE = "/host:metadata"
SCOPED = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
    re.M)


def hlo_protos(xplane_path: str):
    """The serialized ``HloProto`` of each compiled module the profile
    holds: XSpace.planes(1) -> the metadata plane's event_metadata(4)
    map values(2) -> their stats(5) -> bytes_value(6)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    for tag, plane in _fields(space):
        if tag != 1:
            continue
        parts = list(_fields(plane))
        if not any(t == 2 and bytes(v) == METADATA_PLANE.encode()
                   for t, v in parts):
            continue
        for t, entry in parts:
            if t != 4:
                continue
            for et, meta in _fields(entry):
                if et != 2:
                    continue
                for mt, stat in _fields(meta):
                    if mt != 5:
                        continue
                    for st, value in _fields(stat):
                        if st == 6:
                            yield bytes(value)


def op_scopes(xplane_path: str) -> dict:
    """``{instruction name: op_name}`` over every module in the profile
    (the names are unique within a module; two modules that share one
    keep the first, and the traced window of a cell runs one or two).
    Empty where the file holds no module or jaxlib cannot print it: the
    metrics by scope are then left out, never guessed."""
    from jax._src.lib import xla_client

    scopes: dict = {}
    for blob in hlo_protos(xplane_path):
        try:
            # HloProto.hlo_module(1)
            module = next(bytes(v) for t, v in _fields(memoryview(blob))
                          if t == 1)
            text = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
                module).to_string()
        except Exception:  # a blob that is no module: nothing to name
            continue
        for name, scope in SCOPED.findall(text):
            scopes.setdefault(name, scope)
    return scopes


# -- idle gaps by the program's phase ----------------------------------------


def phase_of(span_name: str) -> str:
    return span_name.split(".", 1)[1]


def idle_by_phase(profile: dict, prefix: str = "engine.") -> dict:
    """Each idle gap of a device (between the merged intervals of its
    operations, first operation to last) split over the program's spans
    it intersects, by overlap: a gap that begins under ``stream`` and
    ends under ``upload`` gives each phase the part it covers. What no
    span covers is ``unattributed_s``; of that, ``outside_spans_s`` lies
    before the first span or after the last (the host's side of a
    profile starts later and stops earlier than the device's). Seconds,
    averaged over devices."""
    devices = {d: ops for d, ops in profile["devices"].items() if ops}
    if not devices:
        return {}
    spans = [(s, s + d, phase_of(n)) for n, s, d, _ in profile["spans"]
             if n.startswith(prefix) and d > 0]
    covered = (spans[0][0], max(e for _, e, _ in spans)) if spans else (0, 0)
    by_phase: dict = {}
    idle = window = outside = 0
    for ops in devices.values():
        merged = trace_reduce._union((s, s + d) for _, s, d in ops)
        window += merged[-1][1] - merged[0][0]
        k = 0  # spans are sorted and, on one thread, do not overlap
        for (_, a), (b, _) in zip(merged, merged[1:]):
            idle += b - a
            outside += (b - a) - max(
                0, min(b, covered[1]) - max(a, covered[0]))
            while k < len(spans) and spans[k][1] <= a:
                k += 1
            j = k
            while j < len(spans) and spans[j][0] < b:
                lo, hi, phase = spans[j]
                by_phase[phase] = by_phase.get(phase, 0) + (
                    min(hi, b) - max(lo, a))
                j += 1
    n = len(devices) * 1e9
    attributed = sum(by_phase.values())
    return {"window_s": window / n, "idle_s": idle / n,
            "by_phase": {p: v / n for p, v in by_phase.items()},
            "unattributed_s": (idle - attributed) / n,
            "outside_spans_s": outside / n}


# -- device seconds by scope --------------------------------------------------


def seconds_where(profile: dict, keep) -> tuple:
    """``(seconds, calls)`` of the leaf operations (those that hold no
    other: a while loop's event covers its body's) for which
    ``keep(instruction, scope)`` holds, averaged over devices."""
    devices = {d: ops for d, ops in profile["devices"].items() if ops}
    scopes = profile["scopes"]
    total = calls = 0
    for ops in devices.values():
        for name, dur in _leaves(ops):
            if keep(name, scopes.get(name.split(" ")[0], "")):
                total += dur
                calls += 1
    n = max(len(devices), 1)
    return total / 1e9 / n, calls / n


def _leaves(ops):
    """``(instruction, ns)`` of each operation that contains no other
    (:func:`trace_reduce._leaves` with the compiler's numbering kept:
    the scope table is keyed by it)."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    for i, (name, start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and nxt[1] < start + dur and \
                nxt[1] + nxt[2] <= start + dur and dur > 0:
            continue
        yield name, dur


def busy_seconds(profile: dict) -> float:
    devices = [ops for ops in profile["devices"].values() if ops]
    return sum(e - s for ops in devices for s, e in trace_reduce._union(
        (s, s + d) for _, s, d in ops)) / 1e9 / max(len(devices), 1)


def under_scope(scope: str):
    """A ``keep`` for :func:`seconds_where`: the scope path has the
    component ``scope`` (``.../optimizer_update/mul``, also inside
    ``transpose(jvp(...))`` wrappers)."""
    rx = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
    return lambda name, path: bool(rx.search(path))


if __name__ == "__main__":
    import json
    import sys

    prof = read_profile(trace_reduce.find_xplane(sys.argv[1]))
    print(json.dumps({"spans": len(prof["spans"]),
                      "scoped_instructions": len(prof["scopes"]),
                      "idle": idle_by_phase(prof),
                      "idle_trainer": idle_by_phase(prof, "lm_trainer.")}))
