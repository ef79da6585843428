"""PR 39's files: seventeen per-layer metrics of the engine's device
clock, found by name with nothing edited. Fifteen read ``stats()``
through the ``engine_stats`` reader (data files only); two hold the
clock to the device trace of the same run
(``harness/device_clock_metrics.py``), which is held here to a profile
worked by hand and to a recorded slice of a v5e profile
(``fixtures/profile_clock.json``: ``engine.record`` spans with the
clock's arguments beside the device's operations)."""

import json
import os

import jax
import pytest

from chipbench import run as entry
from chipbench.harness import device_clock_metrics, readers, span_reduce, spec
from chipbench.tests.tiny import tiny_cell

REPO = os.path.dirname(spec.ROOT)
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
SEED = 2 ** 31 + 39

CELLS = {"sat": "serve-chat-sat", "knee": "serve-chat-knee60",
         "prefill": "serve-prefill-sat", "longdoc": "serve-longdoc-sat",
         "mixedlen": "serve-mixedlen-sat", "reason": "serve-reason-sat"}
STATS = {"device_starved_pct": ("%", tuple(CELLS)),
         "device_unasked_pct": ("%", ("knee",)),
         "device_mixed_tick_ms": ("ms", ("sat", "prefill", "longdoc",
                                         "mixedlen", "reason")),
         "device_decode_tick_ms": ("ms", ("sat", "knee", "reason"))}
GAP = ("sat", "reason")
NEW = sorted([f"{m}.{s}" for m, (_, cells) in STATS.items() for s in cells]
             + [f"device_clock_gap_pct.{s}" for s in GAP])


def fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def test_the_metric_files_are_found_by_name():
    assert len(NEW) == 17
    for name in NEW:
        metric, suffix = name.rsplit(".", 1)
        cell = spec.cell(CELLS[suffix])
        assert name in cell["per_layer"]
        m = next(s for s in cell["per_layer_specs"] if s["name"] == name)
        assert (m["since"], m["cells"], m["layer"], m["better"]) == (
            39, [CELLS[suffix]], "engine loop", "lower")
        assert m["moves"] == ("itl_p95_ms" if suffix == "knee"
                              else "serve_tok_s")
        assert m["moves"] in cell["end_to_end"]
        if metric in STATS:
            assert (m["unit"], m["source"], m["reader"], m["key"]) == (
                STATS[metric][0], "program_counter", "engine_stats", metric)
        else:
            assert (m["unit"], m["source"], m["reader"]) == (
                "%", "program_span", "derived")
            assert spec.named(m["function"]) is \
                device_clock_metrics.device_clock_gap_pct
        # every metric of a cell that PR 39 did not bring comes before
        older = [s["name"] for s in cell["per_layer_specs"]
                 if s.get("since", spec.FIRST_PR) < 39]
        assert cell["per_layer"][:len(older)] == older


def test_benchmark_json_is_the_files_with_the_new_entries_last():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    per_layer = committed["per_layer"]
    assert len(per_layer) == 65 + 17
    assert [m["name"] for m in per_layer[65:]] == NEW
    assert per_layer[64]["name"] == "serve_mfu_pct.longdoc"
    assert not {m["name"] for m in per_layer[:65]} & set(NEW)
    assert len(committed["workloads"]) == 7 and len(committed["configs"]) == 5
    assert {m["layer"] for m in per_layer[65:]} == {"engine loop"}


# -- the clock against the trace, by hand -------------------------------------


def hand_made():
    """Three ticks of 10 ms on one device, the second handed over 2 ms
    late by a call that begins at 11.9 ms and returns at 15 (the clock
    says 1.5: starved) and the third after a doze of 4 ms (the clock
    says 3.9: unasked); 1 ms more of idle lies inside the first
    program, between two of its operations, where no host clock can see
    it. Spans cover the device's window. Times in ns."""
    ms = 1_000_000
    ops = [["fusion.1", 0, 4 * ms], ["fusion.2", 5 * ms, 5 * ms],
           ["fusion.1", 12 * ms, 10 * ms], ["fusion.1", 26 * ms, 10 * ms]]
    spans = [
        ["engine.plan", -1 * ms, 12.9 * ms, {"tick": 2}],
        ["engine.dispatch", 11.9 * ms, 3.1 * ms, {"tick": 2}],
        ["engine.wait", 15 * ms, 7 * ms, {"tick": 1}],
        ["engine.record", 22 * ms, 1 * ms, {
            "tick": 2, "program": "decode", "device_tick_ms": 10.0,
            "device_starved_ms": 1.5, "device_unasked_ms": 0.0}],
        ["engine.idle", 23 * ms, 2 * ms, {"tick": 3}],
        ["engine.dispatch", 25.9 * ms, 0.1 * ms, {"tick": 3}],
        ["engine.wait", 26 * ms, 10 * ms, {"tick": 3}],
        ["engine.record", 36 * ms, 1 * ms, {
            "tick": 3, "program": "mixed", "device_tick_ms": 10.0,
            "device_starved_ms": 0.0, "device_unasked_ms": 3.9}]]
    return {"devices": {"0": ops}, "spans": spans, "scopes": {}}


def test_the_gap_is_what_the_clock_says_against_what_the_trace_says():
    prof = hand_made()
    assert device_clock_metrics.said_gaps(prof) == [
        pytest.approx((10.4e6, 11.9e6)), pytest.approx((22e6, 25.9e6))]
    idle = span_reduce.idle_by_phase(prof)
    assert idle["idle_s"] == pytest.approx(7e-3)      # 1 + 2 + 4 ms
    assert idle["window_s"] == pytest.approx(36e-3)
    # said 1.5 + 3.9, traced 7.0: the half millisecond the clock put on
    # the tick, the tenth each call took to enqueue, and the
    # millisecond inside the program
    assert device_clock_metrics.gap_pct(prof) == pytest.approx(
        100 * 1.6 / 36)
    # the device took each tick up at the head of its dispatch span
    assert device_clock_metrics.start_in_dispatch(prof) == [
        (pytest.approx(0.1 / 3.1), pytest.approx(3.0)),
        (pytest.approx(1.0), pytest.approx(0.0))]
    assert device_clock_metrics.said_tick_ms(prof) == {
        "decode": [10.0], "mixed": [10.0]}
    # a gap that began before the spans did counts from where they begin
    late = hand_made()
    late["spans"] = [s for s in late["spans"] if s[1] >= 22_000_000]
    idle = span_reduce.idle_by_phase(late)
    assert idle["idle_s"] - idle["outside_spans_s"] == pytest.approx(4e-3)
    assert device_clock_metrics.gap_pct(late) == pytest.approx(
        100 * 0.1 / 36)


def test_a_program_from_before_the_clock_gives_nothing_and_does_not_raise():
    old = fixture("profile_serve.json")
    assert device_clock_metrics.said_gaps(old) is None
    assert device_clock_metrics.gap_pct(old) is None
    assert device_clock_metrics.said_tick_ms(old) == {}
    assert device_clock_metrics.device_clock_gap_pct(
        {}, {"trace_dir": None}, {}) is None
    bare = hand_made()
    for s in bare["spans"]:
        s[3] = {"tick": s[3]["tick"]}
    assert device_clock_metrics.gap_pct(bare) is None
    # spans with the arguments and no device operation: nothing to hold
    no_ops = dict(hand_made(), devices={})
    assert device_clock_metrics.gap_pct(no_ops) is None
    run = {"engine_stats": {"ticks": 10}}
    for name in NEW:
        m = spec.metric("layer_metrics", name)
        if m["reader"] == "engine_stats":
            assert readers.read(m, run, {}, None) is None


def test_the_recorded_slice_holds_the_clock_to_the_trace():
    """Ticks of ``serve-chat-sat`` on a v5e, cut from a traced run of
    the driver's command (my chip run, PR 39)."""
    prof = fixture("profile_clock.json")
    records = [a for n, _, _, a in prof["spans"]
               if n == "engine.record" and "device_tick_ms" in a]
    assert len(records) >= 4
    for a in records:
        assert a["program"] in ("decode", "mixed")
        assert float(a["device_tick_ms"]) > 1.0
        assert float(a["device_starved_ms"]) >= 0.0
        assert float(a["device_unasked_ms"]) == 0.0     # slots full
    gaps = device_clock_metrics.said_gaps(prof)
    assert gaps is not None and all(e > s for s, e in gaps)
    idle = span_reduce.idle_by_phase(prof)
    pct = device_clock_metrics.gap_pct(prof)
    said_s = sum(float(a["device_starved_ms"]) for a in records) / 1e3
    assert 0.0 <= pct < 5.0
    assert said_s <= idle["idle_s"] + 0.002
    # five ticks; the fourth waited 14.0 ms for an admission's own call
    # (12.4 ms of the device's 16.2 idle lie under engine.admit)
    assert [a["program"] for a in records] == ["decode"] * 3 + ["mixed"] * 2
    assert said_s == pytest.approx(14.030e-3, abs=1e-6)
    assert idle["idle_s"] == pytest.approx(16.248e-3, abs=1e-6)
    assert idle["by_phase"]["admit"] == pytest.approx(12.351e-3, abs=1e-6)
    assert pct == pytest.approx(3.005, abs=1e-3)
    # each tick the clock timed is as long as its operations span
    said = device_clock_metrics.said_tick_ms(prof)
    assert set(said) <= {"decode", "mixed"}


# -- the fifteen that read stats(), rehearsed on the CPU ----------------------


def test_rehearsal_at_a_tiny_size(tmp_path):
    cell = tiny_cell("serve-chat-sat")
    ctx = entry.make_ctx(jax.devices()[:1], str(tmp_path))
    run = entry.runner_for(cell["traffic_spec"]["kind"])(
        cell, SEED, 1.5, False, ctx)
    assert run["verdict"].correct
    stats = run["engine_stats"]
    assert stats["device_clock_ticks"] > 0
    mine = [m for m in cell["per_layer_specs"] if m["name"] in NEW]
    assert sorted(m["name"] for m in mine) == [
        "device_clock_gap_pct.sat", "device_decode_tick_ms.sat",
        "device_mixed_tick_ms.sat", "device_starved_pct.sat"]
    # (the derived reader wants a chip's peaks; the run was not traced)
    assert device_clock_metrics.device_clock_gap_pct(cell, run, {}) is None
    read = {m["name"]: readers.read(m, run, cell, None)
            for m in mine if m["reader"] == "engine_stats"}
    for name, value in read.items():
        assert value is not None and value >= 0.0, name
    assert read["device_starved_pct.sat"] == stats["device_starved_pct"]
    assert read["device_starved_pct.sat"] <= 100.0
    ticks = [t for t in run["flight"]["ticks"] if t.get("kind") == "tick"]
    assert all("device_tick_ms" in t and "program" in t for t in ticks)
