"""The reduction from the program's spans and scopes to per-layer
metrics, on small fixtures: a recorded slice of a v5e profile of
``serve-chat-sat`` (two mixed ticks and a decode tick) and one of
``train-seq2k`` (one step), both of the final program (my chip runs,
PR 25, cut by ``chipbench.tools.cut_profile``), and arithmetic by hand."""

import json
import os

import pytest

from chipbench.harness import span_metrics, span_reduce, trace_reduce
from chipbench.kernels import causal_attention, splash_prefill

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        prof = json.load(f)
    prof["devices"] = {int(d): ops for d, ops in prof["devices"].items()}
    return prof


def ms(x):
    return int(x * 1e6)


# -- gaps by overlap, by hand ------------------------------------------------


def by_hand():
    """One device, three operations, two gaps: 10-16 ms lies under
    stream (9-12), plan (12-13) and upload (13-15), with 15-16 under no
    span; 20-22 lies inside wait."""
    ops = [["fusion.1", ms(0), ms(10)], ["fusion.2", ms(16), ms(4)],
           ["fusion.3", ms(22), ms(2)]]
    spans = [["engine.stream", ms(9), ms(3), {"tick": 1}],
             ["engine.plan", ms(12), ms(1), {"tick": 2}],
             ["engine.upload", ms(13), ms(2), {"tick": 2}],
             ["engine.wait", ms(17), ms(6), {"tick": 2}],
             ["bench:client.generate", ms(0), ms(30), {}]]
    return {"devices": {0: ops}, "spans": spans, "scopes": {}}


def test_a_gap_is_split_over_the_spans_it_intersects():
    idle = span_reduce.idle_by_phase(by_hand())
    assert idle["window_s"] == pytest.approx(0.024)
    assert idle["idle_s"] == pytest.approx(0.008)
    assert idle["by_phase"] == pytest.approx(
        {"stream": 0.002, "plan": 0.001, "upload": 0.002, "wait": 0.002})
    # the millisecond no span covers is reported, not spread
    assert idle["unattributed_s"] == pytest.approx(0.001)
    # a midpoint rule would have given all of 10-16 to plan or upload


def test_no_program_spans_no_attribution():
    prof = by_hand()
    prof["spans"] = [s for s in prof["spans"] if s[0].startswith("bench:")]
    idle = span_reduce.idle_by_phase(prof)
    assert idle["by_phase"] == {} and idle["unattributed_s"] == \
        pytest.approx(0.008)
    run = {"trace_dir": None, "flight": {"ticks": [
        {"kind": "tick", "plan_ms": 1.0, "device_ms": 20.0}]}}
    for fn in ("loop_host_ms", "idle_host_work_pct", "idle_handoff_pct",
               "tick_useful_pct", "optimizer_device_pct",
               "fused_ce_device_pct", "attention_fwd_roofline",
               "attention_bwd_roofline"):
        # a program without the spans, scopes or counters: no metric
        assert getattr(span_metrics, fn)({}, run, PEAKS) is None
    assert splash_prefill.least_seconds({}, run, {}, PEAKS) is None


def test_flight_metrics_by_hand():
    ticks = [{"kind": "tick", "loop_ms": 25.0, "device_wait_ms": 20.0,
              "idle_ms": 0.0, "decode_tokens": 16, "prefill_tokens": 64,
              "query_positions": 1024},
             {"kind": "tick", "loop_ms": 40.0, "device_wait_ms": 18.0,
              "idle_ms": 16.0, "decode_tokens": 16, "prefill_tokens": 0,
              "query_positions": 16},
             {"kind": "tick", "loop_ms": 24.0, "device_wait_ms": 20.5,
              "idle_ms": 0.0, "decode_tokens": 8, "prefill_tokens": 0}]
    run = {"flight": {"ticks": ticks}}
    assert span_metrics.loop_host_ms({}, run, PEAKS) == pytest.approx(5.0)
    assert span_metrics.tick_useful_pct({}, run, PEAKS) == pytest.approx(
        100.0 * 96 / 1040)


# -- the recorded slices -----------------------------------------------------


def test_recorded_serving_slice_attributes_its_idle():
    prof = fixture("profile_serve.json")
    idle = span_reduce.idle_by_phase(prof)
    assert idle["idle_s"] > 0
    assert set(idle["by_phase"]) <= {
        "ctrl", "admit", "plan", "upload", "dispatch", "wait", "stream",
        "record", "idle"}
    attributed = sum(idle["by_phase"].values())
    assert attributed + idle["unattributed_s"] == pytest.approx(
        idle["idle_s"])
    assert idle["unattributed_s"] < 0.05 * idle["idle_s"]
    # every phase's share is at most what the phase itself lasted
    lasted = {}
    for name, _, dur, _ in prof["spans"]:
        if name.startswith("engine."):
            p = span_reduce.phase_of(name)
            lasted[p] = lasted.get(p, 0) + dur / 1e9
    for p, s in idle["by_phase"].items():
        assert s <= lasted[p] + 1e-9


def test_splash_prefill_least_seconds_never_above_measured():
    prof = fixture("profile_serve.json")
    dealt = [a for n, _, _, a in prof["spans"]
             if n == "engine.dispatch" and a.get("chunk", 1) > 1]
    assert dealt
    layers, d_model = 24, 2048
    least = layers * sum(
        max(f / PEAKS["flops_bf16"], b / PEAKS["hbm_bytes_per_s"])
        for f, b in (splash_prefill.tick(
            a["attended_tokens"], a["key_positions"],
            a["n_dec"] + a["fed_tokens"], d_model) for a in dealt))
    measured, calls = span_reduce.seconds_where(
        prof, lambda name, scope: name.startswith("splash_prefill"))
    assert calls == layers * len(dealt)
    assert 0 < least < measured
    # a tick's counts: no more pairs than every query seeing every key
    for a in dealt:
        assert a["attended_tokens"] <= (a["n_dec"] + a["fed_tokens"]) * \
            a["key_positions"]
        assert a["n_dec"] + a["fed_tokens"] <= a["query_positions"]


def test_recorded_training_slice_by_scope_and_direction():
    prof = fixture("profile_train.json")
    busy = span_reduce.busy_seconds(prof)
    shares = {}
    for scope in ("optimizer_update", "fused_ce"):
        s, calls = span_reduce.seconds_where(
            prof, span_reduce.under_scope(scope))
        assert calls > 0
        shares[scope] = s / busy
    assert sum(shares.values()) < 1.0
    kernel = "CausalSelfAttention_0 (tpu_custom_call)"

    def calls_of(backward):
        return span_reduce.seconds_where(
            prof, lambda name, scope: trace_reduce.base_name(name) == kernel
            and ("transpose(" in scope) == backward)

    (fwd_s, fwd_n), (bwd_s, bwd_n) = calls_of(False), calls_of(True)
    # one forward and two backward calls a layer and step
    assert fwd_n > 0 and bwd_n == 2 * fwd_n
    shape = (4, 2048, 16, 128)
    fwd = fwd_n * causal_attention.roofline_seconds(
        *causal_attention.forward(*shape), PEAKS)
    bwd = bwd_n / 2 * causal_attention.roofline_seconds(
        *causal_attention.backward(*shape), PEAKS)
    assert 0 < fwd < fwd_s and 0 < bwd < bwd_s
    # the two shares bracket the whole kernel's
    whole = (fwd + bwd) / (fwd_s + bwd_s)
    assert min(fwd / fwd_s, bwd / bwd_s) <= whole <= max(fwd / fwd_s,
                                                         bwd / bwd_s)


def test_scope_components_not_substrings():
    keep = span_reduce.under_scope("fused_ce")
    assert keep("fusion.1", "jit(f)/jit(main)/fused_ce/dot_general")
    assert keep("fusion.1", "jit(f)/transpose(jvp(fused_ce))/mul")
    assert not keep("fusion.1", "jit(f)/my_fused_ce_like/mul")
    assert not keep("fusion.1", "")


def test_wire_reader_on_a_message_by_hand():
    # field 1 varint 300; field 2 bytes b"ab"; field 3 fixed32 (skipped)
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x1D, 1, 0, 0, 0])
    got = [(t, v if isinstance(v, int) else bytes(v))
           for t, v in span_reduce._fields(memoryview(buf))]
    assert got == [(1, 300), (2, b"ab")]


WAITING = {
    "serve-chat-sat": ["idle_handoff_pct.sat", "idle_host_work_pct.sat",
                       "loop_host_ms.sat", "splash_prefill_roofline.sat",
                       "tick_useful_pct.sat"],
    "serve-chat-knee60": ["idle_handoff_pct.knee", "idle_host_work_pct.knee",
                          "loop_host_ms.knee",
                          "splash_prefill_roofline.knee",
                          "tick_useful_pct.knee"],
    "train-seq2k": ["attention_bwd_roofline.train",
                    "attention_fwd_roofline.train",
                    "fused_ce_device_pct.train",
                    "optimizer_device_pct.train"]}


@pytest.mark.parametrize("name,metric", [
    (cell, m) for cell in sorted(WAITING) for m in WAITING[cell]])
def test_a_waiting_metric_joins_its_cell_through_spec(name, metric):
    """A metric that PR 25 brought names its cell in its own file:
    ``spec.cell`` puts it after the cell's own list, held to the same
    checks; it names a reader and a function that exist; and
    ``BENCHMARK.json`` lists it with that workload after the eight
    entries PR 24 was accepted with."""
    import json
    import os

    from chipbench.harness import readers, spec

    with open(os.path.join(spec.ROOT, "cells", name + ".json")) as f:
        own = json.load(f)["per_layer"]
    cell = spec.cell(name)
    assert cell["per_layer"] == own + WAITING[name]
    assert [m["name"] for m in cell["per_layer_specs"]] == cell["per_layer"]
    m = cell["per_layer_specs"][cell["per_layer"].index(metric)]
    assert m["moves"] in cell["end_to_end"]
    assert m["cells"] == [name] and m["since"] == 25
    assert m["reader"] in readers.READERS
    assert callable(spec.named(m.get("function", m.get("counts"))))
    with open(os.path.join(os.path.dirname(spec.ROOT),
                           "BENCHMARK.json")) as f:
        listed = json.load(f)["per_layer"]
    accepted = [e["name"] for e in listed[:8]]
    assert metric not in accepted
    assert all(spec.load("layer_metrics", n).get("since", 24) == 24
               for n in accepted)
    entry = [e for e in listed[8:] if e["name"] == metric]
    assert len(entry) == 1 and entry[0]["workloads"] == [name]
    assert entry[0]["moves"] == m["moves"] and entry[0]["layer"] == m["layer"]


@pytest.mark.parametrize("through", ["run", "span_run"])
def test_a_traced_run_without_a_chip_gives_no_result(through, capsys):
    """``tools.span_run`` is a forwarder to ``chipbench.run --trace 1``
    (documents outside the benchmark still name it)."""
    from chipbench import run
    from chipbench.tools import span_run

    argv = ["--workload", "serve-chat-sat", "--seed", "1", "--seconds", "1"]
    if through == "run":
        assert run.main(argv + ["--trace", "1"]) == 3
    else:
        assert span_run.main(argv) == 3
    assert "correct" not in capsys.readouterr().out
