"""PR 38's files: ``serve_mfu_pct.longdoc`` with its function and the
count of useful operations (``chipbench/kernels/deepseek_v32.py``) are
found by name with nothing edited, ``BENCHMARK.json`` is
``spec.benchmark_json()`` of the files with the new entry last, and the
share reads the hand-worked count: on records written out here, and on
a rehearsed run at the tiny size."""

import json
import os

import pytest

from chipbench.harness import readers, serve_mfu, spec
from chipbench.kernels import deepseek_v32
from chipbench.tests.tiny import tiny_cell

REPO = os.path.dirname(spec.ROOT)
CELL = "serve-longdoc-sat"
NAME = "serve_mfu_pct.longdoc"
SEED = 2 ** 31 + 38


def test_the_metric_file_is_found_by_name():
    cell = spec.cell(CELL)
    assert cell["per_layer"][-1] == NAME
    m = cell["per_layer_specs"][-1]
    assert (m["since"], m["cells"], m["moves"], m["layer"], m["unit"],
            m["better"], m["source"], m["reader"]) == (
        38, [CELL], "serve_tok_s", "tick programs", "%", "higher",
        "program_counter", "derived")
    assert m["reader"] in readers.READERS
    assert spec.named(m["function"]) is serve_mfu.serve_mfu_pct
    # the layer is one BENCHMARK.json already names
    assert m["layer"] in {s["layer"] for s in cell["per_layer_specs"][:-1]}
    # and no other cell reports it
    assert [c for c in spec.names("cells")
            if NAME in spec.cell(c)["per_layer"]] == [CELL]


def test_benchmark_json_is_the_files_with_the_new_entry_last():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    assert len(committed["per_layer"]) == 65
    assert committed["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tick programs",
        "moves": "serve_tok_s", "workloads": [CELL]}
    assert not any("mfu" in m["name"] and CELL in m["workloads"]
                   for m in committed["per_layer"][:-1])
    assert len(committed["workloads"]) == 7 and len(committed["configs"]) == 5


def test_the_count_is_the_widths():
    """Two operations a matmul weight a fed token passes through, at
    the published widths over the five layers the configuration keeps
    (worked by hand, per layer: ``wq_a`` 7168 x 1536, ``wq_b`` 1536 x
    128 x 192, ``wkv_a`` 7168 x 576, the absorbed halves of ``wkv_b``
    2 x 128 x 128 x 512, ``wo`` 128 x 128 x 7168, the indexer's 1536 x
    64 x 128 + 7168 x 128 + 7168 x 64: 201 064 448; the dense layer's
    SwiGLU 3 x 7168 x 18432; an expert layer's shared expert 3 x 7168 x
    2048 and router 7168 x 256)."""
    model = spec.load("configs", "deepseek-v3.2-exp-serve")["model"]
    attention = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                 + 2 * 128 * 128 * 512 + 128 * 128 * 7168
                 + 1536 * 64 * 128 + 7168 * 128 + 7168 * 64)
    assert attention == 201_064_448
    per_token = 2 * (5 * attention + 3 * 7168 * 18432
                     + 4 * (3 * 7168 * 2048 + 7168 * 256))
    assert per_token == 3_170_369_536 == deepseek_v32.flops_per_token(model)
    tick = {"decode_tokens": 20, "prefill_tokens": 500, "routed_here": 260,
            "emitted": 21, "keys_selected": 520 * 2048,
            "index_positions_scored": 520 * 4000}
    assert deepseek_v32.tick_flops(model, tick) == (
        520 * per_token + 260 * 2 * 3 * 7168 * 2048
        + 21 * 2 * 7168 * 16160
        + 520 * 2048 * 5 * 2 * 128 * (576 + 512)
        + 520 * 4000 * 5 * 2 * 64 * 128) == 3_329_857_617_920
    # padding is not work: what a tick ran over does not enter
    assert deepseek_v32.tick_flops(
        model, dict(tick, query_positions=2048)) == deepseek_v32.tick_flops(
        model, dict(tick, query_positions=1024))


def _record(t, **counts):
    return {"kind": "tick", "t": t, "decode_tokens": 0, "prefill_tokens": 0,
            "keys_selected": 0, **counts}


def test_the_share_is_the_rings_operations_over_its_span():
    cell = spec.cell(CELL)
    model = cell["config_spec"]["model"]
    ticks = [_record(10.0, prefill_tokens=999),  # opens the span only
             _record(10.1, prefill_tokens=500, decode_tokens=20),
             {"kind": "control", "t": 10.15},
             _record(10.25, decode_tokens=32, emitted=32)]
    run = {"flight": {"ticks": ticks}, "device": {"count": 1}}
    want = (552 * deepseek_v32.flops_per_token(model)
            + 32 * 2 * 7168 * 16160) / 0.25 / 197e12 * 100
    got = serve_mfu.serve_mfu_pct(cell, run, {"flops_bf16": 197e12})
    assert got == pytest.approx(want, rel=1e-9) and 3.5 < got < 3.7
    # a program without the counters (another model's records), a ring
    # of one tick, no ring: nothing to read, and nothing raises
    bare = [{k: v for k, v in t.items() if k != "keys_selected"}
            for t in ticks]
    for flight in ({"ticks": bare}, {"ticks": ticks[:1]}, {}):
        assert serve_mfu.serve_mfu_pct(
            cell, {"flight": flight, "device": {"count": 1}},
            {"flops_bf16": 197e12}) is None


def test_the_share_on_a_rehearsed_run(tmp_path):
    """The tiny twin of the cell on the CPU through ``LMServer``: the
    reader takes its counters from the run's own flight records, and
    the share is the count worked by hand at the tiny widths (64 wide, 4
    heads, 2 layers of which 1 dense, 211 tokens; the ranks, the
    indexer and the experts as published) over the ring's span."""
    import jax

    from chipbench import run as entry

    cell = tiny_cell(CELL)
    ctx = entry.make_ctx(jax.devices()[:1], str(tmp_path))
    run = entry.runner_for(cell["traffic_spec"]["kind"])(
        cell, SEED, 1.5, False, ctx)
    ticks = [t for t in run["flight"]["ticks"] if t["kind"] == "tick"]
    assert len(ticks) > 2 and all("keys_selected" in t for t in ticks)
    attention = (64 * 1536 + 1536 * 4 * 192 + 64 * 576 + 2 * 4 * 128 * 512
                 + 4 * 128 * 64 + 1536 * 64 * 128 + 64 * 128 + 64 * 64)
    per_token = 2 * (2 * attention + 3 * 64 * 18432
                     + 3 * 64 * 2048 + 64 * 256)
    assert per_token == 65_765_376
    flops = sum(
        per_token * (t["decode_tokens"] + t["prefill_tokens"])
        + t["routed_here"] * 2 * 3 * 64 * 2048 + t["emitted"] * 2 * 64 * 211
        + t["keys_selected"] * 2 * 2 * 4 * (576 + 512)
        + t["index_positions_scored"] * 2 * 2 * 64 * 128
        for t in ticks[1:])
    assert flops > 0
    want = 100.0 * flops / ((ticks[-1]["t"] - ticks[0]["t"]) * 1e12)
    assert serve_mfu.serve_mfu_pct(
        cell, run, {"flops_bf16": 1e12}) == pytest.approx(want, rel=1e-9)
    # the blocks a chunk tick ran are on its record (one block here)
    assert any(t.get("live_blocks") == 1 for t in ticks)
