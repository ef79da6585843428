"""The benchmark's own tests, on the CPU at tiny sizes (``tiny.py``).

Run with ``python -m pytest chipbench/tests -q -p no:cacheprovider``.
"""

import copy
import functools
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from tiny import TINY_MODEL, tiny_cell  # noqa: E402

from chipbench import run as entry  # noqa: E402
from chipbench.harness import (device, readers, reference, serve_runner,  # noqa: E402
                               spec, traffic, trace_reduce, train_runner)

REPO = os.path.dirname(spec.ROOT)
CELLS = spec.names("cells")
SEED = 2 ** 31 + 12345  # the driver's seeds do not fit 32 signed bits


# -- the data files -----------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_and_cross_reference(name):
    cell = spec.cell(name)
    assert cell["config_spec"]["role"] in ("train", "serve")
    assert cell["traffic_spec"]["kind"] in ("train_job", "closed_loop",
                                            "open_loop")
    for m in cell["end_to_end_specs"] + cell["per_layer_specs"]:
        assert spec.NAME.match(m["name"]) and spec.UNIT.match(m["unit"])
    for m in cell["per_layer_specs"]:
        assert m["reader"] in readers.READERS
        assert m["moves"] in cell["end_to_end"]
    for m in cell["recorded_specs"]:  # kept beside the result, no claim
        assert m["reader"] in readers.READERS and "moves" not in m
    for key in cell["limits"]:
        assert isinstance(cell["limits"][key], (int, float))


def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    assert set(committed) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= committed["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in committed["end_to_end"])
    for m in committed["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for c in committed["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))


@pytest.mark.parametrize("role", ["train", "serve"])
def test_configs_keep_every_published_width(role):
    cfg = spec.load("configs", f"cerebras-gpt-1.3b-{role}")
    assert (cfg["n_embd"], cfg["n_head"], cfg["n_inner"], cfg["vocab_size"],
            cfg["n_positions"]) == (2048, 16, 8192, 50257, 2048)
    m = cfg["model"]
    assert (m["d_model"], m["num_heads"], m["vocab_size"], m["max_len"],
            m["num_layers"]) == (2048, 16, 50257, 2048, cfg["n_layer"])
    assert ("n_layer" in cfg["reduced"]) == (cfg["n_layer"] != 24)
    for key in cfg["reduced"]:
        assert key in cfg["published"] and cfg[key] != cfg["published"][key]
    if role == "serve":
        assert cfg["n_layer"] == 24


def _copied_root(tmp_path) -> str:
    root = str(tmp_path / "chipbench")
    shutil.copytree(spec.ROOT, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "tools", "harness", "kernels", "*.py"))
    return root


def _add(root, kind, name, base, drop=(), **changes):
    new = {k: v for k, v in spec.load(kind, base, root).items()
           if k != "name" and k not in drop}
    new.update(changes)
    with open(os.path.join(root, kind, name + ".json"), "w") as f:
        json.dump(new, f)


def test_files_alone_add_a_config_a_mix_a_cell_and_a_metric(tmp_path):
    root = _copied_root(tmp_path)
    before = spec.benchmark_json(root)

    add = functools.partial(_add, root)
    add("configs", "cerebras-gpt-1.3b-serve8", "cerebras-gpt-1.3b-serve",
        engine={"slots": 8, "max_len": 2048})
    add("traffic", "chat-sat64", "chat-sat", clients=64)
    add("layer_metrics", "tick_device_ms.sat64", "tick_device_ms.knee",
        moves="serve_tok_s", since=99)
    add("cells", "serve8-chat-sat64", "serve-chat-sat", order=9,
        config="cerebras-gpt-1.3b-serve8", traffic="chat-sat64",
        per_layer=["occupancy_pct.sat", "tick_device_ms.sat64"])
    after = spec.benchmark_json(root)
    assert [w["name"] for w in after["workloads"]] == \
        [w["name"] for w in before["workloads"]] + ["serve8-chat-sat64"]
    assert len(after["configs"]) == len(before["configs"]) + 1
    added = [m for m in after["per_layer"]
             if m["name"] == "tick_device_ms.sat64"]
    assert added and added[0]["workloads"] == ["serve8-chat-sat64"]
    occupancy = [m for m in after["per_layer"]
                 if m["name"] == "occupancy_pct.sat"][0]
    assert occupancy["workloads"] == ["serve-chat-sat", "serve8-chat-sat64"]
    # and the harness runs the new cell with no line of code added
    cell = spec.cell("serve8-chat-sat64", root)
    assert cell["traffic_spec"]["clients"] == 64
    assert entry.runner_for(cell["traffic_spec"]["kind"]) is serve_runner.run


@pytest.fixture
def rope_lm(monkeypatch):
    """A second name in the program's registry: the same stack with
    rotary positions, as a later PR's architecture would register its
    own class."""
    from distkeras_tpu.models import registry

    transformer_lm = registry._REGISTRY["transformer_lm"]
    monkeypatch.setitem(
        registry._REGISTRY, "rope_lm",
        lambda **kwargs: transformer_lm(pos_emb="rope", **kwargs))


def test_files_alone_add_a_model_a_reference_and_a_waiting_metric(
        tmp_path, out_dir, capsys, rope_lm):
    """What a later PR brings, rehearsed: a configuration whose model is
    another name of the registry and whose block the accepted reference
    does not compute, with a reference of its own; a metric for a cell
    that exists. Every seam is crossed by a name in a new file, and no
    file is edited."""
    root = _copied_root(tmp_path)
    before = spec.benchmark_json(root)
    _add(root, "configs", "rope-serve", "cerebras-gpt-1.3b-serve",
         model_name="rope_lm", rope_theta=10000.0,
         reference="chipbench.tests.rope_reference")
    _add(root, "cells", "rope-chat-sat", "serve-chat-sat", order=9,
         config="rope-serve")
    _add(root, "layer_metrics", "loop_host_ms.late", "loop_host_ms.sat",
         cells=["serve-chat-sat", "rope-chat-sat"], since=99)
    for key in ("reference", "model_name"):
        _add(root, "configs", f"rope-serve-no-{key}", "rope-serve",
             drop=(key,))
        _add(root, "cells", f"rope-chat-sat-no-{key}", "rope-chat-sat",
             config=f"rope-serve-no-{key}")

    # the metric joins the accepted cell after its own and the waiting
    # ones, and comes last in BENCHMARK.json; every entry before it stays
    assert spec.cell("serve-chat-sat", root)["per_layer"] == \
        spec.cell("serve-chat-sat")["per_layer"] + ["loop_host_ms.late"]
    after = spec.benchmark_json(root)
    assert after["per_layer"][-1]["name"] == "loop_host_ms.late"
    assert after["per_layer"][-1]["workloads"] == ["serve-chat-sat",
                                                   "rope-chat-sat"]
    # an accepted metric the new cells list themselves gains them; no
    # entry moves
    assert [m["name"] for m in after["per_layer"][:-1]] == \
        [m["name"] for m in before["per_layer"]]

    cell = tiny_cell("rope-chat-sat", root)
    assert spec.model_name(cell["config_spec"]) == "rope_lm"
    assert "pos_emb" not in cell["config_spec"]["model"]
    assert spec.reference(cell["config_spec"]).__name__ == \
        "chipbench.tests.rope_reference"
    capsys.readouterr()
    result = entry.execute(cell, SEED, 1.5, False, jax.devices()[:1], out_dir)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert list(result)[-1] == "compared"
    assert result["compared"]["served_logit_gap.max"]["value"] <= \
        result["compared"]["served_logit_gap.max"]["limit"] == 1e-3
    assert capsys.readouterr().err.rstrip().splitlines()[-1].startswith(
        '{"check"')
    # the same files with either name taken out: the accepted reference
    # against the rotary model, the rotary reference against the default
    # model. Each dispatch chose, not chance.
    for key in ("reference", "model_name"):
        wrong = tiny_cell(f"rope-chat-sat-no-{key}", root)
        assert (spec.reference(wrong["config_spec"]) is reference) == \
            (key == "reference")
        assert (spec.model_name(wrong["config_spec"]) == "transformer_lm") \
            == (key == "model_name")
        assert entry.execute(wrong, SEED, 1.5, False, jax.devices()[:1],
                             out_dir)["correct"] is False


def test_a_metric_file_that_names_no_cell_or_no_pr_is_refused(tmp_path):
    root = _copied_root(tmp_path)
    _add(root, "layer_metrics", "loop_host_ms.typo", "loop_host_ms.sat",
         cells=["serve-chat-sta"])
    with pytest.raises(ValueError, match="which is no cell"):
        spec.benchmark_json(root)
    # without "since" it would sort among the accepted eight and move them
    _add(root, "layer_metrics", "loop_host_ms.typo", "loop_host_ms.sat",
         drop=("since",))
    with pytest.raises(ValueError, match='no "since"'):
        spec.cell("serve-chat-sat", root)
    with pytest.raises(ValueError, match='no "since"'):
        spec.benchmark_json(root)
    _add(root, "layer_metrics", "loop_host_ms.typo", "loop_host_ms.sat",
         since=30)
    assert spec.benchmark_json(root)["per_layer"][-1]["name"] == \
        "loop_host_ms.typo"
    # the eight PR 24 was accepted with are the ones that say none
    assert spec.FIRST == {n for n in spec.names("layer_metrics")
                          if "since" not in spec.load("layer_metrics", n)}
    assert [m["name"] for m in spec.benchmark_json()["per_layer"][:8]] == \
        sorted(spec.FIRST)


@pytest.mark.parametrize("length,padded", [
    (1, 256), (256, 256), (257, 512), (1024, 1024), (1025, 2048),
    (2048, 2048), (2049, 4096), (5000, 8192)])
def test_the_references_sequence_pads_to_a_power_of_two(length, padded):
    assert serve_runner.padded_length(length) == padded
    if length <= 2048:  # what the accepted cells compile, as before
        assert padded == next(b for b in (256, 512, 1024, 2048)
                              if b >= length)


def test_the_warm_up_prompt_follows_the_engines_chunk():
    class Engine:
        prefill_chunk = 64

    assert serve_runner.warm_prompt_len(Engine) == 70  # as the parent's
    Engine.prefill_chunk = 16
    assert serve_runner.warm_prompt_len(Engine) == 22
    with pytest.raises(ValueError, match="unknown traffic kind"):
        entry.runner_for("no_such_kind")
    assert entry.runner_for("train_job") is train_runner.run


def test_bad_names_and_references_are_refused(tmp_path):
    with pytest.raises(ValueError):
        spec.load("cells", "no such cell")
    cell = spec.cell(CELLS[0])
    bad = dict(cell["per_layer_specs"][0], moves="not_reported")
    root = _copied_root(tmp_path)
    name = cell["per_layer"][0]
    with open(os.path.join(root, "layer_metrics", name + ".json"), "w") as f:
        json.dump({k: v for k, v in bad.items() if k != "name"}, f)
    with pytest.raises(ValueError, match="does not report"):
        spec.cell(CELLS[0], root)


# -- the generator ------------------------------------------------------------


def test_schedule_is_a_function_of_the_seed_alone():
    a = traffic.poisson_schedule(4.0, 50.0, SEED)
    b = traffic.poisson_schedule(4.0, 50.0, SEED)
    c = traffic.poisson_schedule(4.0, 50.0, SEED + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # every seed: as many requests, over as long, the same gaps reordered
    assert len(a) == len(c) == 200 and a[0] == c[0] == 0.0
    assert np.allclose(np.sort(np.diff(a, prepend=0)[1:]),
                       np.sort(np.diff(c, prepend=0)[1:]), atol=1e-9) or \
        np.isclose(a[-1], c[-1], rtol=0.05)
    assert 40.0 < a[-1] < 60.0


def test_sizes_are_the_same_multiset_for_every_seed():
    mix = spec.load("traffic", "chat-knee60")
    a = traffic.request_sizes(mix, 200, SEED)
    b = traffic.request_sizes(mix, 200, SEED + 7)
    assert a != b
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert all(16 <= p <= 1536 and 1 <= o <= 448 and p + o <= 2048
               for p, o in a)
    assert traffic.request_sizes(mix, 200, SEED) == a
    p0 = traffic.prompt_tokens(50257, 40, SEED, 3)
    assert np.array_equal(p0, traffic.prompt_tokens(50257, 40, SEED, 3))
    assert not np.array_equal(p0, traffic.prompt_tokens(50257, 40, SEED, 4))


def test_lateness_is_taken_from_due_times():
    class Late:
        def generate(self, prompt, n):
            return 1

        def frames(self, rid):
            yield "tok", 5
            yield "end", "length"

    import time

    req = serve_runner.Request(0, np.zeros(4, np.int32), 1,
                               due=time.perf_counter() - 0.25)
    req.drive(Late())
    assert req.ok and 0.25 <= req.sent - req.due < 0.5
    assert (req.times[0] - req.due) >= 0.25  # first token counted from due


@pytest.mark.parametrize("values,p,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 95, 19.5), ([7], 95, 7.0), (list(range(101)), 95, 95.0),
])
def test_percentile(values, p, want):
    assert traffic.percentile(values, p) == pytest.approx(want)
    assert traffic.percentile(values, p) == pytest.approx(
        float(np.percentile(values, p)))


# -- the reference ------------------------------------------------------------


TINY_CONFIG = tiny_cell("serve-chat-sat")["config_spec"]


@pytest.fixture(scope="module")
def tiny_lm():
    from distkeras_tpu.models import get_model

    model = get_model(spec.model_name(TINY_CONFIG), **TINY_MODEL,
                      dtype=jnp.float32)
    return model, reference.make_params(TINY_CONFIG, SEED)


def test_weights_have_the_programs_layout_and_follow_the_seed(tiny_lm):
    model, params = tiny_lm
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(params)))
    again = reference.make_params(TINY_CONFIG, SEED)
    other = reference.make_params(TINY_CONFIG, SEED + 1)
    for a, b, c in zip(*map(jax.tree.leaves, (params, again, other))):
        assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_reference_agrees_with_the_model_in_float32(tiny_lm):
    import optax

    model, params = tiny_lm
    toks = traffic.rng(SEED, 9).integers(0, 211, size=(3, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, jnp.asarray(toks))
    got = reference.forward_logits(TINY_CONFIG, params, toks[0],
                                   np.arange(40), pad_to=64)
    assert float(jnp.abs(want[0] - got).max()) < 1e-4

    def objective(p):
        logits = model.apply(p, jnp.asarray(toks))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], jnp.asarray(toks)[:, 1:]).mean()

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(objective)(params)
    ref_loss, ref_grads = reference.loss_and_grads(params, toks)
    assert ref_loss == pytest.approx(float(loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(a)) + 1e-9
    assert np.mean(reference.row_losses(TINY_CONFIG, params,
                                        toks)) == pytest.approx(
        ref_loss, rel=1e-5)


def test_int8_control_moves_the_logits(tiny_lm):
    _, params = tiny_lm
    toks = traffic.rng(SEED, 9).integers(0, 211, size=40).astype(np.int32)
    at = np.arange(40)
    exact = reference.forward_logits(TINY_CONFIG, params, toks, at)
    low = reference.forward_logits(TINY_CONFIG, params, toks, at,
                                   precision="int8")
    assert 1e-3 < float(jnp.abs(exact - low).max()) < 1.0


def test_the_sample_grows_until_it_holds_enough_near_ties(monkeypatch):
    """Ten tokens a request, two of them near-ties and one of those far
    off the best: the sample stops once it holds the near-ties wanted,
    and a thin sample is counted against the near-ties wanted."""
    reqs = [serve_runner.Request(i, np.zeros(4 + i, np.int32), 10)
            for i in range(9)]
    for r in reqs:
        r.tokens = [0] * 10
    gap = np.array([0.2] + [0.0] * 9, np.float32)
    margin = np.array([0.01, 0.02] + [0.5] * 8, np.float32)
    monkeypatch.setattr(serve_runner, "request_readings",
                        lambda cfg, variables, r, precision="f32": (
                            gap, margin))
    limits = {"sample_requests": 3, "sample_requests_max": 8,
              "near_tie_margin": 0.05, "near_ties_wanted": 10,
              "far_off_gap": 0.1, "served_logit_gap_max": 1.0,
              "served_far_off_per_near_tie": 0.6}
    gaps, _ = serve_runner.sample_readings(None, None, reqs, SEED, limits)
    assert len(gaps) == 5
    assert serve_runner.sample_order(reqs, SEED)[0] is reqs[-1]
    cell = {"limits": limits}
    row = serve_runner.check(cell, None, SEED, None, reqs).rows[-1]
    assert (row["far_off"], row["near_ties"], row["value"]) == (5, 10, 0.5)
    thin = serve_runner.check(cell, None, SEED, None, reqs[:2]).rows[-1]
    assert (thin["far_off"], thin["near_ties"], thin["value"]) == (2, 4, 0.2)
    limits["served_far_off_per_near_tie"] = 0.4
    assert not serve_runner.check(cell, None, SEED, None, reqs).correct


# -- the trace ----------------------------------------------------------------


def test_trace_reduce_on_a_recorded_trace():
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "trace_events.json")) as f:
        events = json.load(f)
    events["devices"] = {int(k): v for k, v in events["devices"].items()}
    out = trace_reduce.reduce_events(events)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"] == sorted(out["device_ops"],
                                       key=lambda kv: -kv[1])
    total = sum(out["op_seconds"].values())
    assert total == pytest.approx(out["busy_s"], rel=0.05)


def test_trace_arithmetic_by_hand():
    events = {"devices": {0: [["fusion.1", 0, 100], ["while.2", 200, 300],
                              ["attn.7", 210, 100], ["fusion.3", 400, 50],
                              ["fusion.4", 900, 100]]},
              "spans": [["bench:outer", 0, 1000], ["bench:inner", 500, 450]]}
    out = trace_reduce.reduce_events(events)
    assert out["busy_s"] == pytest.approx(500e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["op_seconds"] == {"fusion": pytest.approx(250e-9),
                                 "attn": pytest.approx(100e-9)}
    assert dict(map(tuple, out["idle_gaps"])) == {
        "bench:outer": pytest.approx(100e-9),
        "bench:inner": pytest.approx(400e-9)}
    assert trace_reduce.kernel_seconds(out, "^attn$") == pytest.approx(100e-9)
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"devices": {}, "spans": []})


# -- the runners, rehearsed ---------------------------------------------------


def test_no_chip_no_result(capsys):
    assert jax.default_backend() == "cpu"
    assert entry.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                       "1", "--trace", "0"]) != 0
    assert "correct" not in capsys.readouterr().out
    with pytest.raises(device.NoChip):
        device.peaks("cpu")


@pytest.fixture(scope="module")
def out_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chipbench_out"))


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_of_each_runner(name, out_dir):
    cell = tiny_cell(name)
    result = entry.execute(cell, SEED, 1.5, False, jax.devices()[:1], out_dir)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == set(cell["end_to_end"])
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result.get("recorded", {})) == set(cell.get("recorded", []))
    # a CPU run names its device, and has no device trace to reduce
    assert result["device"]["platform"] == "cpu"
    with pytest.raises((ValueError, FileNotFoundError)):
        entry.execute(cell, SEED, 1.5, True, jax.devices()[:1], out_dir)


def _run(name, out_dir, seconds=1.5):
    cell = tiny_cell(name)
    ctx = entry.make_ctx(jax.devices()[:1], out_dir)
    runner = entry.runner_for(cell["traffic_spec"]["kind"])
    return runner(cell, SEED, seconds, False, ctx)


@pytest.mark.parametrize("name,module", [("train-seq2k", train_runner),
                                         ("serve-chat-sat", serve_runner)])
def test_the_int8_control_is_not_correct(name, module, out_dir):
    run = _run(name, out_dir)
    assert run["verdict"].correct
    control = module.check(*run["check_args"], precision="int8")
    assert not control.correct
    assert any(not row["ok"] for row in control.rows)


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(
        out_dir, monkeypatch):
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    assert not _run("train-seq2k", out_dir)["verdict"].correct


def test_a_token_altered_where_it_is_produced_is_not_correct(
        out_dir, monkeypatch):
    from distkeras_tpu.serving import engine

    real = engine.sample_tokens

    def off_by_one(logits, *a, **kw):
        return (real(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_tokens", off_by_one)
    for fn in (engine._tick_fn, engine._mixed_tick_fn):
        fn.cache_clear()
    try:
        assert not _run("serve-chat-sat", out_dir)["verdict"].correct
    finally:
        for fn in (engine._tick_fn, engine._mixed_tick_fn):
            fn.cache_clear()
