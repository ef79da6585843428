"""PR 44's files: the configuration ``trinity-mini-train`` with its
reference (``row_losses``, ``train_losses`` with the bias rule), the mix
``seq8k``, the cell ``train-moe-seq8k``, its eleven metric files, the
operation counts and the derived readers load through ``spec.cell`` with
nothing edited, and ``BENCHMARK.json`` is ``spec.benchmark_json()`` of
the files with every accepted entry where it was."""

import copy
import json
import os

import jax
import numpy as np
import pytest

from chipbench.harness import afmoe_metrics, readers, span_reduce, spec
from chipbench.kernels import afmoe

REPO = os.path.dirname(spec.ROOT)
CELL = "train-moe-seq8k"
CONFIG = "trinity-mini-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ACCEPTED_CELLS = ["train-seq2k", "serve-chat-sat", "serve-chat-knee60",
                  "serve-longdoc-sat", "serve-prefill-sat",
                  "serve-mixedlen-sat", "serve-reason-sat",
                  "serve-reason-mtp-sat"]
METRICS = ["mfu_pct", "banded_attention_roofline",
           "banded_attention_fwd_roofline", "banded_attention_bwd_roofline",
           "attend_device_pct", "moe_experts_device_pct",
           "moe_experts_roofline", "held_load_max_over_mean",
           "routed_here_over_even"]
# accepted metrics of the same reader and scope: the cell lists them,
# and their files stay as they are
SHARED = ["device_idle_pct.train", "fused_ce_device_pct.train",
          "optimizer_device_pct.train"]
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def _catalog_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Trinity-Mini":
                return row
    raise AssertionError("Trinity-Mini is not in the catalog")


def test_the_new_cell_loads_and_cross_references():
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["order"]) \
        == (CONFIG, "seq8k", 1, 8)
    assert cell["end_to_end"] == ["train_tok_s", "setup_s"]
    assert sorted(cell["per_layer"]) == sorted(
        [n + ".train8k" for n in METRICS] + SHARED)
    assert len(cell["why"]) <= 200 and "seeded" in cell["why"]
    assert cell["config_spec"]["trainer"]["batch_size"] == 2
    for m in cell["per_layer_specs"]:
        if m["name"] in SHARED:
            assert CELL not in m.get("cells", ())
            continue
        assert m["since"] == 44 and m["cells"] == [CELL]
        assert m["reader"] in readers.READERS
        assert m["moves"] == "train_tok_s"
        if m["reader"] == "derived":
            spec.named(m["function"])  # resolves
        if m["name"].endswith("roofline.train8k") or "mfu" in m["name"]:
            assert (m["unit"], m["better"]) == ("%", "higher")
    assert set(cell["limits"]) == {"loss_rel_gap", "last_loss_over_first"}
    mix = cell["traffic_spec"]
    assert (mix["kind"], mix["seq_len"], mix["steps_per_epoch"],
            mix["unigram_skew"], mix["trace_skip_epochs"],
            mix["trace_epochs"]) == ("train_job", 8192, 8, 1.2, 1, 2)
    assert mix["nominal_tokens_per_s"] % 1000 == 0


def test_benchmark_json_is_the_files_with_the_accepted_entries_in_place():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    assert [w["name"] for w in committed["workloads"]] == ACCEPTED_CELLS + [
        CELL]
    assert [c["name"] for c in committed["configs"]][-2:] == [
        "glm-4.7-flash-serve", CONFIG]
    assert committed["configs"][-1]["reduced"] == REDUCED
    names = [m["name"] for m in committed["per_layer"]]
    assert len(names) == 97 + len(METRICS)
    assert not any(n.endswith(".train8k") for n in names[:97])
    assert sorted(names[97:]) == sorted(n + ".train8k" for n in METRICS)
    for m in committed["per_layer"][:97]:
        if m["name"] in SHARED:  # appended, nothing else changed
            assert m["workloads"] == ["train-seq2k", CELL]
        else:
            assert CELL not in m["workloads"]
    for m in committed["per_layer"][97:]:
        assert m["workloads"] == [CELL]
    assert all(w["chips"] == 1 for w in committed["workloads"])
    by_name = {m["name"]: m for m in committed["end_to_end"]}
    assert by_name["train_tok_s"]["workloads"] == ["train-seq2k", CELL]
    assert "workloads" not in by_name["setup_s"]
    assert committed["run_seconds"] == 50


def test_the_configuration_is_the_catalogs_row_with_its_cuts():
    cfg = spec.load("configs", CONFIG)
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg[key] != value, key
        else:  # every other number and group as published
            assert cfg[key] == value, key
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["num_dense_layers"] == 2
    assert cfg["published"]["num_experts"] == 128
    assert cfg["published"]["vocab_size"] == 200192
    m = cfg["model"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["sliding_window"], m["intermediate_size"],
            m["moe_intermediate_size"], m["n_routed_experts"],
            m["num_experts_per_tok"], m["route_scale"],
            m["n_shared_experts"], m["rope_theta"], m["rms_eps"],
            m["load_balance_coeff"]) == (
        2048, 32, 4, 128, 2048, 6144, 1024, 128, 8, 2.826, 1, 1e4, 1e-5,
        1e-3)
    assert (m["num_layers"], m["num_dense_layers"], m["experts_held"],
            m["vocab_size"], m["layer_types"]) == (
        cfg["num_hidden_layers"], cfg["num_dense_layers"],
        cfg["num_experts"], cfg["vocab_size"], cfg["layer_types"])
    # the floors: a whole period, four layers after the dense one, 8
    # experts, an eighth of the vocabulary
    assert cfg["layer_types"][1:] == row["config"]["layer_types"][:4]
    assert m["num_layers"] - m["num_dense_layers"] >= 4
    assert m["experts_held"] >= 8
    assert m["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert cfg["precision"]["parameters"] == "float32"
    reference = spec.reference(cfg)
    assert (cfg["precision"]["control"],
            cfg["precision"]["second_control"]) == ("int8", "no_window")
    assert {"int8", "no_window"} <= set(reference.PRECISIONS)
    assert callable(reference.row_losses) and callable(
        reference.train_losses)
    assert spec.model_name(cfg) == "afmoe_lm"
    assert cfg["trainer"]["axes"] == {"dp": 1}
    assert cfg["trainer"]["remat"] == "block"
    for key in ("published", "deployment", "departures", "assumed"):
        assert cfg[key]


def test_the_parameter_count_is_the_issues():
    cfg = spec.load("configs", CONFIG)
    reference = spec.reference(cfg)
    shapes = jax.eval_shape(lambda: reference.make_params(cfg, 1))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # ISSUE 44: 504.2 M (the norms and the router's bias beside it)
    assert abs(count - 504.2e6) < 0.1e6
    from distkeras_tpu.models import get_model

    init = jax.eval_shape(
        get_model(spec.model_name(cfg), **cfg["model"]).init,
        jax.random.PRNGKey(0), jax.numpy.zeros((1, 128), "int32"))
    assert jax.tree.map(lambda a: a.shape, init["params"]) == jax.tree.map(
        lambda a: a.shape, shapes["params"])


def test_the_counts_are_what_the_tokens_need():
    model = spec.load("configs", CONFIG)["model"]
    T, W = 8192, 2048
    # the band: the first W queries see the wedge, the rest W keys each
    assert afmoe.live_pairs(T, W) == W * (W + 1) // 2 + (T - W) * W
    assert afmoe.live_pairs(T, None) == T * (T + 1) // 2
    assert afmoe.live_pairs(2048, 2048) == afmoe.live_pairs(2048, None)
    assert round(afmoe.live_pairs(T, W) / T) == 1792
    brute = sum(min(t + 1, 5) for t in range(12))
    assert afmoe.live_pairs(12, 5) == brute
    parts = afmoe.forward_flops_per_token(model, T)
    # ISSUE 44's arithmetic: projections 54.5 M a layer, the dense
    # SwiGLU 75.5, the head 102.5, half a routed pair a token a layer
    assert round(parts["attn_project"] / 5 / 1e6, 1) == 54.5
    assert round(parts["dense_ffn"] / 1e6, 1) == 75.5
    assert round(parts["head"] / 1e6, 1) == 102.5
    assert parts["moe_experts"] == 4 * 0.5 * 3 * 2 * 2048 * 1024
    assert round(afmoe.train_flops_per_token(model, T) / 1e9, 2) == 2.14
    share = parts["attend"] / sum(parts.values())
    assert 0.25 < share < 0.27
    # grouped heads: K and V are read once a KV head
    f, b = afmoe.attention_forward(1, T, 32, 4, 128, W)
    assert f == 4 * 32 * 128 * afmoe.live_pairs(T, W)
    assert b == 2 * T * 128 * (32 + 4) * 2
    f_bwd, _ = afmoe.attention_backward(1, T, 32, 4, 128, W)
    assert f_bwd == 2.5 * f
    flops, nbytes = afmoe.experts_step(model, 16384)
    assert flops == 9 * 2 * 2048 * 1024 * 16384
    assert nbytes == 3 * 4 * 8 * 3 * 2048 * 1024 * 2


def _profile():
    """A made-up profile of one step at batch 2: per layer two forward
    launches (the first and remat's), dq and dk/dv, and the experts'
    launches a batch row; 8 ms an attention launch, 1 ms each of the
    others."""
    devices, scopes, at = [], {}, 0

    def op(name, scope, ms=1.0):
        nonlocal at
        devices.append([name, at, int(ms * 1e6)])
        scopes[name.split(" ")[0]] = scope
        at += int(ms * 1e6) + 10

    fwd = "jit(w)/while/body/jvp(AfmoeLM)/layers_{i}/attn/{k}/pallas_call"
    again = ("jit(w)/while/body/transpose(jvp(AfmoeLM))/jvp(AfmoeLM)/"
             "checkpoint/rematted_computation/layers_{i}/attn/{k}/"
             "pallas_call")
    back = ("jit(w)/while/body/transpose(jvp(AfmoeLM))/jvp(AfmoeLM)/"
            "checkpoint/layers_{i}/attn/{k}/pallas_call")
    n = 0
    for i in range(5):
        k = "full_attend" if i == 4 else "window_attend"
        for scope in (fwd, again, back, back):
            n += 1
            op(f"{k}.{n} (tpu_custom_call)", scope.format(i=i, k=k), 8.0)
        if i:
            for launch in 2 * ("moe_gate_up", "moe_down", "moe_bwd_hidden",
                               "moe_bwd_weights"):
                n += 1
                op(f"{launch}.{n} (tpu_custom_call)",
                   f"jit(w)/layers_{i}/moe.held/moe_experts/{launch}")
    op("fusion.1", "jit(w)/optimizer_update/mul", 4.0)
    return {"spans": [], "devices": {0: devices}, "scopes": scopes}


def test_the_readers_on_a_made_up_profile(monkeypatch, tmp_path):
    cell = spec.cell(CELL)
    trace_dir = str(tmp_path / f"trace.{CELL}")
    with open(tmp_path / f"{CELL}.metrics.jsonl", "w") as f:
        for step in range(1, 17):
            f.write(json.dumps({
                "t": step, "step": step, "loss": 10.0,
                "routed_here": 16000.0 if step > 8 else 1.0,
                "held_load_max_over_mean": 1.2 if step > 8 else 9.0,
                "routed_here_over_even": 0.9 if step > 8 else 7.0,
            }) + "\n")
    run = {"trace_dir": trace_dir, "train_tok_s": 30000.0,
           "device": {"count": 1, "kind": "TPU v5 lite"}}
    monkeypatch.setattr(span_reduce, "profile_of", lambda run: _profile())
    assert afmoe_metrics.mfu_pct(cell, run, PEAKS) == pytest.approx(
        100 * 30000 * afmoe.train_flops_per_token(
            cell["config_spec"]["model"], 8192) / 197e12)
    # the window's rows only (the set-up epoch's are left out)
    assert afmoe_metrics.held_load_max_over_mean(
        cell, run, PEAKS) == pytest.approx(1.2)
    assert afmoe_metrics.routed_here_over_even(
        cell, run, PEAKS) == pytest.approx(0.9)
    shape = (2, 8192, 32, 4, 128)
    least = {(k, back): afmoe.roofline_seconds(*fn(*shape, w), PEAKS)
             for k, w in (("window", 2048), ("full", None))
             for back, fn in ((False, afmoe.attention_forward),
                              (True, afmoe.attention_backward))}
    fwd = 2 * (4 * least["window", False] + least["full", False])
    bwd = 4 * least["window", True] + least["full", True]
    assert afmoe_metrics.banded_attention_fwd_roofline(
        cell, run, PEAKS) == pytest.approx(100 * fwd / 80e-3)
    assert afmoe_metrics.banded_attention_bwd_roofline(
        cell, run, PEAKS) == pytest.approx(100 * bwd / 80e-3)
    assert afmoe_metrics.banded_attention_roofline(
        cell, run, PEAKS) == pytest.approx(100 * (fwd + bwd) / 160e-3)
    busy = 160e-3 + 32e-3 + 4e-3
    assert afmoe_metrics.attend_device_pct(
        cell, run, PEAKS) == pytest.approx(100 * 160e-3 / busy, rel=1e-3)
    # one step's launches: four expert layers, two rows
    want = afmoe.roofline_seconds(*afmoe.experts_step(
        cell["config_spec"]["model"], 16000.0), PEAKS)
    assert afmoe_metrics.moe_experts_roofline(
        cell, run, PEAKS) == pytest.approx(100 * want / 32e-3)
    for name in METRICS:  # every share stays a share
        m = spec.load("layer_metrics", name + ".train8k")
        if m["reader"] == "derived" and m["unit"] == "%":
            value = spec.named(m["function"])(cell, run, PEAKS)
            # (this profile has no fused_ce scope: nothing to read)
            assert value is None or 0 < value <= 100, name


def test_the_readers_find_nothing_in_a_run_that_has_none(monkeypatch):
    """The parent's program has no such scope, launch or row, and an
    untraced run no profile: every reader returns None and raises
    nothing."""
    cell = spec.cell(CELL)
    run = {"trace_dir": None, "device": {"count": 1}}
    empty = {"spans": [], "devices": {0: [["fusion.1", 0, 1000]]},
             "scopes": {"fusion.1": "jit(w)/mul"}}
    for profile in (None, empty):
        monkeypatch.setattr(span_reduce, "profile_of", lambda run: profile)
        for fn in (afmoe_metrics.mfu_pct, afmoe_metrics.attend_device_pct,
                   afmoe_metrics.banded_attention_roofline,
                   afmoe_metrics.banded_attention_fwd_roofline,
                   afmoe_metrics.banded_attention_bwd_roofline,
                   afmoe_metrics.held_load_max_over_mean,
                   afmoe_metrics.routed_here_over_even,
                   afmoe_metrics.moe_experts_roofline):
            assert fn(cell, run, PEAKS) is None


def test_rehearsal_at_a_tiny_size(tmp_path):
    """The cell's own files with the sizes replaced in memory (every
    ratio of the configuration kept: a dense layer and a period of
    three window layers and a full one, 4 of 16 experts held, a shared
    expert, a window shorter than the sequence), on the CPU through
    ``LMTrainer``; then both controls against the rehearsal's limits."""
    from chipbench import run as entry
    from chipbench.harness import train_runner

    cell = copy.deepcopy(spec.cell(CELL))
    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    cfg["model"].update(
        vocab_size=61, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        sliding_window=12, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=2, experts_held=4,
        expert_tile=8)
    cfg["compute_dtype"] = "float32"
    cfg["trainer"]["batch_size"] = 2
    cfg["trainer"]["schedule"] = {"init": 1e-3, "peak": 1e-2,
                                  "warmup_steps": 10}
    mix.update(seq_len=32, steps_per_epoch=3, nominal_tokens_per_s=400,
               trace_skip_epochs=0, trace_epochs=1)
    cell["limits"] = {"loss_rel_gap": 1e-4, "last_loss_over_first": 0.999}
    ctx = entry.make_ctx(jax.devices()[:1], str(tmp_path))
    run = train_runner.run(cell, 2 ** 31 + 13, 1.0, False, ctx)
    assert run["verdict"].correct and run["failed"] == 0
    with open(tmp_path / f"{CELL}.metrics.jsonl") as f:
        rows = [r for r in map(json.loads, f) if "step" in r]
    assert {"routed_here", "expert_load_max_over_mean",
            "held_load_max_over_mean", "routed_here_over_even",
            "router_bias_abs_max"} <= set(rows[0])
    for control in ("int8", "no_window"):
        verdict = train_runner.check(*run["check_args"], precision=control)
        assert not verdict.correct, control
