"""PR 34's files: the configuration ``mimo-v2.5-serve`` with its
reference, the mix ``mixedlen-sat``, the cell ``serve-mixedlen-sat``, its
ten metric files, the full attend's counts and the derived readers load
through ``spec.cell`` with nothing edited, and ``BENCHMARK.json`` is
``spec.benchmark_json()`` of the files with every accepted entry where
it was."""

import copy
import json
import os

import numpy as np
import pytest

from chipbench.harness import hybrid_attend_metrics, readers, spec, traffic
from chipbench.kernels import hybrid_attend

REPO = os.path.dirname(spec.ROOT)
CELL = "serve-mixedlen-sat"
PUBLISHED = {  # huggingface.co/XiaomiMiMo/MiMo-V2.5 config.json
    "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 192,
    "v_head_dim": 128, "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192, "swa_v_head_dim": 128,
    "sliding_window": 128, "sliding_window_size": 128,
    "attention_chunk_size": 128, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "attention_value_scale": 0.707, "intermediate_size": 16384,
    "moe_intermediate_size": 2048, "num_experts_per_tok": 8, "n_group": 1,
    "topk_group": 1, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576, "n_shared_experts": None,
    "routed_scaling_factor": None, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False}
ACCEPTED_CELLS = ["train-seq2k", "serve-chat-sat", "serve-chat-knee60",
                  "serve-longdoc-sat", "serve-prefill-sat"]


def test_the_new_cell_loads_and_cross_references():
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["order"]) \
        == ("mimo-v2.5-serve", "mixedlen-sat", 1, 6)
    assert cell["end_to_end"] == ["serve_tok_s", "setup_s"]
    assert len(cell["per_layer_specs"]) == 10
    for m in cell["per_layer_specs"]:
        assert m["since"] == 34 and m["cells"] == [CELL]
        assert m["name"].endswith(".mixedlen")
        assert m["reader"] in readers.READERS
        assert m["moves"] == "serve_tok_s"
        if m["reader"] in ("derived", "trace_kernel"):
            spec.named(m.get("function") or m["counts"])  # resolves
    # the limits' keys are serve-chat-sat's
    assert set(cell["limits"]) == set(spec.cell("serve-chat-sat")["limits"])


def test_the_mix_is_the_issues():
    mix = spec.load("traffic", "mixedlen-sat")
    assert {k: mix[k] for k in (
        "kind", "clients", "size_pool", "prompt_len", "output_len",
        "max_total", "ramp_s", "trace_after_s", "trace_s")} == {
        "kind": "closed_loop", "clients": 64, "size_pool": 128,
        "prompt_len": {"median": 4096, "sigma": 1.0, "min": 256,
                       "max": 20480},
        "output_len": {"median": 256, "sigma": 0.5, "min": 64, "max": 1024},
        "max_total": 24576, "ramp_s": 20, "trace_after_s": 2, "trace_s": 5}
    engine = spec.load("configs", "mimo-v2.5-serve")["engine"]
    assert mix["clients"] == 2 * engine["slots"]
    sizes = traffic.request_sizes(mix, mix["size_pool"], 2 ** 31 + 7)
    prompts = np.array([p for p, _ in sizes])
    outputs = np.array([o for _, o in sizes])
    assert max(p + o for p, o in sizes) <= engine["max_len"]
    # short and long in one queue: the pool's own statistics
    assert 5900 < prompts.mean() < 6150 and 280 < outputs.mean() < 300
    assert 0.08 <= (prompts <= 1024).mean() <= 0.10
    assert 0.23 <= (prompts >= 8192).mean() <= 0.25
    assert prompts.min() >= 256 and prompts.max() == 20480


def test_benchmark_json_is_the_files_with_the_accepted_entries_in_place():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    assert [w["name"] for w in committed["workloads"]] == ACCEPTED_CELLS + [
        CELL]
    assert [c["name"] for c in committed["configs"]] == [
        "cerebras-gpt-1.3b-train", "cerebras-gpt-1.3b-serve",
        "deepseek-v3.2-exp-serve", "mimo-v2.5-serve"]
    names = [m["name"] for m in committed["per_layer"]]
    assert len(names) == 48
    assert not any(n.endswith(".mixedlen") for n in names[:38])
    assert all(n.endswith(".mixedlen") for n in names[38:])
    for m in committed["per_layer"][:38]:
        assert CELL not in m["workloads"]
    for m in committed["per_layer"][38:]:
        assert m["workloads"] == [CELL]
    assert all(w["chips"] == 1 for w in committed["workloads"])
    by_name = {m["name"]: m for m in committed["end_to_end"]}
    assert by_name["serve_tok_s"]["workloads"][-1] == CELL
    assert "workloads" not in by_name["setup_s"]


def test_the_configuration_keeps_every_width_and_lists_each_cut():
    cfg = spec.load("configs", "mimo-v2.5-serve")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
         "n_routed_experts", "vocab_size"])
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key]
    published = cfg["published"]
    assert published["num_hidden_layers"] == 48 == len(
        published["hybrid_layer_pattern"]) == len(published["moe_layer_freq"])
    # layer 0 and one whole period: the published layers 0 and 6-11
    keep = [0] + list(range(6, 12))
    assert cfg["hybrid_layer_pattern"] == [
        published["hybrid_layer_pattern"][i] for i in keep] == [
        0, 1, 1, 1, 1, 1, 0]
    assert cfg["moe_layer_freq"] == [published["moe_layer_freq"][i]
                                     for i in keep]
    m = cfg["model"]
    assert (m["d_model"], m["num_heads"], m["head_dim"], m["v_head_dim"],
            m["num_kv_heads"], m["swa_num_kv_heads"], m["sliding_window"],
            m["intermediate_size"], m["moe_intermediate_size"],
            m["num_experts_per_tok"], m["n_routed_experts"]) == (
        4096, 64, 192, 128, 4, 8, 128, 16384, 2048, 8, 256)
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"])
    assert m["hybrid_layer_pattern"] == cfg["hybrid_layer_pattern"]
    assert m["moe_layer_freq"] == cfg["moe_layer_freq"]
    assert m["window_ring"] >= m["sliding_window"] + 64 - 1
    # the floors: a whole period beside the dense layer, at least 8
    # experts, at least an eighth of the vocabulary
    assert m["experts_held"] >= 8
    assert m["vocab_size"] * 8 >= published["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] * m[
        "experts_held"] == m["n_routed_experts"]
    assert cfg["precision"]["parameters"] == "bfloat16"
    reference = spec.reference(cfg)
    assert {cfg["precision"]["control"], cfg["precision"][
        "second_control"]} <= set(reference.PRECISIONS)
    assert cfg["precision"]["second_control"] == "no_window"
    assert spec.model_name(cfg) == "mimo_v2_lm"
    for key in ("published", "deployment", "departures", "assumed"):
        assert cfg[key]


def test_the_full_attends_counts_are_what_the_rows_need():
    model = spec.load("configs", "mimo-v2.5-serve")["model"]
    # one decoding row at position 999: 1000 pairs, 1000 positions
    flops, nbytes = hybrid_attend.tick(1000, 1000, 1, model)
    assert flops == 2 * 64 * (192 + 128) * 1000
    assert nbytes == (1000 * 4 + 1 * 64) * (192 + 128) * 2
    # with no trace there is nothing to read, and nothing raises
    run = {"trace_dir": None, "engine_stats": {}, "flight": {}}
    cell = spec.cell(CELL)
    assert hybrid_attend.full_least_seconds(cell, run, None, {}) is None
    assert hybrid_attend_metrics.window_attend_device_pct(cell, run, {}) \
        is None
    assert hybrid_attend_metrics.attend_device_pct(cell, run, {}) is None


def test_rehearsal_at_a_tiny_size(tmp_path):
    """The cell's own files with the sizes replaced in memory (every
    ratio of the configuration kept: KV heads 2 and 4, keys wider than
    values, a ring twice the window), on the CPU through ``LMServer``."""
    import jax

    from chipbench import run as entry

    cell = copy.deepcopy(spec.cell(CELL))
    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    cfg["model"].update(
        vocab_size=211, d_model=64, num_heads=8, head_dim=24, v_head_dim=16,
        num_kv_heads=2, swa_num_kv_heads=4, sliding_window=8, window_ring=16,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=8, num_layers=4,
        hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
        max_len=128, expert_tile=8)
    cfg["compute_dtype"] = cfg["precision"]["parameters"] = "float32"
    cfg["engine"] = {"slots": 3, "max_len": 128, "prefill_chunk": 8,
                     "scheduler": {"tick_token_budget": 12,
                                   "size_classes": 2}}
    for key, median in (("prompt_len", 30), ("output_len", 24)):
        mix[key] = {"median": median, "sigma": 0.7, "min": 4, "max": 90}
    mix.update(max_total=120, ramp_s=0.3, trace_after_s=0.1, trace_s=0.5,
               clients=4, size_pool=16)
    cell["limits"] = {"served_logit_gap_max": 1e-3, "far_off_gap": 1e-3,
                      "near_tie_margin": 0.05, "near_ties_wanted": 20,
                      "served_far_off_per_near_tie": 0.01,
                      "sample_requests": 4, "sample_requests_max": 8}
    result = entry.execute(cell, 2 ** 31 + 11, 1.5, False, jax.devices()[:1],
                           str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
