"""PR 28's files: the configuration ``deepseek-v3.2-exp-serve`` with its
reference, the mixes ``longdoc-sat`` and ``prefill-sat``, the cells
``serve-longdoc-sat`` and ``serve-prefill-sat`` and their metric files
load through ``spec.cell`` with nothing edited, and ``BENCHMARK.json`` is
``spec.benchmark_json()`` of the files."""

import json
import os

import numpy as np
import pytest

from chipbench.harness import readers, spec, traffic

REPO = os.path.dirname(spec.ROOT)
NEW_CELLS = {"serve-longdoc-sat": ("deepseek-v3.2-exp-serve", "longdoc-sat"),
             "serve-prefill-sat": ("cerebras-gpt-1.3b-serve", "prefill-sat")}
PUBLISHED = {  # huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp config.json
    "hidden_size": 7168, "num_attention_heads": 128, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "index_n_heads": 64, "index_head_dim": 128,
    "index_topk": 2048, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_experts_per_tok": 8, "n_group": 8,
    "topk_group": 4, "n_shared_experts": 1, "routed_scaling_factor": 2.5}


@pytest.mark.parametrize("name", sorted(NEW_CELLS))
def test_the_new_cells_load_and_cross_reference(name):
    cell = spec.cell(name)
    config, mix = NEW_CELLS[name]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (config, mix, 1)
    assert cell["end_to_end"] == ["serve_tok_s", "setup_s"]
    assert cell["traffic_spec"]["kind"] == "closed_loop"
    assert cell["traffic_spec"]["clients"] == 2 * cell["config_spec"][
        "engine"]["slots"]
    for m in cell["per_layer_specs"]:
        assert m["since"] == 28 and m["cells"] == [name]
        assert m["reader"] in readers.READERS
        assert m["moves"] == "serve_tok_s"
        if m["reader"] in ("derived", "trace_kernel"):
            spec.named(m.get("function") or m["counts"])  # resolves
    # the limits' keys are serve-chat-sat's
    assert set(cell["limits"]) == set(spec.cell("serve-chat-sat")["limits"])
    # every request of the mix fits the engine's context
    sizes = traffic.request_sizes(cell["traffic_spec"], 256, 2 ** 31 + 7)
    assert max(p + o for p, o in sizes) <= cell["config_spec"]["engine"][
        "max_len"]


def test_benchmark_json_is_the_files_with_the_accepted_entries_in_place():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    assert [w["name"] for w in committed["workloads"]] == [
        "train-seq2k", "serve-chat-sat", "serve-chat-knee60",
        "serve-longdoc-sat", "serve-prefill-sat"]
    assert len(committed["configs"]) == 3
    names = [m["name"] for m in committed["per_layer"]]
    assert len(names) == 35 and all(
        n.endswith((".longdoc", ".prefill")) for n in names[22:])
    assert not any(n.endswith((".longdoc", ".prefill")) for n in names[:22])
    assert all(w["chips"] == 1 for w in committed["workloads"])


def test_the_configuration_keeps_every_width_and_lists_each_cut():
    cfg = spec.load("configs", "deepseek-v3.2-exp-serve")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value and key not in cfg["reduced"]
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size", "num_nextn_predict_layers"])
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key]
    m = cfg["model"]
    assert (m["d_model"], m["num_heads"], m["num_layers"], m["first_k_dense"],
            m["experts_held"], m["n_routed_experts"], m["vocab_size"]) == (
        7168, 128, cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
        cfg["n_routed_experts"], 256, cfg["vocab_size"])
    for ours, theirs in (("num_experts_per_tok",) * 2, ("index_topk",) * 2,
                         ("q_lora_rank",) * 2, ("kv_lora_rank",) * 2,
                         ("moe_intermediate_size",) * 2,
                         ("intermediate_size",) * 2, ("n_group",) * 2,
                         ("topk_group",) * 2):
        assert m[ours] == cfg[theirs]
    # the floors: four expert layers after the dense one, at least 8
    # experts, at least an eighth of the vocabulary
    assert m["num_layers"] - m["first_k_dense"] >= 4
    assert m["experts_held"] >= 8
    assert m["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] * m[
        "experts_held"] == m["n_routed_experts"]
    assert cfg["precision"]["parameters"] == "bfloat16"
    reference = spec.reference(cfg)
    assert {cfg["precision"]["control"], cfg["precision"][
        "second_control"]} <= set(reference.PRECISIONS)
    assert spec.model_name(cfg) == "deepseek_v32_lm"


def test_most_longdoc_prompts_are_longer_than_the_selection():
    mix = spec.load("traffic", "longdoc-sat")
    prompts = np.array([p for p, _ in traffic.request_sizes(mix, 256, 5)])
    assert (prompts > 2048).mean() > 0.9
    assert prompts.min() >= 1024 and prompts.max() <= 11776
