"""PR 36's files: the configuration ``solar-open2-250b-serve`` with its
reference, the mix ``reason-sat``, the cell ``serve-reason-sat``, its
twelve metric files, the counts of its two kinds of layer and the
derived readers load through ``spec.cell`` with nothing edited, and
``BENCHMARK.json`` is ``spec.benchmark_json()`` of the files with every
accepted entry where it was."""

import copy
import json
import os

import numpy as np

from chipbench.harness import delta_rule_metrics, readers, spec, traffic
from chipbench.kernels import solar_open2

REPO = os.path.dirname(spec.ROOT)
CELL = "serve-reason-sat"
CONFIG = "solar-open2-250b-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ACCEPTED_CELLS = ["train-seq2k", "serve-chat-sat", "serve-chat-knee60",
                  "serve-longdoc-sat", "serve-prefill-sat",
                  "serve-mixedlen-sat"]
REDUCED = {"num_hidden_layers": 8, "gqa_layers": [0, 4],
           "n_routed_experts": 20, "vocab_size": 24576}


def _catalog_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Solar-Open2-250B":
                return row
    raise AssertionError("Solar-Open2-250B is not in the catalog")


def test_the_new_cell_loads_and_cross_references():
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["order"]) \
        == (CONFIG, "reason-sat", 1, 7)
    assert cell["end_to_end"] == ["serve_tok_s", "setup_s"]
    assert len(cell["per_layer_specs"]) == 12
    for m in cell["per_layer_specs"]:
        assert m["since"] == 36 and m["cells"] == [CELL]
        assert m["name"].endswith(".reason")
        assert m["reader"] in readers.READERS
        assert m["moves"] == "serve_tok_s"
        if m["reader"] in ("derived", "trace_kernel"):
            spec.named(m.get("function") or m["counts"])  # resolves
    assert {m["layer"] for m in cell["per_layer_specs"]
            if m["name"].startswith("delta_")} == {"linear attention"}
    # the limits' keys are serve-chat-sat's, each with its reason
    assert set(cell["limits"]) == set(spec.cell("serve-chat-sat")["limits"])
    assert "todo" not in cell["limits_why"]


def test_the_mix_is_the_issues():
    mix = spec.load("traffic", "reason-sat")
    assert {k: mix[k] for k in (
        "kind", "clients", "size_pool", "prompt_len", "output_len",
        "max_total", "ramp_s", "trace_after_s", "trace_s")} == {
        "kind": "closed_loop", "clients": 128, "size_pool": 128,
        "prompt_len": {"median": 768, "sigma": 0.8, "min": 128,
                       "max": 3072},
        "output_len": {"median": 768, "sigma": 0.5, "min": 192,
                       "max": 3072},
        "max_total": 6144, "ramp_s": 25, "trace_after_s": 2, "trace_s": 5}
    engine = spec.load("configs", CONFIG)["engine"]
    assert engine == {"slots": 64, "max_len": 6144}
    assert mix["clients"] == 2 * engine["slots"]
    sizes = traffic.request_sizes(mix, mix["size_pool"], 2 ** 31 + 7)
    prompts = np.array([p for p, _ in sizes])
    outputs = np.array([o for _, o in sizes])
    assert max(p + o for p, o in sizes) <= engine["max_len"]
    # decode-led: the answer about as long as the prompt
    assert 1000 < prompts.mean() < 1100 and 840 < outputs.mean() < 900
    assert prompts.min() >= 128 and outputs.min() >= 192


def test_benchmark_json_is_the_files_with_the_accepted_entries_in_place():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    assert [w["name"] for w in committed["workloads"]] == ACCEPTED_CELLS + [
        CELL]
    assert [c["name"] for c in committed["configs"]][-2:] == [
        "mimo-v2.5-serve", CONFIG]
    names = [m["name"] for m in committed["per_layer"]]
    assert len(names) == 64
    assert not any(n.endswith(".reason") for n in names[:52])
    assert all(n.endswith(".reason") for n in names[52:])
    for m in committed["per_layer"][:52]:
        assert CELL not in m["workloads"]
    for m in committed["per_layer"][52:]:
        assert m["workloads"] == [CELL]
    assert all(w["chips"] == 1 for w in committed["workloads"])
    by_name = {m["name"]: m for m in committed["end_to_end"]}
    assert by_name["serve_tok_s"]["workloads"][-1] == CELL
    assert "workloads" not in by_name["setup_s"]


def test_the_configuration_is_the_catalogs_row_with_each_cut_listed():
    cfg = spec.load("configs", CONFIG)
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] != value
            assert cfg["published"][key] == value
        else:  # every other number and group as published
            assert cfg[key] == value, key
    m = cfg["model"]
    assert (m["d_model"], m["num_heads"], m["head_dim"], m["num_kv_heads"],
            m["kda_num_heads"], m["kda_head_dim"],
            m["short_conv_kernel_size"], m["moe_intermediate_size"],
            m["num_experts_per_tok"], m["n_routed_experts"],
            m["n_shared_experts"]) == (
        4096, 64, 128, 8, 64, 128, 4, 1280, 8, 320, 1)
    assert (m["num_layers"], m["experts_held"], m["vocab_size"],
            m["gqa_layers"]) == (
        cfg["num_hidden_layers"], cfg["n_routed_experts"],
        cfg["vocab_size"], cfg["gqa_layers"])
    # two whole periods of the published pattern, as it begins
    assert cfg["gqa_layers"] == [i for i in cfg["published"]["gqa_layers"]
                                 if i < cfg["num_hidden_layers"]]
    # the floors: a whole period and four layers more, at least 8
    # experts, at least an eighth of the vocabulary
    assert m["num_layers"] >= 2 * (cfg["gqa_interval"] + 1)
    assert m["experts_held"] >= 8
    assert m["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] * m[
        "experts_held"] == m["n_routed_experts"]
    assert cfg["precision"]["parameters"] == "bfloat16"
    assert cfg["precision"]["recurrent_state"] == "float32"
    reference = spec.reference(cfg)
    assert (cfg["precision"]["control"],
            cfg["precision"]["second_control"]) == ("int8", "no_decay")
    assert {"int8", "no_decay"} <= set(reference.PRECISIONS)
    assert spec.model_name(cfg) == "solar_open2_lm"
    for key in ("published", "deployment", "departures", "assumed"):
        assert cfg[key]


def test_the_parameter_count_is_the_issues():
    """3 898.7 M within 0.1 %, from the reference's own shapes."""
    reference = spec.reference(spec.load("configs", CONFIG))
    count = 0

    def walk(node):
        nonlocal count
        for sub in node.values():
            if isinstance(sub, dict):
                walk(sub)
            else:
                count += int(np.prod(sub))

    walk(reference._shapes(reference.sizes(spec.load("configs", CONFIG))))
    assert abs(count / 3898.7e6 - 1) < 1e-3


def test_the_counts_are_what_the_rows_need():
    cell = spec.cell(CELL)
    model = cell["config_spec"]["model"]
    # a row's state a KDA layer: 64 heads of 128 x 128 float32
    assert solar_open2.state_bytes(model) == 64 * 64 * 1024
    assert solar_open2._kinds(model) == (2, 6)
    # 61 rows stepped and 3 chunks of 64 positions, in 6 layers
    assert solar_open2.state_rows(
        {"chunk": 64, "state_rows_stepped": 6 * 61,
         "chunk_positions_computed": 6 * 3 * 64}) == 6 * 64
    assert solar_open2.state_rows(
        {"chunk": 1, "state_rows_stepped": 6 * 64,
         "chunk_positions_computed": 0}) == 6 * 64
    # with no trace there is nothing to read, and nothing raises
    run = {"trace_dir": None, "engine_stats": {}, "flight": {}}
    assert solar_open2.full_least_seconds(cell, run, None, {}) is None
    assert solar_open2.state_least_seconds(cell, run, {}) is None
    assert delta_rule_metrics.delta_rule_device_pct(cell, run, {}) is None
    assert delta_rule_metrics.delta_state_roofline(cell, run, {}) is None
    assert delta_rule_metrics.delta_chunk_useful_pct(cell, run, {}) is None
    run["engine_stats"] = {"chunk_positions_live_total": 45,
                           "chunk_positions_computed_total": 60}
    assert delta_rule_metrics.delta_chunk_useful_pct(cell, run, {}) == 75.0


def test_rehearsal_at_a_tiny_size(tmp_path):
    """The cell's own files with the sizes replaced in memory (every
    ratio of the configuration kept: two periods of GQA, KDA, KDA, KDA;
    8 query heads over 2 KV heads; a shared expert), on the CPU through
    ``LMServer``."""
    import jax

    from chipbench import run as entry

    cell = copy.deepcopy(spec.cell(CELL))
    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    cfg["model"].update(
        vocab_size=211, d_model=64, num_heads=8, head_dim=16,
        num_kv_heads=2, kda_num_heads=4, kda_head_dim=16, kda_gate_rank=8,
        moe_intermediate_size=32, n_routed_experts=16,
        num_experts_per_tok=4, experts_held=8, max_len=128, expert_tile=8)
    cfg["compute_dtype"] = cfg["precision"]["parameters"] = "float32"
    cfg["engine"] = {"slots": 3, "max_len": 128, "prefill_chunk": 8,
                     "scheduler": {"tick_token_budget": 12}}
    for key, median in (("prompt_len", 30), ("output_len", 30)):
        mix[key] = {"median": median, "sigma": 0.7, "min": 4, "max": 60}
    mix.update(max_total=120, ramp_s=0.3, trace_after_s=0.1, trace_s=0.5,
               clients=6, size_pool=16)
    cell["limits"] = {"served_logit_gap_max": 1e-3, "far_off_gap": 1e-3,
                      "near_tie_margin": 0.05, "near_ties_wanted": 20,
                      "served_far_off_per_near_tie": 0.01,
                      "sample_requests": 4, "sample_requests_max": 8}
    result = entry.execute(cell, 2 ** 31 + 11, 1.5, False, jax.devices()[:1],
                           str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
