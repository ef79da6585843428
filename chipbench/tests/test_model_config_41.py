"""PR 41's files: the configuration ``glm-4.7-flash-serve`` with its
reference (``mtp_logits`` beside ``forward_logits``), the cell
``serve-reason-mtp-sat`` over the unedited mix ``reason-sat``, its
fifteen metric files, the operation counts and the derived readers load
through ``spec.cell`` with nothing edited, and ``BENCHMARK.json`` is
``spec.benchmark_json()`` of the files with every accepted entry where
it was."""

import copy
import json
import os

import numpy as np

from chipbench.harness import mtp_metrics, readers, spec
from chipbench.kernels import glm4_moe_lite

REPO = os.path.dirname(spec.ROOT)
CELL = "serve-reason-mtp-sat"
CONFIG = "glm-4.7-flash-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ACCEPTED_CELLS = ["train-seq2k", "serve-chat-sat", "serve-chat-knee60",
                  "serve-longdoc-sat", "serve-prefill-sat",
                  "serve-mixedlen-sat", "serve-reason-sat"]
METRICS = ["device_idle_pct", "occupancy_pct", "weight_gb_per_tick",
           "tick_useful_pct", "loop_host_ms", "overrun_pct",
           "device_starved_pct", "device_spec_tick_ms",
           "device_mixed_tick_ms", "spec_accept_pct",
           "mtp_draft_device_pct", "mla_attend_device_pct",
           "moe_experts_device_pct", "expert_rows_useful_pct",
           "serve_mfu_pct"]


def _catalog_row():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "GLM-4.7-Flash":
                return row
    raise AssertionError("GLM-4.7-Flash is not in the catalog")


def test_the_new_cell_loads_and_cross_references():
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["order"]) \
        == (CONFIG, "reason-sat", 1, 8)
    assert cell["end_to_end"] == ["serve_tok_s", "setup_s"]
    assert cell["per_layer"] == [n + ".mtp" for n in METRICS]
    for m in cell["per_layer_specs"]:
        assert m["since"] == 41 and m["cells"] == [CELL]
        assert m["reader"] in readers.READERS
        assert m["moves"] == "serve_tok_s"
        if m["reader"] == "derived":
            spec.named(m["function"])  # resolves
    assert {m["layer"] for m in cell["per_layer_specs"]
            if m["name"].startswith(("spec_", "mtp_"))} == {"drafter"}
    # the cell says that seeded weights accept no draft
    assert "accept no draft" in cell["why"]
    assert "~0" in spec.load("layer_metrics", "spec_accept_pct.mtp")["what"]
    # the limits' keys are serve-chat-sat's, each with its reason
    assert set(cell["limits"]) == set(spec.cell("serve-chat-sat")["limits"])
    assert "todo" not in cell["limits_why"]
    # the mix is PR 36's file, which serve-reason-sat runs too
    assert cell["traffic_spec"] == spec.cell("serve-reason-sat")[
        "traffic_spec"]
    engine = cell["config_spec"]["engine"]
    assert engine == {"slots": 64, "max_len": 6144, "draft": "mtp",
                      "spec_k": 1}
    assert cell["traffic_spec"]["clients"] == 2 * engine["slots"]
    assert cell["traffic_spec"]["max_total"] == engine["max_len"]


def test_benchmark_json_is_the_files_with_the_accepted_entries_in_place():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    assert committed == spec.benchmark_json()
    assert [w["name"] for w in committed["workloads"]] == ACCEPTED_CELLS + [
        CELL]
    assert [c["name"] for c in committed["configs"]][-2:] == [
        "solar-open2-250b-serve", CONFIG]
    assert committed["configs"][-1]["reduced"] == ["num_hidden_layers"]
    names = [m["name"] for m in committed["per_layer"]]
    assert len(names) == 82 + len(METRICS)
    assert not any(n.endswith(".mtp") for n in names[:82])
    assert sorted(names[82:]) == sorted(n + ".mtp" for n in METRICS)
    for m in committed["per_layer"][:82]:
        assert CELL not in m["workloads"]
    for m in committed["per_layer"][82:]:
        assert m["workloads"] == [CELL]
    assert all(w["chips"] == 1 for w in committed["workloads"])
    by_name = {m["name"]: m for m in committed["end_to_end"]}
    assert by_name["serve_tok_s"]["workloads"][-1] == CELL
    assert "workloads" not in by_name["setup_s"]


def test_the_configuration_is_the_catalogs_row_with_the_depth_cut():
    cfg = spec.load("configs", CONFIG)
    row = _catalog_row()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert cfg[key] == 6 != value
            assert cfg["published"][key] == value == 47
        else:  # every other number and group as published
            assert cfg[key] == value, key
    m = cfg["model"]
    assert (m["d_model"], m["num_heads"], m["q_lora_rank"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["intermediate_size"],
            m["moe_intermediate_size"], m["n_routed_experts"],
            m["n_shared_experts"], m["num_experts_per_tok"],
            m["routed_scaling_factor"], m["vocab_size"], m["rope_theta"],
            m["rms_eps"]) == (
        2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 64, 1, 4, 1.8,
        154880, 1e6, 1e-5)
    assert (m["num_layers"], m["first_k_dense"]) == (
        cfg["num_hidden_layers"], cfg["first_k_dense_replace"])
    # every expert, the whole vocabulary and the module are held
    assert "experts_held" not in m
    assert cfg["num_nextn_predict_layers"] == 1
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    # the floors: the leading dense layer and at least four of the
    # layers that follow it
    assert m["num_layers"] - m["first_k_dense"] >= 4
    assert cfg["precision"]["parameters"] == "bfloat16"
    reference = spec.reference(cfg)
    assert (cfg["precision"]["control"],
            cfg["precision"]["second_control"]) == ("int8", "no_shared")
    assert {"int8", "no_shared"} <= set(reference.PRECISIONS)
    assert callable(reference.mtp_logits)
    assert spec.model_name(cfg) == "glm4_moe_lite_lm"
    for key in ("published", "deployment", "departures", "assumed"):
        assert cfg[key]


def test_the_parameter_count_and_the_cache_are_the_issues():
    """4 539.3 M parameters within 0.1 %, from the reference's own
    shapes (the module 643.69 M of them), and 8 064 bytes a position."""
    cfg = spec.load("configs", CONFIG)
    reference = spec.reference(cfg)

    def count(node):
        return sum(count(sub) if isinstance(sub, dict) else int(np.prod(sub))
                   for sub in node.values())

    shapes = reference._shapes(reference.sizes(cfg))
    assert abs(count(shapes) / 4539.3e6 - 1) < 1e-3
    assert abs(count(shapes["mtp"]) / 643.69e6 - 1) < 1e-3
    assert abs(count(shapes["layers_1"]) / 635.31e6 - 1) < 1e-3
    m = cfg["model"]
    latent_layers = m["num_layers"] + cfg["num_nextn_predict_layers"]
    assert latent_layers * (m["kv_lora_rank"] + m["qk_rope_head_dim"]
                            ) * 2 == 8064


def test_the_counts_are_what_the_tokens_need():
    cell = spec.cell(CELL)
    model = cell["config_spec"]["model"]
    # attention 21.76 M weights a layer, the dense layer 62.9 M, an
    # expert layer's shared expert and router 9.57 M
    assert glm4_moe_lite.fed_token(model) == 2 * (
        6 * 21_757_952 + 62_914_560 + 5 * (9_437_184 + 131_072))
    assert glm4_moe_lite.routed_pair(model) == 2 * 9_437_184
    assert glm4_moe_lite.emitted_token(model) == 2 * 2048 * 154880
    assert glm4_moe_lite.attended_key(model) == 2 * 20 * (576 + 512)
    # ticks that verified 64 windows of two and kept one draft, with a
    # prompt's last chunk of 64 beside them (it samples a first token:
    # 66 emitted for 129 positions kept of 192) and one row that had
    # ended: the refused drafts' share of what was attended is not work
    work = {"window_positions": 192, "draft_tokens": 64,
            "accepted_tokens": 1, "overrun_tokens": 1,
            "emitted_tokens": 66, "attended_tokens": 192_000}
    assert glm4_moe_lite.kept_positions(work) == 128
    per_token = glm4_moe_lite.fed_token(model) + 5 * 4 * 2 * 9_437_184
    assert glm4_moe_lite.useful_flops(model, work) == (
        128 * per_token + 66 * 2 * 2048 * 154880
        + 128_000 * 6 * 2 * 20 * 1088)
    # with no trace and no counter there is nothing to read, and nothing
    # raises: what the parent's program gives these readers
    run = {"trace_dir": None, "engine_stats": {}, "flight": {},
           "device": {"count": 1}}
    for name in ("spec_accept_pct", "mtp_draft_device_pct",
                 "tick_useful_pct", "serve_mfu_pct"):
        assert getattr(mtp_metrics, name)(cell, run, {}) is None
    # (another model's engine has the sums every engine has)
    run["engine_stats"] = {"attended_tokens_total": 5, "overrun_tokens": 0,
                           "query_positions_total": 9, "tokens_generated": 3,
                           "device_clock_span_ms": 75e3}
    for name in ("tick_useful_pct", "serve_mfu_pct"):
        assert getattr(mtp_metrics, name)(cell, run, {}) is None
    run["engine_stats"].update(draft_tokens_total=80, accepted_tokens_total=2)
    assert mtp_metrics.spec_accept_pct(cell, run, {}) == 2.5
    chunk = spec.load("layer_metrics", "device_mixed_tick_ms.mtp")
    assert readers.read(chunk, run, cell, None) is None
    run["engine_stats"]["device_spec_chunk_tick_ms"] = 38.5
    assert readers.read(chunk, run, cell, None) == 38.5
    # both shares read stats()' sums, not the flight ring (a traced
    # run's ring holds the drain behind the window)
    run["engine_stats"].update(
        {f"{k}_total": 2000 * v for k, v in work.items()
         if k not in ("overrun_tokens", "emitted_tokens")},
        overrun_tokens=2000, tokens_generated=2000 * 66,
        query_positions_total=2000 * 256)
    for name in ("tick_useful_pct", "serve_mfu_pct"):
        assert spec.named(spec.load("layer_metrics", name + ".mtp")[
            "function"]) is getattr(mtp_metrics, name)
    assert mtp_metrics.tick_useful_pct(cell, run, {}) == 50.0
    assert mtp_metrics.serve_mfu_pct(cell, run, {"flops_bf16": 197e12}) == \
        100.0 * 2000 * glm4_moe_lite.useful_flops(model, work) / (
            75.0 * 197e12)


def test_rehearsal_at_a_tiny_size(tmp_path):
    """The cell's own files with the sizes replaced in memory (every
    ratio of the configuration kept: a dense layer and expert layers,
    keys 12 + 8 against values 16, a shared expert, the module
    drafting), on the CPU through ``LMServer``; at a vocabulary of 23
    some drafts are accepted."""
    import jax

    from chipbench import run as entry

    cell = copy.deepcopy(spec.cell(CELL))
    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    cfg["model"].update(
        vocab_size=23, d_model=64, num_layers=3, num_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=16, max_len=128,
        kv_tile=16, expert_tile=8)
    cfg["compute_dtype"] = cfg["precision"]["parameters"] = "float32"
    cfg["engine"] = {"slots": 3, "max_len": 128, "prefill_chunk": 8,
                     "draft": "mtp", "spec_k": 1,
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
