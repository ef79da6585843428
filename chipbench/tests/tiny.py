"""Tiny twins of the cells, for the CPU: the real cell's files with the
sizes replaced in memory."""

import copy

from chipbench.harness import spec

TINY_MODEL = {"vocab_size": 211, "d_model": 64, "num_heads": 4,
              "num_layers": 2, "max_len": 128}


def tiny_cell(name: str, root: str = spec.ROOT) -> dict:
    cell = copy.deepcopy(spec.cell(name, root))
    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    # the sizes are replaced; what else the file states (positions) stays
    cfg["model"] = {**cfg["model"], **TINY_MODEL}
    cfg["compute_dtype"] = "float32"
    if cfg["role"] == "train":
        cfg["trainer"]["batch_size"] = 2
        cfg["trainer"]["schedule"] = {"init": 1e-3, "peak": 1e-2,
                                      "warmup_steps": 10}
        mix.update(seq_len=32, steps_per_epoch=3, nominal_tokens_per_s=400,
                   trace_skip_epochs=0, trace_epochs=1)
        cell["limits"] = {"loss_rel_gap": 1e-4, "last_loss_over_first": 0.999}
    else:
        cfg["engine"] = {"slots": 2, "max_len": 128}
        for key, median in (("prompt_len", 20), ("output_len", 24)):
            mix[key] = {"median": median, "sigma": 0.5, "min": 4, "max": 70}
        mix.update(max_total=100, ramp_s=0.3, trace_after_s=0.1, trace_s=0.5)
        if mix["kind"] == "closed_loop":
            mix.update(clients=3, size_pool=16)
        else:
            mix["rate_rps"] = 6.0
        cell["limits"] = {"served_logit_gap_max": 1e-3, "far_off_gap": 1e-3,
                          "near_tie_margin": 0.05, "near_ties_wanted": 20,
                          "served_far_off_per_near_tie": 0.01,
                          "sample_requests": 4, "sample_requests_max": 8}
    return cell
