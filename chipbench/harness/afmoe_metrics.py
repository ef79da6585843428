"""Per-layer metrics of ``afmoe_lm``'s training step (PR 44's scopes,
launches and metrics rows; the ``derived`` reader calls each as
``fn(cell, run, peaks)``). Every one returns ``None`` where the program
has nothing of the kind to read, as a commit from before PR 44 has not:
the metric is then left out of the line.

The attention launches are told apart by their name scope in the
compiled module: ``window_attend`` / ``full_attend`` is the layer's
kind, ``transpose(`` marks the backward pass, and
``rematted_computation`` inside it the forward launch that
``remat="block"`` runs again (a launch like the first: counted with the
forward launches)."""

from __future__ import annotations

import json
import os

from chipbench.harness import span_reduce
from chipbench.harness.span_metrics import _scope_pct
from chipbench.harness.sparse_moe_metrics import _sum
from chipbench.kernels import afmoe

KINDS = ("window_attend", "full_attend")


def _shape(cell: dict):
    cfg, model = cell["config_spec"], cell["config_spec"]["model"]
    d, H, Hk, hd = afmoe._sizes(model)
    return (cfg["trainer"]["batch_size"], cell["traffic_spec"]["seq_len"],
            H, Hk, hd)


def mfu_pct(cell: dict, run: dict, peaks: dict):
    """Model FLOP/s utilization of the whole step: tokens per second
    times the useful operations a token (the kernels file's count; no
    recomputed work) over the chips' bf16 peak."""
    if "train_tok_s" not in run:
        return None
    per_token = afmoe.train_flops_per_token(
        cell["config_spec"]["model"], cell["traffic_spec"]["seq_len"])
    return 100.0 * run["train_tok_s"] * per_token / (
        run["device"]["count"] * peaks["flops_bf16"])


def _launches(run: dict, backward: bool):
    """``{kind: (seconds, calls)}`` of the attention launches of the
    forward pass (the one ``remat`` runs again among them) or of the
    backward pass."""
    profile = span_reduce.profile_of(run)
    if not profile or not profile["scopes"]:
        return None
    out = {}
    for kind in KINDS:
        under = span_reduce.under_scope(kind)

        def keep(name, scope):
            if "(tpu_custom_call)" not in name or not under(name, scope):
                return False
            return backward == ("transpose(" in scope and
                                "rematted_computation" not in scope)

        out[kind] = span_reduce.seconds_where(profile, keep)
    return out if any(calls for _, calls in out.values()) else None


def _attention_roofline(cell, run, peaks, backward):
    B, T, H, Hk, hd = _shape(cell)
    window = cell["config_spec"]["model"].get("sliding_window", 2048)
    least = seconds = 0.0
    for back in ((False, True) if backward is None else (backward,)):
        found = _launches(run, back)
        if found is None:
            continue
        for kind, (secs, calls) in found.items():
            shape = (B, T, H, Hk, hd, window if kind == KINDS[0] else None)
            if back:  # two launches a pass: dq; dk with dv
                least += calls / 2 * afmoe.roofline_seconds(
                    *afmoe.attention_backward(*shape), peaks)
            else:
                least += calls * afmoe.roofline_seconds(
                    *afmoe.attention_forward(*shape), peaks)
            seconds += secs
    return 100.0 * least / seconds if seconds else None


def banded_attention_roofline(cell: dict, run: dict, peaks: dict):
    return _attention_roofline(cell, run, peaks, None)


def banded_attention_fwd_roofline(cell: dict, run: dict, peaks: dict):
    return _attention_roofline(cell, run, peaks, False)


def banded_attention_bwd_roofline(cell: dict, run: dict, peaks: dict):
    return _attention_roofline(cell, run, peaks, True)


def attend_device_pct(cell: dict, run: dict, peaks: dict):
    return _sum(*(_scope_pct(run, kind) for kind in KINDS))


def _step_rows(cell: dict, run: dict):
    """The window's metrics rows (the trainer's ``metrics_path``, which
    the runner keeps beside the trace): steps after the set-up epoch."""
    if not run.get("trace_dir"):
        return []
    path = os.path.join(os.path.dirname(run["trace_dir"]),
                        f"{cell['name']}.metrics.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        rows = [r for r in map(json.loads, f) if "step" in r]
    return rows[cell["traffic_spec"]["steps_per_epoch"]:]


def _row_mean(cell: dict, run: dict, key: str):
    values = [r[key] for r in _step_rows(cell, run) if key in r]
    return sum(values) / len(values) if values else None


def held_load_max_over_mean(cell: dict, run: dict, peaks: dict):
    """The fullest held expert's rows over the held experts' mean (a
    mean over the expert layers), averaged over the window's steps:
    what this chip pays of the routing's imbalance."""
    return _row_mean(cell, run, "held_load_max_over_mean")


def routed_here_over_even(cell: dict, run: dict, peaks: dict):
    """The held experts' share of the pairs over an even share (a mean
    over the expert layers), averaged over the window's steps."""
    return _row_mean(cell, run, "routed_here_over_even")


def moe_experts_roofline(cell: dict, run: dict, peaks: dict):
    """The held experts' forward and backward against their roofline:
    the least seconds of a step's nine products over the rows routed
    here (the window's mean ``routed_here`` a step) and of the banks
    read twice and their gradient written once, times the steps the
    trace holds (a backward weight launch a row and expert layer), over
    the device seconds under ``moe_experts``."""
    profile = span_reduce.profile_of(run)
    routed = [r["routed_here"] for r in _step_rows(cell, run)
              if "routed_here" in r]
    if not profile or not profile["scopes"] or not routed:
        return None
    under = span_reduce.under_scope("moe_experts")
    seconds, calls = span_reduce.seconds_where(profile, under)
    _, launches = span_reduce.seconds_where(
        profile, lambda name, scope: under(name, scope)
        and name.startswith("moe_bwd_weights"))
    model = cell["config_spec"]["model"]
    layers = model["num_layers"] - model.get("num_dense_layers", 2)
    steps = launches / (layers * cell["config_spec"]["trainer"]["batch_size"])
    if not seconds or not steps:
        return None
    least = steps * afmoe.roofline_seconds(
        *afmoe.experts_step(model, sum(routed) / len(routed)), peaks)
    return 100.0 * least / seconds
