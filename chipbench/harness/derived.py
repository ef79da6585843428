"""Per-layer metrics worked out from a run's own end-to-end reading."""

from __future__ import annotations

from chipbench.kernels import gpt2_block


def train_mfu_pct(cell: dict, run: dict, peaks: dict) -> float:
    """Model FLOP/s utilization: tokens per second times the operations
    one token requires (the benchmark's own count from the shapes, no
    recomputed work) over the chips' bf16 peak."""
    cfg = cell["config_spec"]
    per_token = gpt2_block.train_flops_per_token(
        cfg["model"], cell["traffic_spec"]["seq_len"])
    return 100.0 * run["train_tok_s"] * per_token / (
        run["device"]["count"] * peaks["flops_bf16"])
