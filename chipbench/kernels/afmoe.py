"""Operations and bytes of ``afmoe_lm``'s training step (Trinity-Mini's
architecture, PR 44), from its shapes: the useful operations a token by
part (what ``mfu_pct.train8k`` counts; recomputed work is not in it),
the banded attention launches' (``ops/pallas_attention.py`` with a
window and grouped KV heads) and the held experts' grouped matmul with
its gradient (``ops/grouped_experts.py``: the same count whatever
implements the layer)."""

from __future__ import annotations

SLIDING = "sliding_attention"


def live_pairs(T: int, window: int | None) -> int:
    """(query, key) pairs one head attends over a sequence of ``T``:
    the causal wedge, or under a window the band ``W (W + 1) / 2 + (T -
    W) W``."""
    if window is None or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def layer_windows(model: dict):
    """Each layer's window, ``None`` for a full layer."""
    kinds = model.get("layer_types") or [
        "full_attention" if i % 4 == 3 else SLIDING
        for i in range(model["num_layers"])]
    return [model.get("sliding_window", 2048) if k == SLIDING else None
            for k in kinds[:model["num_layers"]]]


def _sizes(model: dict):
    d = model.get("d_model", 2048)
    H, Hk, hd = (model.get("num_heads", 32), model.get("num_kv_heads", 4),
                 model.get("head_dim", 128))
    return d, H, Hk, hd


def forward_flops_per_token(model: dict, T: int) -> dict:
    """One token's forward operations by part, summed over the layers:
    ``attn_project`` (q, k, v, the output gate, o), ``attend`` (scores
    and values over the mean live keys of each layer's kind),
    ``dense_ffn``, ``moe_shared``, ``moe_experts`` (the expected pairs a
    token sends to held experts: ``k * held / E``), ``moe_route``,
    ``head``."""
    d, H, Hk, hd = _sizes(model)
    E = model.get("n_routed_experts", 128)
    k = model.get("num_experts_per_tok", 8)
    held = model.get("experts_held") or E
    F = model.get("moe_intermediate_size", 1024)
    dense = model.get("num_dense_layers", 2)
    out = dict.fromkeys(("attn_project", "attend", "dense_ffn", "moe_shared",
                         "moe_experts", "moe_route", "head"), 0.0)
    for i, window in enumerate(layer_windows(model)):
        out["attn_project"] += 2 * d * hd * (3 * H + 2 * Hk)
        out["attend"] += 4 * hd * H * live_pairs(T, window) / T
        if i < dense:
            out["dense_ffn"] += 2 * 3 * d * model.get("intermediate_size",
                                                      6144)
        else:
            out["moe_shared"] += 2 * 3 * d * F * model.get(
                "n_shared_experts", 1)
            out["moe_experts"] += 2 * 3 * d * F * k * held / E
            out["moe_route"] += 2 * d * E
    out["head"] = 2 * d * model["vocab_size"]
    return out


def train_flops_per_token(model: dict, T: int) -> float:
    """Forward and backward: three times the forward's operations."""
    return 3 * sum(forward_flops_per_token(model, T).values())


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take for the call."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def attention_forward(B, T, H, Hk, hd, window, itemsize: int = 2):
    """(flops, bytes) of one forward launch: QK^T and PV over the live
    band; q read and the output written once, K and V once a KV head
    (the group shares them)."""
    flops = 2 * 2 * B * H * hd * live_pairs(T, window)
    return flops, 2 * B * T * hd * (H + Hk) * itemsize


def attention_backward(B, T, H, Hk, hd, window, itemsize: int = 2):
    """(flops, bytes) of the backward launches (dq; dk with dv): five
    band products where the forward has two; q, o, do and K, V read,
    dq and dk, dv written."""
    flops = 5 * 2 * B * H * hd * live_pairs(T, window)
    return flops, B * T * hd * (4 * H + 4 * Hk) * itemsize


def experts_step(model: dict, routed_here: float, itemsize: int = 2):
    """(flops, bytes) of the held experts' forward and backward in one
    optimizer step, all expert layers: three passes (forward, the
    rows' gradient, the banks' gradient) of three products over the
    ``routed_here`` rows; the held banks read twice and their gradient
    written once."""
    d, _, _, _ = _sizes(model)
    F = model.get("moe_intermediate_size", 1024)
    held = model.get("experts_held") or model.get("n_routed_experts", 128)
    layers = model["num_layers"] - model.get("num_dense_layers", 2)
    flops = 3 * 3 * 2 * d * F * routed_here
    return flops, 3 * layers * held * 3 * d * F * itemsize
