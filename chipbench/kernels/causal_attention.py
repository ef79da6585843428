"""Operations and bytes of the training path's causal-attention calls
(``ops/pallas_attention.py``), from their shapes. One forward call and
its backward calls serve ``[B, T, H, hd]`` queries, keys and values."""

from __future__ import annotations


def forward(B: int, T: int, H: int, hd: int, itemsize: int = 2):
    """(flops, bytes) of one causal forward call: QK^T and PV over the
    lower triangle; q, k, v read and the output written once."""
    flops = 2 * 2 * B * H * hd * T * (T + 1) / 2
    return flops, 4 * B * T * H * hd * itemsize


def backward(B: int, T: int, H: int, hd: int, itemsize: int = 2):
    """(flops, bytes) of the backward pass (dq, and dk with dv): five
    triangle products where the forward has two (scores recomputed, dP,
    dQ, dK, dV); q, k, v, o, do read and dq, dk, dv written."""
    flops = 5 * 2 * B * H * hd * T * (T + 1) / 2
    return flops, 8 * B * T * H * hd * itemsize


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take for the call."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


PATTERN = r"^CausalSelfAttention_0 \(tpu_custom_call\)$"


def least_seconds_train(cell: dict, run: dict, trace: dict, peaks: dict):
    """The least seconds the chip could take for the attention calls the
    trace holds. The train step makes three per layer and step (forward;
    dq; dk with dv), so a third of the calls counted is the number of
    forward-and-backward groups."""
    from chipbench.harness import trace_reduce

    model = cell["config_spec"]["model"]
    B = cell["config_spec"]["trainer"]["batch_size"]
    T = cell["traffic_spec"]["seq_len"]
    H = model["num_heads"]
    shape = (B, T, H, model["d_model"] // H)
    groups = trace_reduce.kernel_seconds(trace, PATTERN, "op_calls") / 3
    return groups * (roofline_seconds(*forward(*shape), peaks)
                     + roofline_seconds(*backward(*shape), peaks))
