"""Blocks more than one language model is built from: RMSNorm, the
SwiGLU feed-forward, the expert layer of which a chip holds a share
(``deepseek_v32_lm``, ``mimo_v2_lm``), and the packing of a mixed
tick's live tokens (``transformer_lm``, ``mimo_v2_lm``). One home, so
that an optimisation of one is an optimisation of every model that
runs it, and the benchmark's cells of the others catch what it costs
them."""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.moe import dropless_held_experts, group_limited_route


def _normal(fan_in_axes: int = 1):
    """Fan-in scaled normal over the first ``fan_in_axes`` axes."""
    def init(key, shape, dtype):
        fan_in = int(np.prod(shape[:fan_in_axes]))
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)
    return init


def _dot(x, kernel, dtype):
    """``x [..., in] @ kernel [in, ...]``: operands in ``dtype``, float32
    accumulation and result."""
    return jax.lax.dot_general(
        x.astype(dtype), kernel.astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate u) * W_up u)``; float32 out."""
    width: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        d = u.shape[-1]
        w_gate = self.param("w_gate", _normal(), (d, self.width),
                            self.param_dtype)
        w_up = self.param("w_up", _normal(), (d, self.width),
                          self.param_dtype)
        w_down = self.param("w_down", _normal(), (self.width, d),
                            self.param_dtype)
        h = jax.nn.silu(_dot(u, w_gate, self.dtype)) * _dot(
            u, w_up, self.dtype)
        return _dot(h, w_down, self.dtype)


class RoutedExperts(nn.Module):
    """The expert layer: sigmoid router over all experts with
    group-limited top-k (``n_group = topk_group = 1``: no limit), this
    chip's share of the routed experts, and the shared expert where the
    model has one (``n_shared_experts = 0``: no ``shared`` parameters,
    nothing added)."""
    n_routed_experts: int
    experts_held: int
    expert_rank: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    width: int
    n_shared_experts: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    expert_tile: int = 128
    # one loop over the held experts in place of one loop an expert
    # (``ops/moe.py . dropless_held_experts``): less program to compile
    rolled: bool = False

    @nn.compact
    def __call__(self, u, live):
        B, T, d = u.shape
        E, held = self.n_routed_experts, self.experts_held
        pd = self.param_dtype
        router = self.param("router", _normal(), (d, E), pd)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (E,), jnp.float32)
        w_gate = self.param("w_gate", _normal(), (held, d, self.width), pd)
        w_up = self.param("w_up", _normal(), (held, d, self.width), pd)
        w_down = self.param("w_down", _normal(), (held, self.width, d), pd)
        x = u.reshape(B * T, d)
        with jax.named_scope("moe_route"):
            # float32 scores at full precision: a routing decision is
            # discrete, and rounding here sends a token elsewhere
            scores = jax.nn.sigmoid(jnp.dot(
                x, router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            experts, gates = group_limited_route(
                scores, bias.astype(jnp.float32), self.n_group,
                self.topk_group, self.num_experts_per_tok,
                self.routed_scaling_factor)
        with jax.named_scope("moe_experts"):
            y, counts = dropless_held_experts(
                x.astype(self.dtype), experts, gates, live.reshape(B * T),
                w_gate.astype(self.dtype), w_up.astype(self.dtype),
                w_down.astype(self.dtype), self.expert_rank * held,
                self.expert_tile, self.rolled)
        for name, value in counts.items():
            self.sow("counters", name, value, reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        if not self.n_shared_experts:
            return y.reshape(B, T, d)
        with jax.named_scope("moe_shared"):
            shared = SwiGLU(self.width * self.n_shared_experts, self.dtype,
                            pd, name="shared")(u)
        return shared + y.reshape(B, T, d)


class LivePacking(NamedTuple):
    """Where a mixed tick's live tokens lie once packed (traced; see
    ``TransformerLM.__call__``, ``live_tokens``)."""

    idx: jnp.ndarray  # [N]: the flat [S * C] position packed row n holds
    inv: jnp.ndarray  # [S, C]: the packed row of a position; N where none


def live_packing(valid_lens, C: int, N: int) -> LivePacking:
    """Pack the positions ``t < valid_lens[s]`` of an ``[S, C]`` tick
    to ``N`` rows, live positions first and in row-major order (a stable
    sort), so row ``s``'s tokens are contiguous and end at
    ``cumsum(valid_lens)[s] - 1``. Rows beyond the live count hold dead
    positions: computed, never unpacked, never read. The caller
    guarantees ``sum(valid_lens) <= N``."""
    live = (jnp.arange(C)[None, :] < valid_lens[:, None]).reshape(-1)
    idx = jnp.argsort(~live, stable=True)[:N]
    inv = jnp.where(live, jnp.cumsum(live.astype(jnp.int32)) - 1, N)
    return LivePacking(idx, inv.reshape(-1, C))


def unpack_live(t, packing: LivePacking):
    """``[1, N, ...]`` packed rows -> the ``[S, C, ...]`` layout, zeros
    where no token was dealt."""
    return jnp.take(t[0], packing.inv, axis=0, mode="fill", fill_value=0)


def pack_live(t, packing: LivePacking):
    """``[S, C, ...]`` -> the ``[1, N, ...]`` packed rows."""
    return t.reshape((-1,) + t.shape[2:])[packing.idx][None]
