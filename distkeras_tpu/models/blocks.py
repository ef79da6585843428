"""Blocks more than one language model is built from: RMSNorm, the
SwiGLU feed-forward, the expert layer of which a chip holds a share
(``mimo_v2_lm``, ``solar_open2_lm``; ``deepseek_v32_lm`` takes it a
part at a time), and the packing of a mixed tick's live tokens
(``transformer_lm``, ``mimo_v2_lm``, ``solar_open2_lm`` to one compiled
count; ``deepseek_v32_lm`` to the blocks in use). One home, so that an
optimisation of one is an optimisation of every model that runs it, and
the benchmark's cells of the others catch what it costs them."""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from distkeras_tpu.ops.moe import (
    dropless_held_experts, expert_bytes, group_limited_route)


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


def expert_counter_units(model) -> dict:
    """``tick_counter_units`` of a model whose expert layers are
    :class:`RoutedExperts`: the device counts ``experts_read`` (a tick's
    bytes pass an int32, its experts do not) and the host keeps
    ``expert_weight_bytes``, that many times one expert's matrices."""
    return {"experts_read": ("expert_weight_bytes", expert_bytes(
        model.d_model, model.moe_intermediate_size, model.dtype))}


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
                self.expert_tile)
        for name, value in counts.items():
            self.sow("counters", name, value, reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        if not self.n_shared_experts:
            return y.reshape(B, T, d)
        with jax.named_scope("moe_shared"):
            shared = SwiGLU(self.width * self.n_shared_experts, self.dtype,
                            pd, name="shared")(u)
        return shared + y.reshape(B, T, d)


class RoutedExpertsByPart(RoutedExperts):
    """:class:`RoutedExperts`' parameters and arithmetic with the
    parts callable one by one, for a model whose mixed tick runs the
    router and the shared expert over the blocks of packed rows in use
    (:func:`map_live_blocks`) and the held experts once over the packed
    array: their grouped matmul already sizes its work from the rows
    routed, and a call a block would read every expert's weights again
    for a quarter of the rows. Called whole it is ``RoutedExperts``."""
    d_model: int = 0

    def setup(self):
        d, E, held = self.d_model, self.n_routed_experts, self.experts_held
        pd = self.param_dtype
        self.router = self.param("router", _normal(), (d, E), pd)
        self.bias = self.param("e_score_correction_bias",
                               nn.initializers.zeros, (E,), jnp.float32)
        self.w_gate = self.param("w_gate", _normal(), (held, d, self.width),
                                 pd)
        self.w_up = self.param("w_up", _normal(), (held, d, self.width), pd)
        self.w_down = self.param("w_down", _normal(), (held, self.width, d),
                                 pd)
        self.shared = (SwiGLU(self.width * self.n_shared_experts, self.dtype,
                              pd) if self.n_shared_experts else None)

    def route(self, x):
        """``x [n, d]`` float32 -> ``(experts, gates) [n, k]`` over all
        experts."""
        with jax.named_scope("moe_route"):
            # float32 scores at full precision: a routing decision is
            # discrete, and rounding here sends a token elsewhere
            scores = jax.nn.sigmoid(jnp.dot(
                x, self.router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            return group_limited_route(
                scores, self.bias.astype(jnp.float32), self.n_group,
                self.topk_group, self.num_experts_per_tok,
                self.routed_scaling_factor)

    def held(self, x, experts, gates, live):
        """What the experts held here give the ``live [n]`` of the
        tokens ``x [n, d]``: ``[n, d]`` float32, the layer's counters
        sown."""
        with jax.named_scope("moe_experts"):
            y, counts = dropless_held_experts(
                x.astype(self.dtype), experts, gates, live,
                self.w_gate.astype(self.dtype), self.w_up.astype(self.dtype),
                self.w_down.astype(self.dtype),
                self.expert_rank * self.experts_held, self.expert_tile)
        for name, value in counts.items():
            self.sow("counters", name, value, reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        return y

    def __call__(self, u, live):
        B, T, d = u.shape
        x = u.reshape(B * T, d)
        y = self.held(x, *self.route(x), live.reshape(B * T))
        if self.shared is None:
            return y.reshape(B, T, d)
        with jax.named_scope("moe_shared"):
            return self.shared(u) + y.reshape(B, T, d)


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


# Rows a block of packed live tokens holds where a model runs its
# per-token layers over the blocks in use: past the chip's ridge (~240
# rows a weight byte on a v5e), so a block's matmuls are bound by their
# arithmetic and reading the weights again for the next block costs no
# more than that block's own multiplications
LIVE_BLOCK = 512


def live_block_rows(positions: int) -> int:
    """Rows a block of a tick of ``positions`` = ``S * C`` holds:
    :data:`LIVE_BLOCK` where that divides the tick, else the whole tick
    is one block (the tiny engines of the tests)."""
    return LIVE_BLOCK if positions % LIVE_BLOCK == 0 else positions


def map_live_blocks(mdl, fn, xs, like, n_blocks, tail: int = 0,
                    over=None):
    """``fn(mdl, *blocks)`` over the first ``n_blocks`` blocks of
    :func:`live_block_rows` packed rows of the ``[1, N, ...]`` arrays
    ``xs``, in ONE loop whose trip count is the traced ``n_blocks``
    (``ceil(live / block)``, found on the device): one compiled body,
    run as often as blocks hold a token. ``fn`` returns a tuple of ``[1,
    block, ...]`` arrays, ``like`` gives each one's trailing shape and
    dtype; the results come back ``[1, N + tail, ...]``, zeros in the
    blocks that were not run and in the ``tail`` rows beyond them (room
    for a consumer that slices a fixed number of rows from any token
    on). ``over`` is a tuple of arrays of the results' shapes to write
    over instead of fresh zeros (the same stage's results in the layer
    before: the blocks run there cover the blocks run here, and
    nothing reads a row of a block that was not run). ``mdl`` is the
    module whose parameters ``fn`` reads (lifted into the loop:
    read-only there, so ``fn`` declares no variable and sows nothing)."""
    N = xs[0].shape[1]
    block = live_block_rows(N)

    def body(m, carry):
        b, outs = carry
        at = b * block
        # behind a barrier: a layout the body's matmuls want is given
        # to the block, not to all N rows in a copy of their own
        ys = fn(m, *jax.lax.optimization_barrier(tuple(
            jax.lax.dynamic_slice_in_dim(x, at, block, 1) for x in xs)))
        # rows major, said aloud: left to itself the compiler writes a
        # wide result rows-minor (the projection's own layout), gives
        # that layout to all N rows, and copies them for the reader
        ys = tuple(with_layout_constraint(
            y.astype(o.dtype), Layout(major_to_minor=tuple(range(y.ndim))))
            for o, y in zip(outs, ys))
        return b + 1, tuple(
            jax.lax.dynamic_update_slice_in_dim(o, y, at, 1)
            for o, y in zip(outs, ys))

    init = over or tuple(jnp.zeros((1, N + tail) + tuple(shape), dtype)
                         for shape, dtype in like)
    return nn.while_loop(lambda _, carry: carry[0] < n_blocks, body, mdl,
                         (jnp.zeros((), jnp.int32), init))[1]
