"""Switch-style mixture-of-experts with expert parallelism (ep).

The reference has no MoE (its models are MLP/CNN-scale; SURVEY.md §2 lists
expert parallelism as absent). This module is the framework's ep capability:
top-1 (Switch) routing with per-source capacity, experts sharded over a mesh
axis, and the canonical two-``all_to_all`` exchange — tokens travel to their
expert's device and back over ICI, the TPU-native equivalent of the
all-to-all dispatch in Switch Transformer / GShard.

Everything is dense one-hot matmul dispatch (MXU-friendly, static shapes,
no gather/scatter), so the whole layer jits into one XLA program. Dropped
tokens (capacity overflow) contribute zero and ride the residual connection,
the standard Switch behavior.

:func:`group_limited_route` and :func:`dropless_held_experts` are the
serving path's routed layer (``models/deepseek_v32.py``): the router
scores every expert of the model, the layer is told which of them this
chip holds and computes their part of the result by a grouped matmul
over as many rows as were routed here, with no capacity and no dropped
token. What the experts held elsewhere would add arrives, in a
deployment, from the chips that hold them; nothing here stands in for
it.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.grouped_experts import grouped_experts


def switch_moe(
    x: jnp.ndarray,          # [S, D] local tokens
    router_kernel,           # [D, E_global] (replicated)
    w1, b1,                  # [E_local, D, F], [E_local, F]
    w2, b2,                  # [E_local, F, D], [E_local, D]
    ep_size: int = 1,
    ep_axis: Optional[str] = None,
    capacity_factor: float = 1.25,
    dtype=jnp.float32,
    top_k: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed MoE layer. Returns ``(y [S, D], aux_loss scalar)``.

    ``top_k=1`` is the Switch Transformer; ``top_k=2`` is GShard-style
    (gates of the chosen experts renormalized to sum to 1, first choices
    get capacity priority over second choices).

    With ``ep_axis`` set (inside shard_map), each device holds
    ``E_local = E_global / ep_size`` experts and its own ``S`` tokens;
    dispatch crosses devices via two ``all_to_all``s. Capacity is
    ``capacity_factor * top_k * S / E_global`` **per source device** — the
    same number whether sharded or not, which keeps the sharded layer
    exactly equal to per-source-block unsharded computation (tested).

    The aux term is the Switch load-balancing loss
    ``E * sum_e(fraction_first_choice_e * mean_router_prob_e)`` over the
    LOCAL tokens (callers psum/mean it across shards).
    """
    S, D = x.shape
    E_local = w1.shape[0]
    E = E_local * ep_size
    k = top_k
    C = max(1, int(capacity_factor * k * S / E))

    logits = (x.astype(jnp.float32) @ router_kernel.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # [S, E] f32
    gate_k, expert_k = jax.lax.top_k(probs, k)            # [S, k]
    if k > 1:
        gate_k = gate_k / gate_k.sum(-1, keepdims=True)

    # choice-major flattening: ALL first choices rank (and claim capacity)
    # before any second choice — the GShard priority rule
    flat_expert = expert_k.T.reshape(k * S)               # [k*S]
    onehot_flat = jax.nn.one_hot(flat_expert, E, dtype=jnp.float32)
    rank = jnp.cumsum(onehot_flat, axis=0) * onehot_flat  # 1-based
    keep = (rank > 0) & (rank <= C)
    dispatch = onehot_flat * keep                         # [k*S, E]
    pos = jnp.clip(rank - 1, 0, C - 1).astype(jnp.int32)
    dispatch_t = (
        dispatch[..., None] * jax.nn.one_hot(pos, C, dtype=jnp.float32)
    ).reshape(k, S, E, C)
    send_t = dispatch_t.sum(axis=0)                       # [S, E, C]
    combine_t = jnp.einsum("ksec,sk->sec", dispatch_t, gate_k)

    # aux load-balancing loss (Switch eq. 4) over FIRST choices
    frac = onehot_flat.reshape(k, S, E)[0].mean(axis=0)
    aux = E * jnp.sum(frac * probs.mean(axis=0))

    d = jnp.einsum("sd,sec->ecd", x.astype(jnp.float32), send_t)  # [E, C, D]
    if ep_axis is not None and ep_size > 1:
        d = d.reshape(ep_size, E_local, C, D)
        # axis 0 = destination device → after exchange, axis 0 = source
        d = jax.lax.all_to_all(d, ep_axis, split_axis=0, concat_axis=0)
        d = d.transpose(1, 0, 2, 3).reshape(E_local, ep_size * C, D)
    h = jnp.einsum("ecd,edf->ecf", d.astype(dtype), w1.astype(dtype))
    h = jax.nn.gelu(h + b1[:, None].astype(dtype))
    y = jnp.einsum("ecf,efd->ecd", h, w2.astype(dtype))
    y = (y + b2[:, None].astype(dtype)).astype(jnp.float32)
    if ep_axis is not None and ep_size > 1:
        y = y.reshape(E_local, ep_size, C, D).transpose(1, 0, 2, 3)
        y = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0)
        y = y.reshape(E, C, D)
    out = jnp.einsum("ecd,sec->sd", y, combine_t)
    return out.astype(x.dtype), aux.astype(jnp.float32)


def group_limited_route(scores, bias, n_group: int, topk_group: int,
                        top_k: int, scale: float):
    """Group-limited top-k over ALL experts (``noaux_tc``): ``scores [N,
    E]`` float32 affinities (sigmoid), ``bias [E]`` the selection-only
    correction. Each of ``n_group`` groups scores the sum of its two
    best ``scores + bias``; the ``topk_group`` best groups stay; the
    ``top_k`` best experts among them are chosen. Gates are the chosen
    ``scores`` (without ``bias``) over their sum, times ``scale``.
    Returns ``(experts [N, top_k] int32, gates [N, top_k] float32)``."""
    N, E = scores.shape
    biased = scores + bias
    groups = biased.reshape(N, n_group, E // n_group)
    group_score = jax.lax.top_k(groups, 2)[0].sum(axis=-1)
    kept = jax.lax.top_k(group_score, topk_group)[1]
    keep = jnp.zeros((N, n_group), bool).at[
        jnp.arange(N)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(keep, E // n_group, axis=1), biased,
                       -jnp.inf)
    experts = jax.lax.top_k(masked, top_k)[1]
    gates = jnp.take_along_axis(scores, experts, axis=1)
    gates = gates / gates.sum(axis=-1, keepdims=True) * scale
    return experts.astype(jnp.int32), gates


def _on_tpu() -> bool:
    """The grouped matmul's kernels are written for the TPU; elsewhere
    the same steps run as plain XLA."""
    return jax.default_backend() == "tpu"


def dropless_held_experts(x, experts, gates, live, w_gate, w_up, w_down,
                          first: int, tile: int = 128):
    """The part of a routed layer's result that the experts held here
    give: no capacity, no token dropped, no one-hot dispatch.

    ``x [N, D]`` tokens; ``experts``/``gates [N, k]`` the router's choice
    over all experts (global ids); ``live [N]`` marks the tokens anyone
    reads (a mixed tick's padding is not routed); this chip holds the
    ``E_l`` experts ``first .. first + E_l - 1`` as ``w_gate``/``w_up
    [E_l, D, F]`` and ``w_down [E_l, F, D]`` (SwiGLU). The (token,
    expert) pairs are sorted by expert, pairs sent to no held expert
    last, and a grouped matmul runs over each expert's rows ``tile`` at
    a time: one gather of the rows, each touched expert's weights read
    once a tile, one combine into the tokens' order. On a TPU that is
    :func:`distkeras_tpu.ops.grouped_experts.grouped_experts` (two
    Pallas launches that walk the row tiles expert by expert, as many
    steps as the routing made; it refuses widths its launches cannot
    take); elsewhere the same three steps in plain XLA around
    ``jax.lax.ragged_dot``. Returns ``(y [N, D] float32,
    counts)`` with ``counts`` = ``routed_here`` (pairs of live tokens
    sent to held experts), ``routed_total`` (all pairs of live tokens),
    ``expert_rows_computed`` (rows the tiles ran over, padding
    included: ``tile`` for every ``tile`` rows or part of them that an
    expert was sent) and ``experts_read`` (held experts that were sent
    a row: times :func:`expert_bytes` what the layer has to read, which
    the host works out, since the layers of one tick take the bytes
    past an int32), int32 scalars."""
    N, D = x.shape
    k = experts.shape[1]
    E_l, _, F = w_gate.shape
    local = experts - first
    held = (local >= 0) & (local < E_l) & live[:, None]
    key = jnp.where(held, local, E_l).reshape(N * k)
    order = jnp.argsort(key, stable=True)
    sizes = (key[:, None] == jnp.arange(E_l)).sum(0, dtype=jnp.int32)
    tok = (order // k).astype(jnp.int32)
    gate = gates.reshape(N * k)[order].astype(jnp.float32)
    if _on_tpu():
        y = grouped_experts(x, tok, gate, sizes, w_gate, w_up, w_down,
                            tile=tile, interpret=False)
    else:
        y = _grouped_xla(x, tok, gate, sizes, w_gate, w_up, w_down)
    counts = {
        "routed_here": held.sum(dtype=jnp.int32),
        "routed_total": live.sum(dtype=jnp.int32) * k,
        "expert_rows_computed": ((sizes + tile - 1) // tile).sum(
            dtype=jnp.int32) * tile,
        "experts_read": (sizes > 0).sum(dtype=jnp.int32),
    }
    return y, counts


def expert_bytes(d: int, width: int, dtype) -> int:
    """Bytes of one SwiGLU expert's three matrices as a tick reads them
    (``[d, width]`` twice, ``[width, d]``, in the compute dtype)."""
    return 3 * d * width * jnp.dtype(dtype).itemsize


def _grouped_xla(x, tok, gate, sizes, w_gate, w_up, w_down):
    """:func:`~distkeras_tpu.ops.grouped_experts.grouped_experts` in
    plain XLA, for the backends its kernels are not written for: the
    sorted rows gathered, three ragged matmuls (a row past the last
    expert's run comes out zero), one scatter-add."""
    xs = x[tok]

    def dot(rows, bank):
        return jax.lax.ragged_dot(rows, bank, sizes,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)
    out = dot(h.astype(x.dtype), w_down)
    return jnp.zeros(x.shape, jnp.float32).at[tok].add(out * gate[:, None])


class SwitchMoE(nn.Module):
    """Flax wrapper owning the router + expert params.

    ``num_experts`` is GLOBAL; with ``ep_size>1`` the module creates the
    local ``num_experts/ep_size`` slice (same param names/structure as the
    ``ep_size=1`` module, so a full-size host init slices onto the mesh via
    :func:`distkeras_tpu.parallel.spmd.lm_param_specs`).
    """

    num_experts: int = 8
    hidden: int = 1024
    ep_size: int = 1
    ep_axis: str = "ep"
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    top_k: int = 1  # 1 = Switch, 2 = GShard-style

    @nn.compact
    def __call__(self, x):  # [B, T, D] -> [B, T, D]; aux is SOWN
        # into the 'intermediates' collection (read it via
        # apply(..., mutable=['intermediates']), as the MoE train step does)
        B, T, D = x.shape
        E, F = self.num_experts, self.hidden
        if E % self.ep_size != 0:
            raise ValueError(
                f"num_experts={E} not divisible by ep_size={self.ep_size}"
            )
        El = E // self.ep_size
        init = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal")
        router = self.param("router", init, (D, E), jnp.float32)
        w1 = self.param("w1", init, (El, D, F), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (El, F), jnp.float32)
        w2 = self.param("w2", init, (El, F, D), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (El, D), jnp.float32)
        y, aux = switch_moe(
            x.reshape(B * T, D), router, w1, b1, w2, b2,
            ep_size=self.ep_size,
            ep_axis=self.ep_axis if self.ep_size > 1 else None,
            capacity_factor=self.capacity_factor,
            dtype=self.dtype,
            top_k=self.top_k,
        )
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(B, T, D)
