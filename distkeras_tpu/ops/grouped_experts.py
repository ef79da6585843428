"""The held experts' grouped matmul as two Pallas launches over the
(token, expert) pairs sorted by expert
(:func:`distkeras_tpu.ops.moe.dropless_held_experts` is the caller and
holds the routing, the sort and the counters).

A *visit* is up to ``tile`` consecutive rows of one expert's run of the
sorted pairs: an expert sent ``n`` rows has ``ceil(n / tile)`` visits,
an expert sent none has none and its weights are never read. The visit
list (:func:`visits`) is handed to both launches as scalar prefetch and
is the walk of both grids, so the weight blocks of consecutive visits —
of consecutive experts — follow each other through one double-buffered
pipeline that does not drain inside a launch, and the trip count is the
number of visits the routing made, not the static ``N * k``.

**Gate and up** (:func:`_gate_up_kernel`, launch ``moe_gate_up``):
grid ``(visits, F / bf)``. At a visit's first step its tokens are
gathered from ``x`` in HBM, one copy a row, as many as the visit has
(a token travels as float32, ``D / 128`` whole sublanes: one row of a
16-bit array is half a sublane, which a copy cannot address; the tile
is rounded back to the compute dtype, which loses nothing). Each step
multiplies the tile by one ``[D, bf]`` block of both banks and keeps
``silu(x W_gate) * (x W_up)`` rounded to the compute dtype; ``h`` goes
to HBM as ``[F / bf, visits * tile, bf]``, a visit's blocks one output
block.

**Down and combine** (:func:`_down_kernel`, launch ``moe_down``): grid
``(D / bd, visits)``. A ``[N, bd]`` block of the result stays in VMEM
while every visit passes: the visit's ``h`` times the expert's ``[F,
bd]`` block of ``w_down``, and each of its rows, times the pair's gate,
added to its token's row of the block. The result leaves the chip once
a block, in token order: there is no array of per-pair results and no
scatter after the launch.

Block widths come from the shapes (:func:`_block`): the widest whole
number of lanes that divides the axis and keeps the double-buffered
weight blocks inside :data:`_WEIGHT_VMEM`. Rows of a visit past its
count are computed on whatever the tile held and never read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a launch may ask of the core's 128 MiB of VMEM, and the part of
# it the double-buffered weight blocks may take
VMEM_LIMIT = 64 * 1024 * 1024
_WEIGHT_VMEM = 24 * 1024 * 1024


def supports(D: int, F: int, tile: int) -> bool:
    """Shapes the launches run on a TPU (tests/test_chip_compile.py
    holds the four configurations' to the compiler for a described
    v5e): a token's row whole float32 tiles (8 sublanes of 128 lanes),
    blocks of whole lanes, a row tile of whole 16-bit sublane pairs.
    :func:`grouped_experts` refuses any other there: the chip has this
    one form of the layer."""
    return D % 1024 == 0 and F % 128 == 0 and tile % 16 == 0


def visits(sizes, tile: int, pairs: int):
    """The walk of the sorted pairs: ``sizes [E_l]`` rows an expert,
    their runs packed from row 0 in expert order, ``pairs`` rows in
    all. Returns ``(expert [V], base [V], rows [V], n)`` int32 with ``V
    = ceil(pairs / tile) + E_l`` the most visits there can be and ``n``
    how many there are: visit ``i < n`` runs the sorted rows ``base[i]
    .. base[i] + rows[i] - 1`` under expert ``expert[i]``; a visit past
    ``n`` is all zeros."""
    E_l = sizes.shape[0]
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    count = (sizes + tile - 1) // tile
    upto = jnp.cumsum(count).astype(jnp.int32)
    i = jnp.arange(-(-pairs // tile) + E_l, dtype=jnp.int32)[:, None]
    # visit i against every expert's run of visits: comparisons and
    # sums over [V, E_l] (a search is a loop on the device, and a
    # gather of V scalars cost 56 us, four of them 12 % of the layer)
    mine = ((upto - count)[None, :] <= i) & (i < upto[None, :])

    def of(per_expert):
        return jnp.where(mine, per_expert, 0).sum(1, dtype=jnp.int32)

    expert = of(jnp.arange(E_l, dtype=jnp.int32)[None, :])
    base = of((ends - sizes)[None, :] + (i - (upto - count)[None, :]) * tile)
    rows = jnp.minimum(of(ends[None, :]) - base, tile)
    return expert, base, rows, upto[-1]


def _block(width: int, rows: int, banks: int, itemsize: int) -> int:
    """Columns a weight block holds: the widest whole number of lanes
    that divides ``width`` with ``banks`` double-buffered ``[rows,
    block]`` blocks inside :data:`_WEIGHT_VMEM`; the whole axis where
    it is no whole number of lanes (the tests' tiny widths)."""
    for b in (1024, 512, 256, 128):
        if width % b == 0 and 2 * banks * rows * b * itemsize <= _WEIGHT_VMEM:
            return b
    return width if width % 128 else 128


def _gate_up_kernel(expert_ref, base_ref, rows_ref, tok_ref, x_hbm, wg_ref,
                    wu_ref, h_ref, staged, xt, sem, *, tile: int):
    v, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _():
        G = x_hbm.shape[1]

        def row(r):
            return pltpu.make_async_copy(
                x_hbm.at[tok_ref[base_ref[v] + r]],
                staged.at[pl.ds(pl.multiple_of(r * G, G), G)], sem)

        def start(r, c):
            row(r).start()
            return c

        def wait(r, c):
            row(r).wait()
            return c

        jax.lax.fori_loop(0, rows_ref[v], start, 0)
        jax.lax.fori_loop(0, rows_ref[v], wait, 0)
        # a token arrived as G sublanes of 128 lanes: sublane c of
        # every token is the tile's c-th group of 128 columns
        xt[...] = jnp.concatenate(
            [staged[pl.ds(c, tile, stride=G), :] for c in range(G)],
            axis=1).astype(xt.dtype)

    h = jax.nn.silu(jnp.dot(
        xt[...], wg_ref[...], preferred_element_type=jnp.float32)) * jnp.dot(
        xt[...], wu_ref[...], preferred_element_type=jnp.float32)
    h_ref[f] = h.astype(h_ref.dtype)


def _down_kernel(expert_ref, base_ref, rows_ref, tok_ref, gate_ref, h_ref,
                 wd_ref, y_ref, out):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    out[...] = sum(
        jnp.dot(h_ref[f], wd_ref[f], preferred_element_type=jnp.float32)
        for f in range(h_ref.shape[0]))

    def add(r, c):
        at = base_ref[v] + r
        y_ref[pl.ds(tok_ref[at], 1), :] += out[pl.ds(r, 1), :] * gate_ref[at]
        return c

    jax.lax.fori_loop(0, rows_ref[v], add, 0)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_experts(x, tok, gate, sizes, w_gate, w_up, w_down, *,
                    tile: int, interpret: bool):
    """``y [N, D]`` float32: for the sorted pair ``r`` of expert ``e``
    (``sizes [E_l]`` rows an expert, runs packed from row 0), ``gate[r]
    * w_down[e](silu(w_gate[e] x[tok[r]]) * w_up[e] x[tok[r]])`` added
    into row ``tok[r]``. ``x [N, D]`` in the compute dtype (that of the
    banks ``[E_l, D, F]``, ``[E_l, F, D]``); ``tok [P]`` int32 and
    ``gate [P]`` float32 are read below ``sum(sizes)`` only. Under its own ``jit``: the expert
    layers of a model call it with one set of shapes, so the kernels
    are traced and lowered once a program."""
    N, D = x.shape
    E_l, _, F = w_gate.shape
    if not interpret and not supports(D, F, tile):
        raise ValueError(
            f"the routed experts' launches take d_model in whole 1024s, an "
            f"expert width in whole 128s and expert_tile in whole 16s on a "
            f"TPU: got {D}, {F}, {tile}")
    dt = w_gate.dtype
    size = jnp.dtype(dt).itemsize
    bf = _block(F, D, 2, size)
    bd = _block(D, F, 1, size)
    nf, nd = F // bf, D // bd
    # a token's row travels as whole float32 sublanes of 128 lanes
    lanes = 128 if D % 128 == 0 else D
    expert, base, rows, n = visits(sizes, tile, tok.shape[0])
    # a layer that was sent nothing still zeroes its result: one visit
    # of no rows
    n = jnp.maximum(n, 1)
    scalars = (expert, base, rows, tok)
    params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)

    h = pl.pallas_call(
        functools.partial(_gate_up_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, nf),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec((None, D, bf), lambda v, f, e, *_: (e[v], 0, f)),
                pl.BlockSpec((None, D, bf), lambda v, f, e, *_: (e[v], 0, f)),
            ],
            out_specs=pl.BlockSpec((nf, tile, bf), lambda v, f, *_:
                                   (0, v, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile * (D // lanes), lanes), jnp.float32),
                pltpu.VMEM((tile, D), dt),
                pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((nf, expert.shape[0] * tile, bf), dt),
        interpret=interpret,
        compiler_params=params,
        name="moe_gate_up",
    )(*scalars, x.astype(jnp.float32).reshape(N, D // lanes, lanes),
      w_gate, w_up)

    return pl.pallas_call(
        _down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(nd, n),
            in_specs=[
                pl.BlockSpec((nf, tile, bf), lambda d, v, *_: (0, v, 0)),
                pl.BlockSpec((None, nf, bf, bd), lambda d, v, e, *_:
                             (e[v], 0, 0, d)),
            ],
            out_specs=pl.BlockSpec((N, bd), lambda d, v, *_: (0, d)),
            scratch_shapes=[pltpu.VMEM((tile, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        interpret=interpret,
        compiler_params=params,
        name="moe_down",
    )(*scalars, gate, h,
      w_down.reshape(E_l, nf, bf, D))
