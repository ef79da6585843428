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
_RESULT_VMEM = 32 * 1024 * 1024


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


def _result_block(D: int, F: int, N: int, itemsize: int) -> int:
    """Columns of the ``[N, block]`` float32 result a down launch keeps
    in VMEM: :func:`_block`'s for the weights, narrowed until the
    double-buffered result fits :data:`_RESULT_VMEM` (a training step's
    16 384 tokens; a serving tick's rows never narrow it)."""
    b = _block(D, F, 1, itemsize)
    while b > 128 and b % 256 == 0 and 2 * N * b * 4 > _RESULT_VMEM:
        b //= 2
    return b


def _struct(shape, dtype, like):
    """An output's aval with ``like``'s varying mesh axes: inside
    ``shard_map`` (the LM train step) an output has to say how it
    varies; outside there is nothing to say."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _vary_like(a, like):
    """``a`` varying over the mesh axes ``like`` varies over (the banks
    are replicated where the tokens are sharded; the transpose of the
    cast is the sum of their gradient over those axes)."""
    missing = tuple(sorted(jax.typeof(like).vma - jax.typeof(a).vma))
    return jax.lax.pcast(a, missing, to="varying") if missing else a


def _rows_f32(x, lanes: int):
    """A token's row as whole float32 sublanes of ``lanes`` lanes."""
    N, D = x.shape
    return x.astype(jnp.float32).reshape(N, D // lanes, lanes)


def _gather_rows(v, tok_ref, base_ref, rows_ref, pairs, tile: int):
    """Copies the visit's rows of each ``(hbm, staged, tile_ref, sem)``
    in ``pairs`` from HBM, as many as the visit has, and lays them out
    as ``[tile, D]`` in the tile's dtype (a token arrived as G sublanes
    of 128 lanes: sublane c of every token is the tile's c-th group of
    128 columns)."""
    def row(hbm, staged, sem, r):
        G = hbm.shape[1]
        return pltpu.make_async_copy(
            hbm.at[tok_ref[base_ref[v] + r]],
            staged.at[pl.ds(pl.multiple_of(r * G, G), G)], sem)

    def start(r, c):
        for hbm, staged, _, sem in pairs:
            row(hbm, staged, sem, r).start()
        return c

    def wait(r, c):
        for hbm, staged, _, sem in pairs:
            row(hbm, staged, sem, r).wait()
        return c

    jax.lax.fori_loop(0, rows_ref[v], start, 0)
    jax.lax.fori_loop(0, rows_ref[v], wait, 0)
    for hbm, staged, out, _ in pairs:
        G = hbm.shape[1]
        out[...] = jnp.concatenate(
            [staged[pl.ds(c, tile, stride=G), :] for c in range(G)],
            axis=1).astype(out.dtype)


def _bwd_hidden_kernel(expert_ref, base_ref, rows_ref, tok_ref, gate_ref,
                       x_hbm, dy_hbm, wg_ref, wu_ref, wd_ref, hs_ref,
                       dgu_ref, dgate_ref, sx, sdy, xt, dyt, gv, acc, sem,
                       *, tile: int):
    """Backward, a visit's hidden rows (launch ``moe_bwd_hidden``):
    ``g``, ``u`` again from the visit's tokens, ``dh`` from the same
    tokens' rows of ``dy`` over the transposed down block, and from
    them the gate's gradient (``h . dh`` before the gate), ``h`` and the
    gradients of ``g`` and ``u`` times the pair's gate, zero in the rows
    past the visit's count (the weight launch sums over rows)."""
    v, f = pl.program_id(0), pl.program_id(1)
    nf = hs_ref.shape[0]

    @pl.when(f == 0)
    def _():
        _gather_rows(v, tok_ref, base_ref, rows_ref,
                     ((x_hbm, sx, xt, sem.at[0]),
                      (dy_hbm, sdy, dyt, sem.at[1])), tile)
        gv[...] = jnp.zeros_like(gv)

        def put(r, c):
            gv[pl.ds(r, 1), :] = jnp.full(
                (1, gv.shape[1]), gate_ref[base_ref[v] + r], jnp.float32)
            return c

        jax.lax.fori_loop(0, rows_ref[v], put, 0)
        acc[...] = jnp.zeros_like(acc)

    g = jnp.dot(xt[...], wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(xt[...], wu_ref[...], preferred_element_type=jnp.float32)
    dh = jax.lax.dot_general(dyt[...], wd_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(g)
    a = g * s
    live = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < rows_ref[v]
    acc[...] += jnp.where(live, jnp.sum(a * u * dh, axis=1, keepdims=True),
                          0.0)
    dh = dh * gv[:, :1]
    hs_ref[f] = jnp.where(live, a * u * gv[:, :1], 0.0).astype(hs_ref.dtype)
    dgu_ref[f] = jnp.where(live, dh * u * (s + a * (1.0 - s)),
                           0.0).astype(dgu_ref.dtype)
    dgu_ref[nf + f] = jnp.where(live, dh * a, 0.0).astype(dgu_ref.dtype)

    @pl.when(f == nf - 1)
    def _():
        dgate_ref[...] = acc[...]


def _bwd_weights_kernel(expert_ref, base_ref, rows_ref, tok_ref, x_hbm,
                        dy_hbm, hs_ref, dg_ref, du_ref, dwg_ref, dwu_ref,
                        dwd_ref, sx, sdy, xt, dyt, sem, *, tile: int):
    """Backward, the banks (launch ``moe_bwd_weights``): grid ``(F / bf,
    visits)``; an expert's blocks of the three gradients stay in VMEM
    while its visits pass (they follow each other) and each adds its
    rows' product; a block leaves the chip once an expert."""
    v = pl.program_id(1)
    _gather_rows(v, tok_ref, base_ref, rows_ref,
                 ((x_hbm, sx, xt, sem.at[0]), (dy_hbm, sdy, dyt, sem.at[1])),
                 tile)

    @pl.when((v == 0) | (expert_ref[v] != expert_ref[jnp.maximum(v - 1, 0)]))
    def _():
        dwg_ref[...] = jnp.zeros_like(dwg_ref)
        dwu_ref[...] = jnp.zeros_like(dwu_ref)
        dwd_ref[...] = jnp.zeros_like(dwd_ref)

    # rows past the visit's count hold whatever the tile held
    live = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < rows_ref[v]
    x = jnp.where(live, xt[...], 0)
    dy = jnp.where(live, dyt[...], 0)
    over_rows = (((0,), (0,)), ((), ()))
    dwg_ref[...] += jax.lax.dot_general(
        x, dg_ref[...], over_rows, preferred_element_type=jnp.float32)
    dwu_ref[...] += jax.lax.dot_general(
        x, du_ref[...], over_rows, preferred_element_type=jnp.float32)
    dwd_ref[...] += jax.lax.dot_general(
        hs_ref[...], dy, over_rows, preferred_element_type=jnp.float32)


class _Plan:
    """The launches' static numbers for one set of shapes."""

    def __init__(self, x, w_gate, tile: int, interpret: bool):
        self.N, self.D = x.shape
        self.E_l, _, self.F = w_gate.shape
        self.tile, self.interpret = tile, interpret
        if not interpret and not supports(self.D, self.F, tile):
            raise ValueError(
                f"the routed experts' launches take d_model in whole 1024s, "
                f"an expert width in whole 128s and expert_tile in whole "
                f"16s on a TPU: got {self.D}, {self.F}, {tile}")
        self.dt = w_gate.dtype
        self.size = jnp.dtype(self.dt).itemsize
        self.bf = _block(self.F, self.D, 2, self.size)
        self.nf = self.F // self.bf
        # a token's row travels as whole float32 sublanes of 128 lanes
        self.lanes = 128 if self.D % 128 == 0 else self.D
        self.params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)

    def staging(self, n: int = 1):
        """Scratch of ``n`` gathered sources: the rows as they arrive,
        the tiles in the compute dtype, a semaphore each."""
        return ([pltpu.VMEM((self.tile * (self.D // self.lanes), self.lanes),
                            jnp.float32)] * n
                + [pltpu.VMEM((self.tile, self.D), self.dt)] * n)


def _gate_up(plan, scalars, n, x, w_gate, w_up):
    D, bf, nf, tile = plan.D, plan.bf, plan.nf, plan.tile
    return pl.pallas_call(
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
            scratch_shapes=plan.staging() + [pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=_struct((nf, scalars[0].shape[0] * tile, bf), plan.dt, x),
        interpret=plan.interpret,
        compiler_params=plan.params,
        name="moe_gate_up",
    )(*scalars, _rows_f32(x, plan.lanes), w_gate, w_up)


def _down(plan, scalars, n, gate, h, bank, like):
    """``h [nh, rows, bf]`` times ``bank [E_l, nh * bf, D]`` by visit,
    each row times its pair's gate added into its token's row."""
    N, D, bf, tile = plan.N, plan.D, plan.bf, plan.tile
    nh = h.shape[0]
    bd = _result_block(D, nh * bf, N, plan.size)
    return pl.pallas_call(
        _down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(D // bd, n),
            in_specs=[
                pl.BlockSpec((nh, tile, bf), lambda d, v, *_: (0, v, 0)),
                pl.BlockSpec((None, nh, bf, bd), lambda d, v, e, *_:
                             (e[v], 0, 0, d)),
            ],
            out_specs=pl.BlockSpec((N, bd), lambda d, v, *_: (0, d)),
            scratch_shapes=[pltpu.VMEM((tile, bd), jnp.float32)],
        ),
        out_shape=_struct((N, D), jnp.float32, like),
        interpret=plan.interpret,
        compiler_params=plan.params,
        name="moe_down",
    )(*scalars, gate, h, bank.reshape(plan.E_l, nh, bf, D))


def _walk(sizes, tok, tile: int):
    expert, base, rows, n = visits(sizes, tile, tok.shape[0])
    # a layer that was sent nothing still zeroes its result: one visit
    # of no rows
    return (expert, base, rows, tok), jnp.maximum(n, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _grouped(x, tok, gate, sizes, w_gate, w_up, w_down, tile, interpret):
    plan = _Plan(x, w_gate, tile, interpret)
    scalars, n = _walk(sizes, tok, tile)
    h = _gate_up(plan, scalars, n, x, w_gate, w_up)
    return _down(plan, scalars, n, gate, h, w_down, x)


def _grouped_fwd(x, tok, gate, sizes, w_gate, w_up, w_down, tile, interpret):
    y = _grouped(x, tok, gate, sizes, w_gate, w_up, w_down, tile, interpret)
    return y, (x, tok, gate, sizes, w_gate, w_up, w_down)


def _grouped_bwd(tile, interpret, res, dy):
    """Three more launches over the forward's walk: the visits' hidden
    rows again with their gradients (``moe_bwd_hidden``), the tokens'
    gradient as the forward's down launch over the transposed gate and
    up banks, and the banks' gradient an expert over that expert's rows
    (``moe_bwd_weights``). ``h`` is not kept: the forward's ``[nf,
    visits * tile, bf]`` is as large as the static pairs."""
    x, tok, gate, sizes, w_gate, w_up, w_down = res
    plan = _Plan(x, w_gate, tile, interpret)
    D, F, E_l = plan.D, plan.F, plan.E_l
    bf, nf, dt = plan.bf, plan.nf, plan.dt
    P = tok.shape[0]
    scalars, n = _walk(sizes, tok, tile)
    V = scalars[0].shape[0]
    xr, dyr = _rows_f32(x, plan.lanes), _rows_f32(dy, plan.lanes)
    sems = [pltpu.SemaphoreType.DMA((2,))]
    by_visit = lambda v, f, *_: (0, v, 0)  # noqa: E731
    hs, dgu, dgate_v = pl.pallas_call(
        functools.partial(_bwd_hidden_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n, nf),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec((None, D, bf), lambda v, f, e, *_: (e[v], 0, f)),
                pl.BlockSpec((None, D, bf), lambda v, f, e, *_: (e[v], 0, f)),
                pl.BlockSpec((None, None, bf, D), lambda v, f, e, *_:
                             (e[v], f, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((nf, tile, bf), by_visit),
                pl.BlockSpec((2 * nf, tile, bf), by_visit),
                pl.BlockSpec((tile, 128), lambda v, f, *_: (v, 0)),
            ],
            scratch_shapes=plan.staging(2) + [
                pltpu.VMEM((tile, 128), jnp.float32),
                pltpu.VMEM((tile, 128), jnp.float32)] + sems,
        ),
        out_shape=[_struct((nf, V * tile, bf), dt, x),
                   _struct((2 * nf, V * tile, bf), dt, x),
                   _struct((V * tile, 128), jnp.float32, x)],
        interpret=interpret,
        compiler_params=plan.params,
        name="moe_bwd_hidden",
    )(*scalars, gate, xr, dyr, w_gate, w_up, w_down.reshape(E_l, nf, bf, D))

    # the tokens' gradient: the rows of [dg | du] over [W_gate | W_up]
    # transposed, combined as the forward combines (the gate is in them)
    dx = _down(plan, scalars, n, jnp.ones_like(gate), dgu,
               jnp.swapaxes(jnp.concatenate([w_gate, w_up], axis=2), 1, 2),
               x)

    dwg, dwu, dwd = pl.pallas_call(
        functools.partial(_bwd_weights_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nf, n),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec((None, tile, bf), lambda f, v, *_: (f, v, 0)),
                pl.BlockSpec((None, tile, bf), lambda f, v, *_: (f, v, 0)),
                pl.BlockSpec((None, tile, bf), lambda f, v, *_:
                             (nf + f, v, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, D, bf), lambda f, v, e, *_: (e[v], 0, f)),
                pl.BlockSpec((None, D, bf), lambda f, v, e, *_: (e[v], 0, f)),
                pl.BlockSpec((None, None, bf, D), lambda f, v, e, *_:
                             (e[v], f, 0, 0)),
            ],
            scratch_shapes=plan.staging(2) + sems,
        ),
        out_shape=[_struct((E_l, D, F), jnp.float32, x),
                   _struct((E_l, D, F), jnp.float32, x),
                   _struct((E_l, nf, bf, D), jnp.float32, x)],
        interpret=interpret,
        compiler_params=plan.params,
        name="moe_bwd_weights",
    )(*scalars, xr, dyr, hs, dgu, dgu)

    # an expert sent no row was never visited: its blocks hold nothing
    sent = (sizes > 0)[:, None, None]
    banks = tuple(jnp.where(sent, g.reshape(E_l, *w.shape[1:]), 0).astype(dt)
                  for g, w in ((dwg, w_gate), (dwu, w_up), (dwd, w_down)))
    # the gate's gradient lies by visit, an expert's run padded to whole
    # tiles: back to the sorted pairs' order, a shift an expert
    count = (sizes + tile - 1) // tile
    start = jnp.cumsum(sizes) - sizes
    shift = (jnp.cumsum(count) - count) * tile - start
    r = jnp.arange(P, dtype=jnp.int32)
    dgate = jnp.zeros((P,), jnp.float32)
    for e in range(E_l):
        mine = (start[e] <= r) & (r < start[e] + sizes[e])
        dgate = jnp.where(mine, jax.lax.dynamic_slice_in_dim(
            dgate_v[:, 0], shift[e], P), dgate)
    return (dx.astype(x.dtype), None, dgate, None) + banks


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_experts(x, tok, gate, sizes, w_gate, w_up, w_down, *,
                    tile: int, interpret: bool):
    """``y [N, D]`` float32: for the sorted pair ``r`` of expert ``e``
    (``sizes [E_l]`` rows an expert, runs packed from row 0), ``gate[r]
    * w_down[e](silu(w_gate[e] x[tok[r]]) * w_up[e] x[tok[r]])`` added
    into row ``tok[r]``. ``x [N, D]`` in the compute dtype (that of the
    banks ``[E_l, D, F]``, ``[E_l, F, D]``); ``tok [P]`` int32 and
    ``gate [P]`` float32 are read below ``sum(sizes)`` only. Under
    its own ``jit``: the expert layers of a model call it with one set of shapes, so the kernels
    are traced and lowered once a program. Differentiable in ``x``,
    ``gate`` and the banks (:func:`_grouped_bwd`)."""
    # inside the train step's shard_map the banks are replicated and the
    # tokens are not; a launch takes operands that vary alike
    w_gate, w_up, w_down = (_vary_like(w, x) for w in (w_gate, w_up, w_down))
    return _grouped(x, tok, gate, sizes, w_gate, w_up, w_down, tile,
                    interpret)
