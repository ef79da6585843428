"""Pallas TPU attention over the serving engine's per-row KV cache, for
every query width a tick has: the chunk attend of a mixed tick
(``T`` = the prefill chunk) and the decode attend (``T == 1``). One
kernel, one walk: each row's K/V tiles up to the row's cursor, and
nothing past it.

The dense attend (``CausalSelfAttention._cached_attend``) scores each
row's ``T`` new tokens against all ``L`` cache positions under a mask.
A serving tick is bound by bytes, not FLOPs: the slot cache of 16 rows
of 2048 positions of a 1.3 B model is 6.4 GB, a tick's weights 5.7 GB,
and chat rows hold a fifth of their 2048 positions. Reading the masked
four fifths costs a decode tick (one token a row, no masked *half* to
speak of in FLOPs) as much as a chunk tick. So:

- **The fetch is bounded, not only the arithmetic.** The KV axis is
  tiled into blocks, and the grid is the walk itself: one dimension of
  as many steps as the call's rows hold tiles, row 0's tiles in order,
  then row 1's (:func:`kv_schedule`, computed from the per-row cursors
  and scalar-prefetched; the grid's size is a device scalar). A row's
  walk ends at the tile that holds its last valid query position
  (:func:`walk_tiles`), so a tile past a row's cursor is neither
  copied in (2.6 us at 2 MB on a v5e) nor costs a grid step (0.45 us).
  :func:`fetched_positions` is the same arithmetic on the host (the
  engine's ``key_positions_fetched``).
- **Rows say how many of their queries are real** (``valid_lens``, the
  mixed tick's contract): a decoding row in a chunk-wide tick is scored
  as one sublane of query rows and walks to its one token's tile, a
  row with no valid token gets one tile and no arithmetic.
- **the causal mask is applied per tile** from the same absolute
  positions the dense reference uses (row ``t`` of batch ``b`` sits at
  ``starts[b] + t`` and sees key positions ``<= that``), so the math
  matches the reference;
- **GQA is grouped natively**: queries arrive per KV head as a
  ``[T*G, hd]`` tile, one MXU matmul per KV block covers the whole
  group without repeating K/V. The tile is padded with zero rows to a
  sublane (8), so ``T*G`` of 1 (an MHA decode step), 3 or 12 tile like
  any chunk; the padding rows' outputs are dropped;
- **online softmax over KV blocks** (the same f32 running max/sum
  state as :mod:`distkeras_tpu.ops.pallas_attention`), the heads of a
  tile in two phases (all score matmuls, one stacked softmax update,
  all value matmuls) so that the independent matmuls overlap.

It consumes the contiguous per-row ``[B, L, Hk, hd]`` K/V view both
serving cache layouts already produce — the slot path's cache leaves
directly, the paged path's gathered view (there the bound saves the
kernel's reads, not the gather's) — so ONE kernel serves both,
selected by ``prefill_kernel='auto'|'splash'|'gather'`` on
:class:`~distkeras_tpu.models.transformer.CausalSelfAttention`. The
decode step launches under its own name (``slot_decode_attend``; a
chunk is ``splash_prefill``) so a profile tells them apart. The dense
attend stays the bit-parity reference; interpret mode off-TPU lets CPU
CI run the identical program for the parity suite
(tests/test_splash_prefill.py), while :func:`preferred` keeps 'auto'
on the reference everywhere the shape would mis-tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.paged_attention import VMEM_BUDGET, vmem_bytes

_NEG_INF = -1e30

# KV-axis tile: the largest power-of-two block up to 256 that divides L
# (real-TPU auto-select additionally requires L % 128 == 0 so the tile
# is lane-aligned; interpret mode runs whatever divides). 512 compiles
# too, but the chip compiler's time grows faster than the tile: the
# static walk over its heads and rows unrolls (v5e, H8/Hk2/hd256, T64:
# 1.2 s at 128, 3.3 s at 256, 12 s at 512), and at 512 a tile takes
# longer than two of 256 (v5e, H16/Hk16/hd128: 6.4 us against 3.7 at
# T1).
_KV_BLOCKS = (256, 128, 64, 32, 16, 8, 4, 2, 1)


def _interpret() -> bool:
    """Interpret mode off-TPU (CPU parity tests run the same program)."""
    return jax.default_backend() != "tpu"


def choose_kv_block(L: int) -> int:
    """KV tile the kernel would run at for a cache of length ``L``."""
    for b in _KV_BLOCKS:
        if L % b == 0:
            return b
    return L


_SUBLANE = 8


def _query_rows(T: int, G: int) -> int:
    """Rows of the per-KV-head query tile: ``T * G`` rounded up to a
    sublane. The wrapper pads with zero rows and drops their outputs,
    so a decode step (``T == 1``) and a ragged group tile like any
    chunk."""
    return -(-(T * G) // _SUBLANE) * _SUBLANE


def _vmem_bytes(T: int, G: int, Hk: int, hd: int, kb: int) -> int:
    """Upper bound on one program's VMEM: the pipelined buffers and
    scratch paged_attention.vmem_bytes counts, and the stacked
    ``[Hk, rows, kb]`` scores and probabilities the two-phase head walk
    holds at once (8 bytes a score; the compiler asked for 6.4 to 7.7
    over shapes near the limit)."""
    rows = _query_rows(T, G)
    return vmem_bytes(rows, 1, Hk, hd, kb) + 8 * Hk * rows * kb


def supports(T: int, G: int, hd: int, L: int, Hk: int = 1) -> bool:
    """Shapes 'auto' sends to the kernel on a TPU, every one of which
    the chip's compiler accepts (tests/test_chip_compile.py holds this
    gate to the compiler for a described v5e): lane-aligned head dim, a
    lane-aligned KV tile, and buffers that fit the scoped VMEM limit
    (the tiles hold all ``Hk`` local KV heads). Every query width
    qualifies, one decode token included: the query tile is padded to
    a sublane. Anything else keeps the dense reference — conservative,
    never a mis-tile. Interpret mode (tests) may run any shape by
    forcing ``prefill_kernel='splash'``."""
    return (hd % 128 == 0 and L % 128 == 0
            and _vmem_bytes(T, G, Hk, hd, choose_kv_block(L))
            <= VMEM_BUDGET)


def preferred(T: int, G: int, hd: int, L: int, Hk: int = 1) -> bool:
    """THE auto-select predicate (``prefill_kernel='auto'``): TPU
    backend and a supported shape — mirrors paged_attention.preferred
    so the engine's configured kernel label can't drift from what
    ran."""
    if jax.default_backend() != "tpu":
        return False
    return supports(T, G, hd, L, Hk)


def resolves_to_kernel(mode: str, T: int, G: int, hd: int, L: int,
                       Hk: int = 1) -> bool:
    """What ``prefill_kernel=mode`` means for one call shape: 'gather'
    keeps the dense attend at every ``T``, 'splash' forces the kernel
    at every ``T`` (interpret mode off the chip), 'auto' asks
    :func:`preferred`. The attention module and the engine's
    ``key_positions_fetched`` count both resolve through here."""
    if mode == "gather":
        return False
    return mode == "splash" or preferred(T, G, hd, L, Hk)


def walk_tiles(starts, lens, kb: int, nkv: int):
    """KV tiles each row's walk reads: those up to the one that holds
    the row's last valid query position ``starts + lens - 1``, capped
    at the cache; one tile (fetched, not scored) for a row with no
    valid token. One definition for the device (:func:`kv_schedule`,
    the kernel's finalize step) and the host
    (:func:`fetched_positions`): works on jax and numpy integers."""
    xp = jnp if isinstance(starts, jax.Array) else np
    last = xp.minimum((starts + lens - 1) // kb, nkv - 1)
    return xp.where(lens > 0, last, 0) + 1


def kv_schedule(starts, lens, kb: int, nkv: int):
    """The walk of a whole call, flattened: ``(row, tile, steps)`` with
    ``row[t]``, ``tile[t]`` what grid step ``t < steps`` works on — row
    0's tiles 0, 1, .. in order, then row 1's — and the last step
    repeated from ``steps`` on (the arrays have the static length ``B *
    nkv``; the grid itself has ``steps`` steps). Scalar-prefetched, so
    the grid burns no step and the pipeline issues no copy for a tile
    past a row's cursor."""
    tiles = walk_tiles(starts, lens, kb, nkv)
    ends = jnp.cumsum(tiles)
    t = jnp.minimum(jnp.arange(starts.shape[0] * nkv), ends[-1] - 1)
    row = jnp.searchsorted(ends, t, side="right", method="compare_all")
    tile = t - (ends - tiles)[row]
    return (row.astype(jnp.int32), tile.astype(jnp.int32),
            ends[-1].astype(jnp.int32))


def q_index(t, row_ref, tile_ref, starts_ref, lens_ref):
    """Query / output block of grid step ``t``: its row's whole tile,
    resident across that row's walk."""
    return (row_ref[t], 0, 0, 0)


def kv_index(t, row_ref, tile_ref, starts_ref, lens_ref):
    """K / V block of grid step ``t``: the scheduled tile of the
    scheduled row, and nothing else is ever named."""
    return (row_ref[t], tile_ref[t], 0, 0)


def fetched_positions(starts, lens, L: int) -> int:
    """K/V positions one call copies in for rows at cursors ``starts``
    with ``lens`` valid tokens each (host integers; ``lens`` may be the
    call's ``T``): every row's walk in whole tiles."""
    kb = choose_kv_block(L)
    return int(walk_tiles(np.asarray(starts, np.int64), np.asarray(lens),
                          kb, L // kb).sum()) * kb


def _kernel(row_ref, tile_ref, starts_ref, lens_ref, q_ref, k_ref, v_ref,
            o_ref, acc, m_s, l_s, *, kb: int, G: int, nkv: int,
            scale: float):
    """One step of the flattened walk: score one KV tile of one row —
    every local KV head of it (the block takes the whole head axis;
    Mosaic admits a second-minor block dim only when it is the array's
    own or a multiple of 8) — into the online-softmax state; initialize
    on the row's first tile, finalize on its last."""
    t = pl.program_id(0)
    b, j = row_ref[t], tile_ref[t]
    start, n = starts_ref[b], lens_ref[b]
    Hk, rows = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def update(nr: int):
        """Score the tile for the first ``nr`` query rows."""
        # query row r = t * G + g sits at absolute position start + t;
        # key slot i of tile j is absolute position j * kb + i — the
        # gathered reference's mask, tile-local. Rows past the valid
        # tokens (a chunk's padding, the zero rows that pad the tile to
        # a sublane) are scored like any other or not at all: nobody
        # reads them
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (nr, 1), 0) // G
        kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        visible = kpos <= qpos
        # two phases over the heads, not one chain a head: all the
        # score matmuls, one softmax update over the stacked heads, all
        # the value matmuls. The scheduler overlaps the independent
        # matmuls; a per-head chain through m_s/l_s/acc took twice the
        # time a tile at T == 1 (v5e: 7.3 us against 3.7; the tile's
        # copy is 2.6)
        s = jnp.stack([
            jax.lax.dot_general(
                q_ref[0, h, :nr], k_ref[0, :, h, :],  # [nr,hd] x [kb,hd]
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) for h in range(Hk)
        ]) * scale  # [Hk, nr, kb]
        s = jnp.where(visible[None], s, _NEG_INF)
        m_old = m_s[:, :nr]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_s[:, :nr] = l_s[:, :nr] * corr + jnp.sum(p, axis=-1,
                                                   keepdims=True)
        m_s[:, :nr] = m_new
        p = p.astype(v_ref.dtype)
        acc[:, :nr] = acc[:, :nr] * corr + jnp.stack([
            jax.lax.dot_general(
                p[h], v_ref[0, :, h, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) for h in range(Hk)
        ])

    # a decoding row of a mixed tick has one valid token in a
    # chunk-wide tile: score a sublane of query rows, not the chunk
    # (v5e, 64-token chunk: 3.7 us a tile against 5.3); a row with no
    # valid token scores nothing
    few = n * G <= _SUBLANE
    pl.when(jnp.logical_and(n > 0, few))(
        lambda: update(min(rows, _SUBLANE)))
    if rows > _SUBLANE:
        pl.when(jnp.logical_not(few))(lambda: update(rows))

    @pl.when(j == walk_tiles(start, n, kb, nkv) - 1)
    def _():
        # position 0 is visible to every valid query, so l > 0 there;
        # rows nobody scored divide zero by the floor
        o_ref[0] = (acc[:] / jnp.maximum(l_s[:], 1e-30)).astype(
            o_ref.dtype)


def splash_prefill_attention(q, keys, vals, starts, valid_lens=None):
    """Causal attention of ``T`` new tokens a row over a contiguous
    per-row KV view, reading each row's K/V up to its cursor only.

    Args:
      q: ``[B, T, H, hd]`` queries (rope already applied, unscaled) —
        T is the prefill chunk width, or 1 for a decode step.
      keys / vals: ``[B, L, Hk, hd]`` per-row K/V in compute dtype (the
        slot cache leaves, or the paged path's gathered — and, under
        int8, already dequantized — view; this call's tokens are
        already written at their positions).
      starts: ``[B]`` int32 — row ``b``'s query ``t`` sits at absolute
        position ``starts[b] + t`` and attends key positions
        ``<= that``.
      valid_lens: ``[B]`` int32 or None — row ``b``'s first
        ``valid_lens[b]`` queries are real (the mixed tick's contract);
        the outputs of the rest are unspecified (finite), as are the
        dense attend's (they attend positions nobody wrote). None: all
        ``T`` are.

    Returns ``[B, T, H, hd]`` in ``q.dtype`` — on the valid queries the
    same contract as the dense masked attend in
    ``CausalSelfAttention``, which stays the bit-parity reference.
    """
    if valid_lens is None:
        valid_lens = jnp.full(q.shape[:1], q.shape[1], jnp.int32)
    return _attend(q, keys, vals, starts.astype(jnp.int32),
                   valid_lens.astype(jnp.int32), interpret=_interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _attend(q, keys, vals, starts, lens, *, interpret: bool):
    """:func:`splash_prefill_attention` under its own ``jit``: the
    layers of a model call it with one set of shapes, so the kernel is
    traced and lowered once a program, not once a layer (24 layers of
    the 16-head walk: 4 s of every engine start-up otherwise)."""
    B, T, H, hd = q.shape
    _, L, Hk, _ = keys.shape
    if H % Hk:
        raise ValueError(f"H={H} not divisible by Hk={Hk}")
    G = H // Hk
    TG = T * G
    rows = _query_rows(T, G)
    kb = choose_kv_block(L)
    nkv = L // kb
    # queries per KV head: row r = t * G + g — one [T*G, hd] MXU tile
    # covers the whole GQA group without repeating K/V
    qr = q.reshape(B, T, Hk, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, Hk, TG, hd)
    if rows != TG:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - TG), (0, 0)))
    row, tile, steps = kv_schedule(starts, lens, kb, nkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, Hk, rows, hd), q_index),
            pl.BlockSpec((1, kb, Hk, hd), kv_index),
            pl.BlockSpec((1, kb, Hk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, Hk, rows, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((Hk, rows, hd), jnp.float32),
            pltpu.VMEM((Hk, rows, 1), jnp.float32),
            pltpu.VMEM((Hk, rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, kb=kb, G=G, nkv=nkv,
                          scale=1.0 / np.sqrt(hd)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, rows, hd), q.dtype,
                                       vma=jax.typeof(q).vma),
        interpret=interpret,
        # a decode step is the same walk under its own name, so a
        # profile tells the two apart
        name="slot_decode_attend" if T == 1 else "splash_prefill",
    )(row, tile, starts, lens, qr, keys, vals)
    return out[:, :, :TG].reshape(B, Hk, T, G, hd).transpose(
        0, 2, 1, 3, 4).reshape(B, T, H, hd)
