"""The two attends of a model whose layers differ in kind (``mimo_v2_lm``):
full layers attend a full-length slot cache through a Pallas kernel,
window layers attend a short ring in plain XLA. Keys are wider than
values (192 against 128 in the published model) and neither attend
pads one to the other.

**The full attend** (:func:`full_attention`) is the cursor-bounded walk
of :mod:`distkeras_tpu.ops.splash_prefill` — the same schedule
(:func:`~distkeras_tpu.ops.splash_prefill.kv_schedule`), the same
per-tile causal mask from absolute positions, the same online softmax —
for a cache whose leaves hold a position's KV heads side by side in the
minor axis: ``keys [B, L, Hk * dk]``, ``vals [B, L, Hk * dv]``. With 4
heads of 192 the minor axis is 768 = 6 x 128 lanes, so the pool has no
padding in HBM and a tile is one dense copy (the chip's default layout
of ``bf16[B, L, 4, 192]`` puts ``L`` minor: a position's keys are then
not contiguous, and a walk by position cannot read them); the kernel
takes a head's channels by a static slice of the tile. Query
tiles are ``[T * G, dk]`` a KV head (``G`` = 16 in the published
model: 1024 rows for a 64-token chunk), so the heads of a tile are
walked one after the other where a stacked softmax would not fit
(:func:`_stacked`), and stacked where it does (a decode step, a
decoding row of a mixed tick), as ``splash_prefill`` found faster. The
launches are named ``full_attend`` (a chunk) and ``full_decode_attend``
(``T == 1``).

**The window attend** (:func:`window_attention`) reads a ring of ``R``
positions a row (``R`` = 256 in the published model: window 128 + chunk
64 - 1, rounded up), position ``p`` at ``p % R``. What a ring entry
holds is decided by arithmetic on the row's cursor, never by whether
the entry was written: entry ``i`` holds the last position ``<=`` the
row's newest that is congruent to ``i``, and is attended only if that
position is ``>= 0`` and inside the query's window. A reused slot's ring
holds the last tenant's keys at entries the new request has not reached:
their positions come out negative and they are masked. The sink is one
more logit in the softmax's denominator with no value. 256 keys a row:
plain XLA under the scope ``window_attend``, no kernel and no roofline.

:func:`dense_attention` is the plain masked attend over whatever
positions it is given, the parity path of the tests (and of a model
built with ``attend_kernel="dense"``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.splash_prefill import (
    _NEG_INF, _query_rows, choose_kv_block, kv_index, kv_schedule, q_index,
    walk_tiles)

# the scoped VMEM the full attend asks for: a 64-token chunk of 16
# queries a KV head holds 1024-row tiles (queries, output, accumulator
# and the softmax's two columns: 11 MB at 4 heads) beside one head's
# scores. A v5e core has 128 MiB
VMEM_LIMIT = 48 * 1024 * 1024
# scores and probabilities of all heads at once, where they fit in this
_STACKED_BYTES = 2 * 1024 * 1024


def _interpret() -> bool:
    """Interpret mode off-TPU (CPU parity tests run the same program)."""
    return jax.default_backend() != "tpu"


def supports(T: int, G: int, dk: int, dv: int, L: int, Hk: int) -> bool:
    """Shapes the full attend's kernel runs on a TPU (tests/
    test_chip_compile.py holds the published ones to the compiler for a
    described v5e): each leaf's minor axis whole lanes, a lane-aligned
    KV tile, and buffers within :data:`VMEM_LIMIT`."""
    rows = _query_rows(T, G)
    kb = choose_kv_block(L)
    tiles = Hk * rows * (2 * 2 * (dk + dv) + 4 * dv + 2 * 128 * 4)
    return ((Hk * dk) % 128 == 0 and (Hk * dv) % 128 == 0 and L % 128 == 0
            and tiles + 4 * kb * Hk * (dk + dv) + 16 * rows * kb
            <= VMEM_LIMIT // 2)


def resolves_to_kernel(mode: str, T: int, G: int, dk: int, dv: int, L: int,
                       Hk: int) -> bool:
    """What ``attend_kernel=mode`` means for one call shape: 'dense'
    keeps the masked attend over all ``L``, 'pallas' forces the kernel
    (interpret mode off the chip), 'auto' takes it on a TPU where
    :func:`supports` says so."""
    if mode == "dense":
        return False
    return mode == "pallas" or (jax.default_backend() == "tpu"
                                and supports(T, G, dk, dv, L, Hk))


def _stacked(Hk: int, nr: int, kb: int) -> bool:
    return 8 * Hk * nr * kb <= _STACKED_BYTES


def _kernel(row_ref, tile_ref, starts_ref, lens_ref, q_ref, k_ref, v_ref,
            o_ref, acc, m_s, l_s, *, kb: int, G: int, nkv: int, dk: int,
            dv: int, scale: float):
    """One step of the flattened walk (``splash_prefill._kernel``'s, for
    heads that lie side by side in the tile's lanes and keys wider than
    values)."""
    t = pl.program_id(0)
    b, j = row_ref[t], tile_ref[t]
    start, n = starts_ref[b], lens_ref[b]
    Hk, rows = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def scores(h, nr, visible):
        s = jax.lax.dot_general(
            q_ref[0, h, :nr], k_ref[0, :, h * dk:(h + 1) * dk],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [nr, kb]
        return jnp.where(visible, s, _NEG_INF)

    def values(h, p):
        return jax.lax.dot_general(
            p, v_ref[0, :, h * dv:(h + 1) * dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [nr, dv]

    def update(nr: int):
        """Score the tile for the first ``nr`` query rows of every
        head: query row r = t * G + g sits at absolute position start +
        t, key slot i of tile j at j * kb + i."""
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (nr, 1), 0) // G
        kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        visible = kpos <= qpos

        def fold(at, s, weighted):
            """Fold the scores ``s`` of the heads ``at`` (one, or all of
            them stacked) into the online-softmax state."""
            m_old = m_s[at, :nr]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_s[at, :nr] = l_s[at, :nr] * corr + jnp.sum(p, axis=-1,
                                                         keepdims=True)
            m_s[at, :nr] = m_new
            acc[at, :nr] = acc[at, :nr] * corr + weighted(
                p.astype(v_ref.dtype))

        if _stacked(Hk, nr, kb):
            # all score matmuls, one softmax update, all value matmuls
            fold(slice(None),
                 jnp.stack([scores(h, nr, visible) for h in range(Hk)]),
                 lambda p: jnp.stack([values(h, p[h]) for h in range(Hk)]))
        else:  # a chunk's tile: one head's scores at a time
            for h in range(Hk):
                fold(h, scores(h, nr, visible),
                     lambda p, h=h: values(h, p))

    # a decoding row of a mixed tick has one valid token in a chunk-wide
    # tile: score its G query rows, not the chunk's; a row with no valid
    # token scores nothing
    few_rows = min(rows, _query_rows(1, G))
    few = n * G <= few_rows
    pl.when(jnp.logical_and(n > 0, few))(lambda: update(few_rows))
    if rows > few_rows:
        pl.when(jnp.logical_not(few))(lambda: update(rows))

    @pl.when(j == walk_tiles(start, n, kb, nkv) - 1)
    def _():
        # position 0 is visible to every valid query, so l > 0 there;
        # rows nobody scored divide zero by the floor
        o_ref[0] = (acc[:] / jnp.maximum(l_s[:], 1e-30)).astype(
            o_ref.dtype)


def full_attention(q, keys, vals, starts, valid_lens=None):
    """Causal attention of ``T`` new tokens a row over a full-length
    slot cache, reading each row's K/V up to its cursor only.

    ``q [B, T, H, dk]`` (rope applied, unscaled); ``keys [B, L, Hk *
    dk]`` and ``vals [B, L, Hk * dv]`` with this call's tokens already
    written; ``starts [B]``: query ``t`` of row ``b`` sits at ``starts[b]
    + t`` and attends positions ``<=`` that; ``valid_lens [B]`` or None
    (all ``T``): how many of a row's queries are real. Returns ``[B, T,
    H, dv]`` in ``q.dtype``; on the valid queries :func:`dense_attention`
    over the same leaves is the parity reference."""
    if valid_lens is None:
        valid_lens = jnp.full(q.shape[:1], q.shape[1], jnp.int32)
    return _full(q, keys, vals, starts.astype(jnp.int32),
                 valid_lens.astype(jnp.int32), interpret=_interpret())


@functools.partial(jax.jit, static_argnames="interpret")
def _full(q, keys, vals, starts, lens, *, interpret: bool):
    """Under its own ``jit``: the full layers of a model call it with
    one set of shapes, so the kernel is traced and lowered once a
    program."""
    B, T, H, dk = q.shape
    L = keys.shape[1]
    Hk = keys.shape[2] // dk
    dv = vals.shape[2] // Hk
    if H % Hk or keys.shape[2] != Hk * dk or vals.shape[2] != Hk * dv:
        raise ValueError(f"q {q.shape}, keys {keys.shape}, vals "
                         f"{vals.shape}: not H heads over Hk KV heads")
    G = H // Hk
    TG = T * G
    rows = _query_rows(T, G)
    kb = choose_kv_block(L)
    nkv = L // kb
    # queries per KV head: row r = t * G + g
    qr = q.reshape(B, T, Hk, G, dk).transpose(0, 2, 1, 3, 4).reshape(
        B, Hk, TG, dk)
    if rows != TG:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - TG), (0, 0)))
    row, tile, steps = kv_schedule(starts, lens, kb, nkv)

    def kv_block(t, row_ref, tile_ref, starts_ref, lens_ref):
        return kv_index(t, row_ref, tile_ref, starts_ref, lens_ref)[:3]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, Hk, rows, dk), q_index),
            pl.BlockSpec((1, kb, Hk * dk), kv_block),
            pl.BlockSpec((1, kb, Hk * dv), kv_block),
        ],
        out_specs=pl.BlockSpec((1, Hk, rows, dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((Hk, rows, dv), jnp.float32),
            pltpu.VMEM((Hk, rows, 1), jnp.float32),
            pltpu.VMEM((Hk, rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, kb=kb, G=G, nkv=nkv, dk=dk, dv=dv,
                          scale=1.0 / np.sqrt(dk)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, rows, dv), q.dtype,
                                       vma=jax.typeof(q).vma),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="full_decode_attend" if T == 1 else "full_attend",
    )(row, tile, starts, lens, qr, keys, vals)
    return out[:, :, :TG].reshape(B, Hk, T, G, dv).transpose(
        0, 2, 1, 3, 4).reshape(B, T, H, dv)


def dense_attention(q, keys, vals, key_pos, starts, window=None, sink=None):
    """The plain masked attend: ``q [B, T, H, dk]`` at positions
    ``starts[b] + t`` over ``keys [B, P, Hk, dk]`` / ``vals [B, P, Hk,
    dv]`` that hold the absolute positions ``key_pos [B, P]`` (negative:
    nothing of this request's). A query sees positions ``<=`` its own,
    with ``window`` only the last ``window`` of them; ``sink [H]`` adds
    each head's logit to the softmax's denominator. float32 scores;
    ``[B, T, H, dv]`` in ``q.dtype``."""
    B, T, H, dk = q.shape
    Hk = keys.shape[2]
    G = H // Hk
    qpos = starts[:, None] + jnp.arange(T)[None]  # [B, T]
    kp = key_pos[:, None, :]
    visible = (kp >= 0) & (kp <= qpos[..., None])
    if window is not None:
        visible &= kp > qpos[..., None] - window
    s = jnp.einsum("bqkgd,bpkd->bkgqp", q.reshape(B, T, Hk, G, dk), keys,
                   preferred_element_type=jnp.float32) / np.sqrt(dk)
    s = jnp.where(visible[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        b = sink.astype(jnp.float32).reshape(1, Hk, G, 1, 1)
        m = jnp.maximum(m, b)
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(b - m)
    # a query that sees nothing (a chunk's padding) divides zero by the
    # floor, or by its sink
    p = (e / jnp.maximum(denom, 1e-30)).astype(vals.dtype)
    out = jnp.einsum("bkgqp,bpkd->bqkgd", p, vals,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, vals.shape[-1]).astype(q.dtype)


def ring_positions(ends, R: int):
    """``[B, R]``: the absolute position ring entry ``i`` of each row
    holds once positions ``< ends[b]`` are written — the last one
    congruent to ``i``; negative where this request has written none
    there (what the entry holds then is another request's)."""
    newest = ends[:, None] - 1
    return newest - jnp.mod(newest - jnp.arange(R)[None], R)


@jax.named_scope("window_attend")
def window_attention(q, ring_k, ring_v, starts, valid_lens, sink,
                     window: int):
    """Sliding-window attention of ``T`` new tokens a row over the
    row's ring (``ring_k [B, R, Hk, dk]``, ``ring_v [B, R, Hk, dv]``,
    this call's tokens already written at ``position % R``): query ``t``
    at ``starts[b] + t`` attends the ``window`` positions up to its own
    that this request has written, and ``sink [H]``. Needs ``R >= window
    + T - 1``, so that no key a query of this call may see was
    overwritten by a later token of the same call."""
    T, R = q.shape[1], ring_k.shape[1]
    if R < window + T - 1:
        raise ValueError(f"a ring of {R} positions cannot hold a window of "
                         f"{window} behind {T} new tokens")
    fed = (jnp.full(q.shape[:1], T, jnp.int32) if valid_lens is None
           else valid_lens)
    return dense_attention(q, ring_k, ring_v,
                           ring_positions(starts + fed, R), starts,
                           window=window, sink=sink)
