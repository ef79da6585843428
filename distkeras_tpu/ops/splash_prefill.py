"""Pallas TPU splash-style chunked-prefill attention for the serving
engine's mixed tick.

The chunked mixed tick attends each row's prompt chunk over the whole
cache with a dense masked einsum — a ``[B, T, L]`` score tensor whose
masked half (keys beyond the row's diagonal) is computed and thrown
away. That is the decode-friendly shape: T is 1 for decoding rows and
the waste is negligible. A PREFILL-specialized replica inverts the
ratio — T is the chunk size (hundreds of tokens) and L the full
context, so the dense attend wastes roughly half its FLOPs and
materializes the full score tensor in HBM.

This kernel is the splash-attention treatment of that shape (the
``make_splash_mha`` block/mask plumbing, grafted onto the serving
cache layout): the KV axis is tiled into blocks, per-row absolute
cursors arrive by scalar prefetch, and

- **beyond-diagonal KV blocks are skipped outright** (``pl.when`` on
  the block's first key position vs the row's last query position) —
  a chunk at the start of a long context touches a fraction of the
  blocks the dense attend streams;
- **the causal mask is applied per tile** from the same absolute
  positions the gathered reference uses (row ``t`` of batch ``b``
  sits at ``starts[b] + t`` and sees key positions ``<= that``), so
  the math — and the bits — match the reference exactly;
- **GQA is grouped natively**: queries arrive per KV head as a
  ``[T*G, hd]`` tile, one MXU matmul per KV block covers the whole
  group without repeating K/V;
- **online softmax over KV blocks** (the same f32 running max/sum
  state as :mod:`distkeras_tpu.ops.pallas_attention`).

It consumes the contiguous per-row ``[B, L, Hk, hd]`` K/V view both
serving cache layouts already produce — the slot path's cache leaves
directly, the paged path's gathered view — so ONE kernel serves both,
selected by ``prefill_kernel='auto'|'splash'|'gather'`` on
:class:`~distkeras_tpu.models.transformer.CausalSelfAttention` (threaded
through the engine exactly like ``paged_kernel`` was in PR 6). The
dense attend stays the bit-parity reference; interpret mode off-TPU
lets CPU CI run the identical program for the parity suite
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
# static per-head walk over its rows unrolls (v5e, H8/Hk2/hd256, T64:
# 1.2 s at 128, 3.3 s at 256, 12 s at 512).
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


def supports(T: int, G: int, hd: int, L: int, Hk: int = 1) -> bool:
    """Shapes 'auto' sends to the kernel on a TPU, every one of which
    the chip's compiler accepts (tests/test_chip_compile.py holds this
    gate to the compiler for a described v5e): a true chunk (T > 1 —
    one decode token is the dense attend's home turf), lane-aligned
    head dim, a sublane-aligned ``[T*G, hd]`` query tile, a
    lane-aligned KV tile, and buffers that fit the scoped VMEM limit
    (the tiles hold all ``Hk`` local KV heads; see
    paged_attention.vmem_bytes). Anything else keeps the dense
    reference — conservative, never a mis-tile. Interpret mode (tests)
    may run any shape by forcing ``prefill_kernel='splash'``."""
    return (T > 1 and hd % 128 == 0 and (T * G) % 8 == 0
            and L % 128 == 0
            and vmem_bytes(T, G, Hk, hd, choose_kv_block(L))
            <= VMEM_BUDGET)


def preferred(T: int, G: int, hd: int, L: int, Hk: int = 1) -> bool:
    """THE auto-select predicate (``prefill_kernel='auto'``): TPU
    backend and a supported shape — mirrors paged_attention.preferred
    so the engine's configured kernel label can't drift from what
    ran."""
    if jax.default_backend() != "tpu":
        return False
    return supports(T, G, hd, L, Hk)


def _kernel(starts_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s,
            *, kb: int, T: int, G: int, Hk: int, nkv: int, scale: float):
    """One (batch row, KV block) program: skip-or-score one KV tile —
    every local KV head of it, walked in a static loop (the block takes
    the whole head axis; Mosaic admits a second-minor block dim only
    when it is the array's own or a multiple of 8) — into the
    online-softmax state; finalize on the last tile."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    TG = T * G

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    start = starts_ref[b]

    # the splash skip: KV tiles wholly beyond this row's last query
    # position (start + T - 1) contribute nothing under the causal
    # mask — their program issues no compute at all
    @pl.when(j * kb <= start + T - 1)
    def _():
        # query row r = t * G + g sits at absolute position start + t;
        # key slot i of tile j is absolute position j * kb + i — the
        # gathered reference's mask, tile-local
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (TG, 1), 0) // G
        kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        visible = kpos <= qpos
        for h in range(Hk):
            q = q_ref[0, h]          # [TG, hd]
            kb_t = k_ref[0, :, h, :]  # [kb, hd] — one head of the tile
            vb_t = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, kb_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [TG, kb]
            s = jnp.where(visible, s, _NEG_INF)
            m_old = m_s[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_s[h] = m_new
            pv = jax.lax.dot_general(
                p.astype(vb_t.dtype), vb_t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc[h] = acc[h] * corr + pv

    @pl.when(j == nkv - 1)
    def _():
        # position 0 is visible to every real row, so l > 0; the
        # padding rows of a mixed tick normalize garbage nobody reads
        o_ref[0] = (acc[:] / jnp.maximum(l_s[:], 1e-30)).astype(
            o_ref.dtype)


def splash_prefill_attention(q, keys, vals, starts):
    """Chunked-prefill causal attention over a contiguous per-row KV
    view.

    Args:
      q: ``[B, T, H, hd]`` chunk queries (rope already applied,
        unscaled) — T is the prefill chunk width.
      keys / vals: ``[B, L, Hk, hd]`` per-row K/V in compute dtype (the
        slot cache leaves, or the paged path's gathered — and, under
        int8, already dequantized — view; this call's chunk is already
        written at its positions).
      starts: ``[B]`` int32 — row ``b``'s query ``t`` sits at absolute
        position ``starts[b] + t`` and attends key positions
        ``<= that``.

    Returns ``[B, T, H, hd]`` in ``q.dtype`` — the same contract as the
    dense masked attend in ``CausalSelfAttention``, which stays the
    bit-parity reference.
    """
    B, T, H, hd = q.shape
    _, L, Hk, _ = keys.shape
    if H % Hk:
        raise ValueError(f"H={H} not divisible by Hk={Hk}")
    G = H // Hk
    TG = T * G
    kb = choose_kv_block(L)
    nkv = L // kb
    # queries per KV head: row r = t * G + g — one [TG, hd] MXU tile
    # covers the whole GQA group without repeating K/V
    qr = q.reshape(B, T, Hk, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, Hk, TG, hd)

    kern = functools.partial(
        _kernel, kb=kb, T=T, G=G, Hk=Hk, nkv=nkv,
        scale=1.0 / np.sqrt(hd),
    )

    def q_idx(b, j, starts_):
        return (b, 0, 0, 0)

    def kv_idx(b, j, starts_):
        return (b, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nkv),
        in_specs=[
            pl.BlockSpec((1, Hk, TG, hd), q_idx),
            pl.BlockSpec((1, kb, Hk, hd), kv_idx),
            pl.BlockSpec((1, kb, Hk, hd), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, Hk, TG, hd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((Hk, TG, hd), jnp.float32),
            pltpu.VMEM((Hk, TG, 1), jnp.float32),
            pltpu.VMEM((Hk, TG, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, TG, hd), q.dtype,
                                       vma=jax.typeof(q).vma),
        interpret=_interpret(),
        name="splash_prefill",
    )(starts.astype(jnp.int32), qr, keys, vals)
    return out.reshape(B, Hk, T, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, T, H, hd)
