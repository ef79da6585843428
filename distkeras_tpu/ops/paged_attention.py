"""Pallas TPU paged attention for the serving engine's block-pooled KV
cache.

The serving engine's gathered attend (``CausalSelfAttention._paged_attend``)
materializes each row's cache view with an XLA gather — ``cache[block_tables]``
— before a dense masked attend. That costs one full ``[B, L, Hk, hd]``
HBM round trip per tick per layer, and under int8 KV it *dequantizes the
whole gathered view* into model dtype first, doubling the stream it was
supposed to halve. This kernel consumes the pool directly:

- **Block tables drive the DMA.** The grid is ``(B, max_blocks)`` and
  the K/V ``in_specs`` index maps look the physical page up in the
  scalar-prefetched block table (``tables[b, j]``), so each program DMAs
  exactly one ``[block_size, Hk, hd]`` page out of the pool — every
  local KV head of it, because Mosaic admits a block whose second-minor
  dim is 1 only where the array's is; the heads are walked inside the
  program. No gathered intermediate exists in HBM or VMEM.
- **int8 dequant folded in.** Under ``cache_dtype='int8'`` the page
  arrives as int8 plus its ``[block_size]`` f32 scales and is dequantized
  in VMEM right before the matmul — the bf16/f32 K/V bytes never exist
  outside the compute tile, so the HBM stream is the quantized one.
- **GQA grouped natively.** Queries arrive per KV head as a
  ``[T*G, hd]`` tile (``G`` = query heads per KV head), so the MXU matmul
  covers the whole group without repeating K/V.
- **Online softmax over pages** (same f32 running max/sum state as
  :mod:`distkeras_tpu.ops.pallas_attention`), with the per-row absolute
  positions from ``seq_lens`` masking exactly like the gathered attend:
  row ``t`` of batch ``b`` sees positions ``<= seq_lens[b] + t``. Pages
  wholly beyond a row's last query position are skipped with ``pl.when``
  (their index map still clamps into the table, so the pipeline fetches
  the trash page at worst).

The kernel is the serving twin of the training-side kernels: forward
only (decode never differentiates), per-page DMA (no ``[B, L]`` VMEM
residency), interpret mode off-TPU so CPU test meshes run the identical
program. Parity vs the gathered reference — MHA/GQA x int8 on/off x
decode/chunk shapes — is asserted by tests/test_paged_kernel.py.

Auto-select (:func:`preferred`) is deliberately narrow: lane-aligned
``hd`` (% 128), a sublane-aligned query tile (``T*G % 8``), a
sublane-aligned page size for the stored dtype, and tiles that fit the
scoped VMEM limit — shapes outside that (e.g. single-token MHA decode,
tiny test models, a 1024-token chunk of a wide model) keep the gathered
path, which remains the bit-parity reference. Every shape the gate
admits is compiled for a described v5e by tests/test_chip_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret() -> bool:
    """Interpret mode off-TPU (CPU test meshes run the same program)."""
    return jax.default_backend() != "tpu"


# what one program's VMEM buffers may add up to: the chip's default
# scoped-VMEM limit (v5e: 16 MiB; the compiler refuses a kernel over it)
VMEM_BUDGET = 16 * 1024 * 1024


def vmem_bytes(T: int, G: int, Hk: int, hd: int, kv_rows: int,
               kv_itemsize: int = 2) -> int:
    """Upper bound on one program's VMEM: double-buffered ``[Hk, T*G,
    hd]`` bf16 query and output tiles (the dtype every TPU path of the
    repo computes in), the f32 accumulator, the two running softmax
    columns (a ``[.., 1]`` scratch occupies a whole 128-lane tile row)
    and double-buffered K and V tiles of ``kv_rows`` rows. Shared with
    splash_prefill, whose kernel has the same buffers."""
    rows = Hk * T * G
    return (rows * hd * (4 * 2 + 4) + 2 * rows * 128 * 4
            + 4 * kv_rows * Hk * hd * kv_itemsize)


def supports(T: int, G: int, hd: int, block_size: int,
             store_itemsize: int = 2, Hk: int = 1) -> bool:
    """Shapes 'auto' sends to the kernel on a TPU, every one of which the
    chip's compiler accepts (tests/test_chip_compile.py holds this gate
    to the compiler for a described v5e): lane-aligned head dim, a
    sublane-aligned ``[T*G, hd]`` query tile, pages whose token axis is
    sublane-aligned for the stored KV dtype (int8 pages want 32-row
    blocks), and buffers that fit the scoped VMEM limit — the tiles hold
    all ``Hk`` local KV heads, so a long chunk of a wide model does not
    fit and the compiler refuses it. Everything else falls back to the
    gathered attend — conservative, never a mis-tile. Interpret mode
    (tests) may run any shape by forcing ``paged_kernel='pallas'``."""
    sublane = 32 // store_itemsize
    return (hd % 128 == 0 and (T * G) % 8 == 0
            and block_size % sublane == 0
            and vmem_bytes(T, G, Hk, hd, block_size,
                           store_itemsize) <= VMEM_BUDGET)


def preferred(T: int, G: int, hd: int, block_size: int,
              store_itemsize: int = 2, Hk: int = 1) -> bool:
    """THE auto-select predicate (``paged_kernel='auto'``): TPU backend
    and a supported shape. Mirrors pallas_attention.preferred so the
    engine's recorded kernel label can't drift from what ran."""
    if jax.default_backend() != "tpu":
        return False
    return supports(T, G, hd, block_size, store_itemsize, Hk)


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
            bs: int, T: int, G: int, Hk: int, nb: int, scale: float,
            quant: bool, compute_dtype):
    """One (batch row, page) program: the DMA'd page holds every local KV
    head (the block takes the whole head axis — Mosaic admits a
    second-minor block dim only when it is the array's own or a multiple
    of 8); heads are walked in a static loop: dequant -> grouped score
    tile -> online-softmax accumulate, finalize on the last page.
    ``rest`` is (ks, vs, o, acc, m, l) when quant else (o, acc, m, l)."""
    if quant:
        ks_ref, vs_ref, o_ref, acc, m_s, l_s = rest
    else:
        o_ref, acc, m_s, l_s = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    TG = T * G

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    start = lens_ref[b]

    # pages wholly beyond this row's last query position do no work (the
    # causal bound of an append-only cache); their table entry is 0, so
    # the pipeline at worst re-fetches the trash page
    @pl.when(j * bs <= start + T - 1)
    def _():
        # query row r = t * G + g sits at absolute position start + t;
        # key slot i of page j is absolute position j * bs + i
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (TG, 1), 0) // G
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        visible = kpos <= qpos
        for h in range(Hk):
            q = q_ref[0, h]  # [TG, hd]
            kb = k_ref[0, :, h, :]  # [bs, hd] — one KV head of the page
            vb = v_ref[0, :, h, :]
            if quant:
                # dequant IN VMEM: the bf16/f32 K/V bytes never exist
                # outside this tile (the gathered path materialized the
                # whole dequantized view in HBM first)
                kb = (kb.astype(jnp.float32)
                      * ks_ref[0, :, h][:, None]).astype(compute_dtype)
                vb = (vb.astype(jnp.float32)
                      * vs_ref[0, :, h][:, None]).astype(compute_dtype)
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [TG, bs]
            s = jnp.where(visible, s, _NEG_INF)
            m_old = m_s[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_s[h] = m_new
            pv = jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc[h] = acc[h] * corr + pv

    @pl.when(j == nb - 1)
    def _():
        # position 0 is always visible to every real row, so l > 0;
        # padding rows of a chunked tick normalize garbage nobody reads
        o_ref[0] = (acc[:] / jnp.maximum(l_s[:], 1e-30)).astype(
            o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    key_scales=None, value_scales=None):
    """Paged causal attention over a block-pooled KV cache.

    Args:
      q: ``[B, T, H, hd]`` queries (rope already applied, unscaled).
      k_pages / v_pages: ``[num_pages, block_size, Hk, hd]`` pool, model
        dtype or int8 (then pass the scales).
      block_tables: ``[B, max_blocks]`` int32 physical page ids per row
        (entries past a row's chain point at the reserved trash page 0).
      seq_lens: ``[B]`` int32 — row ``b``'s query ``t`` sits at absolute
        position ``seq_lens[b] + t`` and attends positions ``<= that``.
      key_scales / value_scales: ``[num_pages, block_size, Hk]`` f32
        dequant scales for int8 pools (both or neither).

    Returns ``[B, T, H, hd]`` in ``q.dtype`` — same contract as the
    gathered attend in ``CausalSelfAttention._paged_attend``, which stays
    the bit-parity reference.
    """
    B, T, H, hd = q.shape
    _, bs, Hk, _ = k_pages.shape
    if H % Hk:
        raise ValueError(f"H={H} not divisible by Hk={Hk}")
    quant = key_scales is not None
    if quant != (value_scales is not None):
        raise ValueError("pass both key_scales and value_scales or neither")
    G = H // Hk
    NB = block_tables.shape[-1]
    TG = T * G
    # queries per KV head: row r = t * G + g — one clean [TG, hd] MXU
    # tile covers the whole GQA group without repeating K/V
    qr = q.reshape(B, T, Hk, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, Hk, TG, hd)

    kern = functools.partial(
        _kernel, bs=bs, T=T, G=G, Hk=Hk, nb=NB, scale=1.0 / np.sqrt(hd),
        quant=quant, compute_dtype=q.dtype,
    )

    def page_idx(b, j, tables, lens):
        # the paged-attention trick: the BlockSpec index map looks the
        # physical page up in the scalar-prefetched table, so the
        # pipeline DMAs pool pages directly — no gathered intermediate
        return (tables[b * NB + j], 0, 0, 0)

    def scale_idx(b, j, tables, lens):
        return (tables[b * NB + j], 0, 0)

    def q_idx(b, j, tables, lens):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hk, TG, hd), q_idx),
        pl.BlockSpec((1, bs, Hk, hd), page_idx),
        pl.BlockSpec((1, bs, Hk, hd), page_idx),
    ]
    args = [qr, k_pages, v_pages]
    if quant:
        in_specs += [
            pl.BlockSpec((1, bs, Hk), scale_idx),
            pl.BlockSpec((1, bs, Hk), scale_idx),
        ]
        args += [key_scales, value_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NB),
        in_specs=in_specs,
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
        name="paged_attention",
    )(block_tables.reshape(-1), seq_lens, *args)
    return out.reshape(B, Hk, T, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, T, H, hd)
