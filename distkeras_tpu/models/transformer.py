"""TransformerLM — the flagship long-context model.

The reference has no attention models (SURVEY.md §5.7) — its workloads are
MLP/CNN-scale. This module is the framework's capability extension for
long-context, multi-chip training: a pre-norm decoder-only transformer whose
parallelism is pluggable along two orthogonal mesh axes:

- **sequence parallel (sp)**: ``attention='ring'`` streams KV blocks around
  the mesh axis (:mod:`distkeras_tpu.ops.ring_attention`), each device
  holding T/sp of the sequence;
- **tensor parallel (tp)**: ``tp_size>1`` shards attention heads and MLP
  hidden features Megatron-style — column-parallel into the block, one
  ``psum`` coming out (:class:`TPDenseGeneral`). Inside ``shard_map``,
  JAX 0.9's vma-aware autodiff inserts the conjugate all-reduces in the
  backward pass automatically (the "f/g" pair of Megatron-LM), so the
  module stays a plain forward function.

The same module value runs single-chip (``tp_size=1``, standard attention)
or sharded; parameter trees are structurally identical, so a full-size init
can be sliced onto the mesh by :func:`distkeras_tpu.parallel.spmd.lm_param_specs`.

Design notes for the MXU/HBM: bfloat16 activations, d_model/heads sized in
multiples of 128, single matmul per projection, no data-dependent control
flow (jit-stable static shapes).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.blocks import live_packing, pack_live, unpack_live
from distkeras_tpu.models.registry import register_model


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Rotary position embedding (rotate-half convention, theta=10000):
    ``x [B, T, H, hd]`` rotated by per-position angles — relative
    positions enter attention through the q·k product itself, so there is
    no additive table and no trained length ceiling beyond the cache.
    ``pos`` are GLOBAL positions (ring shards and decode steps pass their
    offsets): ``[T]`` shared across the batch, or ``[B, T]`` per-row (the
    continuous-batching engine's slots sit at independent depths)."""
    hd = x.shape[-1]
    if hd % 2:
        raise ValueError(
            f"rope needs an even head dim (pairs of rotated channels); "
            f"got head_dim={hd} — pick d_model/num_heads even"
        )
    half = hd // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs  # [(B,) T, half]
    if ang.ndim == 3:
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    else:
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def sinusoidal_positions(max_len: int, dim: int) -> np.ndarray:
    pos = np.arange(max_len)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    out = np.zeros((max_len, dim), dtype=np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


class TPDenseGeneral(nn.Module):
    """Dense projection with optional Megatron-style tensor sharding.

    ``features`` is always the GLOBAL output feature shape; with
    ``tp_size>1`` a ``'col'`` layer creates the local 1/tp_size slice of
    its sharded feature dim, and a ``'row'`` layer consumes locally-sharded
    inputs and ``psum``s its partial product over ``tp_axis`` before adding
    the (replicated) bias — so col→(elementwise)→row needs exactly one
    collective per pair. Parameter names/structure match the ``tp_size=1``
    module, which is how a full-size host init slices onto the mesh.

    Contraction is over the trailing ``in_axes`` axes of ``x`` (the only
    form the transformer needs; keeps the kernel one reshaped matmul for
    the MXU).
    """

    features: Tuple[int, ...]
    in_axes: int = 1
    mode: Optional[str] = None  # 'col' | 'row' | None
    shard_dim: int = 0  # which features dim is sharded in 'col' mode
    tp_size: int = 1
    tp_axis: str = "tp"
    dtype: jnp.dtype = jnp.bfloat16
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        feats = list(self.features)
        if self.mode == "col" and self.tp_size > 1:
            if feats[self.shard_dim] % self.tp_size != 0:
                raise ValueError(
                    f"col-parallel feature dim {feats[self.shard_dim]} not "
                    f"divisible by tp_size={self.tp_size}"
                )
            feats[self.shard_dim] //= self.tp_size
        in_shape = tuple(x.shape[-self.in_axes:])
        kernel = self.param(
            "kernel",
            nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal",
                in_axis=tuple(range(self.in_axes)),
                out_axis=tuple(range(self.in_axes, self.in_axes + len(feats))),
            ),
            in_shape + tuple(feats),
            jnp.float32,
        )
        fan_in = int(np.prod(in_shape))
        xm = x.reshape(x.shape[: -self.in_axes] + (fan_in,)).astype(self.dtype)
        km = kernel.reshape((fan_in, -1)).astype(self.dtype)
        y = (xm @ km).reshape(x.shape[: -self.in_axes] + tuple(feats))
        if self.mode == "row" and self.tp_size > 1:
            # the Megatron g-op: one all-reduce completes the row-parallel
            # product; its autodiff transpose broadcasts, and the col
            # layer's broadcast transposes back to a psum — both inserted
            # by shard_map's vma machinery.
            y = jax.lax.psum(y, self.tp_axis)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, tuple(feats), jnp.float32
            )
            y = y + bias.astype(self.dtype)
        return y


class VocabHead(nn.Module):
    """Output projection to vocab logits: bf16 operands on the MXU with
    f32 ACCUMULATION and f32 logits out (``preferred_element_type``) —
    an f32-compute Dense here ran at the MXU's f32 rate for ~4% of the
    step's FLOPs, while a bf16-out Dense would quantize the logits
    (softmax over 8k classes cares at the ~1e-2 level). Param tree
    matches ``nn.Dense`` (kernel/bias, f32, lecun-normal), so existing
    checkpoints restore unchanged."""

    vocab_size: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.vocab_size), jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.vocab_size,), jnp.float32
        )
        y = jax.lax.dot_general(
            x.astype(self.dtype), kernel.astype(self.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return y + bias


def _quantize_int8(x):
    """Per-(token, head) symmetric int8 quantization for the KV cache:
    ``[..., hd]`` → (int8 values, f32 scales over the last axis). f32
    scales so tiny rows stay exact; the dequantize fuses into the attend
    einsum so bf16 values never round-trip HBM."""
    a = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.maximum(a / 127.0, 1e-8)
    qx = jnp.clip(
        jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127
    ).astype(jnp.int8)
    return qx, s


class CausalSelfAttention(nn.Module):
    num_heads: int
    dtype: jnp.dtype = jnp.bfloat16
    # 'standard' (auto: dense below _DENSE_MAX_T, then the Pallas
    # causal-skip kernel where it applies on TPU, else blocked),
    # 'pallas', 'blocked', 'dense', or 'ring' (sequence-parallel)
    attention: str = "standard"
    seq_axis: str = "sp"  # mesh axis name used when attention == 'ring'
    tp_size: int = 1
    tp_axis: str = "tp"
    # incremental decoding: cache K/V in a 'cache' variable collection of
    # length cache_len and attend new queries over it (VERDICT r3 next
    # #8); callers apply with mutable=["cache"]
    decode: bool = False
    cache_len: int = 0
    # rotary position embeddings: q/k rotated by GLOBAL position before
    # any kernel/cache — composes with every attention mode (the kernels
    # see ordinary q/k) and with decode (the cache stores rotated keys)
    rope: bool = False
    # grouped-query attention (VERDICT r4 next #5): num_kv_heads <
    # num_heads shares each K/V head across num_heads/num_kv_heads query
    # heads. The decode KV cache and its per-token HBM stream shrink by
    # that factor — the lever for the bandwidth-bound incremental-decode
    # regime (benchmarks/decode_bench.py). None = MHA (one KV head per
    # query head, fused qkv projection, param tree unchanged from r4
    # checkpoints). Declared last so existing positional callers keep
    # their meaning.
    num_kv_heads: Optional[int] = None
    # KV-cache storage dtype for decode: 'model' (bf16) or 'int8'
    # (per-row symmetric quantization, f32 scales per [B, L, Hk] row —
    # another 2x off the bandwidth-bound decode stream on top of GQA;
    # the dequantize fuses into the attend einsum so the bf16 values
    # never round-trip HBM). Composes with GQA: kv_heads=2 + int8 is an
    # 8x smaller cache stream than the r4 MHA-bf16 baseline.
    cache_dtype: str = "model"
    # per-row cache cursors (the continuous-batching serving engine,
    # serving/engine.py): cache_index becomes a [B] vector, and writes /
    # rope / the causal mask are applied at each row's own cursor — batch
    # row b is a SLOT holding an independent sequence at its own depth,
    # so finished slots can be refilled mid-flight without touching the
    # others. Requires decode=True; the math per row is identical to the
    # scalar-cursor path (parity-tested in tests/test_serving.py).
    slot_cursor: bool = False
    # paged KV cache (the block-pooled serving engine, serving/kvpool.py
    # + serving/prefix.py): the cache is [num_pages, page_block_size,
    # Hk, hd] per layer — a pool of fixed-size token blocks shared by
    # every sequence — and each call carries per-row block tables
    # ([B, max_blocks] physical block ids) and sequence lengths ([B]
    # cursors). K/V writes scatter to (table[pos // bs], pos % bs);
    # the attend gathers each row's blocks back into a [B, L, Hk, hd]
    # view, so the math (and under rope/GQA/int8, the bits) is the
    # slot-cursor path's exactly. decode=True only; cursors live with
    # the host scheduler, not in the cache collection.
    paged: bool = False
    page_block_size: int = 16
    num_pages: int = 0
    # paged attend implementation: 'auto' (the Pallas paged-attention
    # kernel where ops.paged_attention.preferred says the shape tiles on
    # this backend, else the gathered reference), 'pallas' (force the
    # kernel — interpret mode off-TPU, the parity tests' lever), or
    # 'gather' (force the XLA gather+einsum reference). The kernel DMAs
    # pool pages straight off the block table and dequantizes int8 KV in
    # VMEM; the gathered path materializes the whole [B, L, Hk, hd]
    # (dequantized!) view per call and stays the bit-parity reference.
    paged_kernel: str = "auto"
    # attend implementation over the decode cache, for every query
    # width (a chunk of a mixed tick, one decode token, a speculative
    # window): 'auto' (the Pallas kernel of ops.splash_prefill where
    # the shape tiles on this backend — it copies in each row's K/V
    # tiles up to the row's cursor and no further — else the dense
    # masked reference, which reads all L positions of every row),
    # 'splash' (force; interpret mode off-TPU, the parity tests'
    # lever), or 'gather' (force the dense reference). Serves BOTH
    # decode cache layouts: the slot leaves directly, and the paged
    # path's gathered view when the paged Pallas kernel did not take
    # the call.
    prefill_kernel: str = "auto"

    _DENSE_MAX_T = 512  # short sequences: one fused dense block is fastest

    def _use_paged_kernel(self, T, G, hd, quant, Hk=1) -> bool:
        """Resolve ``paged_kernel`` for this call shape: 'auto' defers
        to the kernel's own preferred() gate (TPU + tileable), 'pallas'
        forces it (interpret mode off-TPU), 'gather' keeps the XLA
        reference."""
        if self.paged_kernel == "gather":
            return False
        if self.paged_kernel == "pallas":
            return True
        from distkeras_tpu.ops import paged_attention as _pa

        store = 1 if quant else jnp.dtype(self.dtype).itemsize
        return _pa.preferred(T, G, hd, self.page_block_size,
                             store_itemsize=store, Hk=Hk)

    def _use_prefill_kernel(self, T, G, hd, L, Hk=1) -> bool:
        """Resolve ``prefill_kernel`` for this call shape: 'auto'
        defers to the kernel's preferred() gate (TPU + tileable),
        'splash' forces it (interpret mode off-TPU), 'gather' keeps the
        dense reference — each for every ``T``. A decode step
        (``T == 1``) has no masked half to skip, but it is bound by
        bytes, not FLOPs: the dense attend streams all ``L`` positions
        of every row's K and V, the kernel only those up to the row's
        cursor."""
        from distkeras_tpu.ops import splash_prefill as _sp

        return _sp.resolves_to_kernel(self.prefill_kernel, T, G, hd, L, Hk)

    def _paged_attend(self, q, k, v, block_tables, seq_lens,
                      valid_lens=None):
        """Paged twin of :meth:`_cached_attend`: same rope-at-cursor,
        same grouped attend, same masks — but K/V live in the global
        block pool and this row's view of it is assembled by gathering
        its block table. Writes land at each token's (block, offset);
        the caller guarantees a row only ever writes blocks it owns
        exclusively (copy-on-write upstream), so the scatter never
        races a shared prefix.

        ``valid_lens`` ([B] int32) marks the chunked mixed
        prefill/decode tick: row b's first ``valid_lens[b]`` tokens are
        real (a prompt chunk, or one sampled decode token), the rest is
        padding whose K/V writes are steered to the reserved trash
        block 0 — positions stay absolute, so the cache bytes are
        bit-identical to an unchunked prefill of the same prompt."""
        B, T, H, hd = q.shape
        Hk = k.shape[2]
        G = H // Hk
        bs = self.page_block_size
        nb = self.num_pages
        max_blocks = block_tables.shape[-1]
        L = max_blocks * bs
        quant = self.cache_dtype == "int8"
        store = jnp.int8 if quant else self.dtype
        ck = self.variable(
            "cache", "paged_key", jnp.zeros, (nb, bs, Hk, hd), store
        )
        cv = self.variable(
            "cache", "paged_value", jnp.zeros, (nb, bs, Hk, hd), store
        )
        if quant:
            ks = self.variable(
                "cache", "key_scale", jnp.ones, (nb, bs, Hk), jnp.float32
            )
            vs = self.variable(
                "cache", "value_scale", jnp.ones, (nb, bs, Hk), jnp.float32
            )
        pos = seq_lens[:, None] + jnp.arange(T)  # [B, T] absolute
        if self.rope:
            q = apply_rope(q, pos)
            k = apply_rope(k, pos)
        # token t of row b lands in physical block table[pos // bs] at
        # offset pos % bs; idle rows point at the reserved trash block
        blk = jnp.take_along_axis(
            block_tables, jnp.minimum(pos // bs, max_blocks - 1), axis=1
        )
        off = pos % bs
        if valid_lens is not None:
            # chunk padding (t >= valid_lens[b]) writes to the trash
            # block, exactly like an idle row — a padded mixed tick
            # leaves the same cache bytes as an exact-length prefill
            blk = jnp.where(
                jnp.arange(T)[None, :] < valid_lens[:, None], blk, 0
            )

        @jax.named_scope("cache_update")
        def put(cache, new):
            return cache.at[blk, off].set(new.astype(cache.dtype))

        def view(cache):
            # [B, max_blocks, bs, ...] gather -> the row-major [B, L,
            # ...] layout the slot path attends over
            g = cache[block_tables]
            return g.reshape((B, L) + cache.shape[2:])

        if quant:
            kq, k_s = _quantize_int8(k)
            vq, v_s = _quantize_int8(v)
            ck.value = put(ck.value, kq)
            cv.value = put(cv.value, vq)
            ks.value = put(ks.value, k_s)
            vs.value = put(vs.value, v_s)
        else:
            ck.value = put(ck.value, k)
            cv.value = put(cv.value, v)
        if self._use_paged_kernel(T, H // Hk, hd, quant, Hk):
            # Pallas paged attention: pages DMA'd straight off the block
            # table, int8 dequant fused in VMEM — the gathered [B, L]
            # view below never materializes (ops/paged_attention.py)
            from distkeras_tpu.ops.paged_attention import paged_attention

            return paged_attention(
                q, ck.value, cv.value, block_tables, seq_lens,
                ks.value if quant else None,
                vs.value if quant else None,
            )
        if quant:
            keys = (view(ck.value).astype(jnp.float32)
                    * view(ks.value)[..., None]).astype(self.dtype)
            vals = (view(cv.value).astype(jnp.float32)
                    * view(vs.value)[..., None]).astype(self.dtype)
        else:
            keys, vals = view(ck.value), view(cv.value)
        if self._use_prefill_kernel(T, G, hd, L, Hk):
            # the cursor-bounded kernel over the gathered view:
            # identical absolute-position masks, KV tiles beyond each
            # row's diagonal not read (ops/splash_prefill.py; the
            # gather above still built them); the dense attend below
            # stays the bit-parity reference
            from distkeras_tpu.ops.splash_prefill import (
                splash_prefill_attention,
            )

            return splash_prefill_attention(q, keys, vals, seq_lens,
                                            valid_lens)
        scale = 1.0 / np.sqrt(hd)
        qg = q.reshape(B, T, Hk, G, hd)
        s = jnp.einsum(
            "bqkgd,blkd->bkgql", qg, keys
        ).astype(jnp.float32) * scale
        mask = jnp.arange(L)[None, None, :] <= pos[..., None]  # [B, T, L]
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgql,blkd->bqkgd", p.astype(self.dtype), vals)
        return out.reshape(B, T, H, hd)

    def _cached_attend(self, q, k, v, valid_lens=None):
        """Write this call's K/V at the cache cursor, attend q over the
        whole cache with a positions-seen-so-far mask. Works for a
        multi-token prefill and for one-token decode steps alike.

        The cache holds the KV heads only ([B, L, Hk, hd]) — under GQA
        that is the whole point: the per-step HBM stream of a
        bandwidth-bound decode drops by H/Hk. Queries attend grouped
        (``g`` = queries per KV head) without materializing repeated
        K/V.

        ``valid_lens`` ([B] int32, slot_cursor only) is the chunked
        mixed prefill/decode tick: row b consumes only its first
        ``valid_lens[b]`` tokens — K/V writes for the padding tail are
        dropped (scatter mode='drop' past the cache) and the cursor
        advances by the valid count, so a prompt streamed chunk-by-chunk
        leaves bit-identical cache bytes to one monolithic prefill."""
        B, T, H, hd = q.shape
        # LOCAL KV head count from k itself: under tensor parallelism H
        # and k.shape[2] are this shard's slices, and the global
        # self.num_kv_heads would mis-group (or silently zero-fill the
        # cache) — the incoming tensors are always the truth
        Hk = k.shape[2]
        G = H // Hk
        L = self.cache_len
        if self.cache_dtype not in ("model", "int8"):
            raise ValueError(
                f"Unknown cache_dtype '{self.cache_dtype}'. "
                "Known: model, int8"
            )
        quant = self.cache_dtype == "int8"
        store = jnp.int8 if quant else self.dtype
        ck = self.variable(
            "cache", "cached_key", jnp.zeros, (B, L, Hk, hd), store
        )
        cv = self.variable(
            "cache", "cached_value", jnp.zeros, (B, L, Hk, hd), store
        )
        if quant:
            # per-(token, head) symmetric scales; f32 so tiny rows stay
            # exact. Cache stream per token: hd int8 + 1 f32 vs hd bf16
            # -> ~2x smaller, dequant fused into the attend einsums
            ks = self.variable(
                "cache", "key_scale", jnp.ones, (B, L, Hk), jnp.float32
            )
            vs = self.variable(
                "cache", "value_scale", jnp.ones, (B, L, Hk), jnp.float32
            )
        idx = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((B,) if self.slot_cursor else (), jnp.int32),
        )
        cur = idx.value  # [] shared cursor, or [B] per-slot cursors
        if self.rope:
            if self.slot_cursor:
                pos = cur[:, None] + jnp.arange(T)[None]  # [B, T]
            else:
                pos = cur + jnp.arange(T)
            q = apply_rope(q, pos)
            k = apply_rope(k, pos)

        @jax.named_scope("cache_update")
        def put(cache, new):
            if valid_lens is not None:
                # chunked mixed tick: scatter each row's VALID tokens at
                # its cursor; padding positions are pushed past L and
                # dropped, so they can neither clobber history (the
                # dynamic_update_slice clamp would) nor leave garbage
                # the next chunk hasn't overwritten
                tpos = jnp.where(
                    jnp.arange(new.shape[1])[None, :]
                    < valid_lens[:, None],
                    cur[:, None] + jnp.arange(new.shape[1])[None, :],
                    L,
                )
                return cache.at[jnp.arange(cache.shape[0])[:, None],
                                tpos].set(new.astype(cache.dtype),
                                          mode="drop")
            if self.slot_cursor:
                # each slot writes at its own cursor
                return jax.vmap(
                    lambda c, n, i: jax.lax.dynamic_update_slice(
                        c, n, (i,) + (0,) * (c.ndim - 1)
                    )
                )(cache, new, cur)
            return jax.lax.dynamic_update_slice(
                cache, new, (0, cur) + (0,) * (cache.ndim - 2)
            )

        if quant:
            kq, k_s = _quantize_int8(k)
            vq, v_s = _quantize_int8(v)
            ck.value = put(ck.value, kq)
            cv.value = put(cv.value, vq)
            ks.value = put(ks.value, k_s)
            vs.value = put(vs.value, v_s)
            keys = ck.value.astype(jnp.float32) * ks.value[..., None]
            vals = (cv.value.astype(jnp.float32)
                    * vs.value[..., None]).astype(self.dtype)
            keys = keys.astype(self.dtype)
        else:
            ck.value = put(ck.value, k.astype(self.dtype))
            cv.value = put(cv.value, v.astype(self.dtype))
            keys, vals = ck.value, cv.value
        idx.value = cur + (T if valid_lens is None else valid_lens)
        if self._use_prefill_kernel(T, G, hd, L, Hk):
            # the cursor-bounded kernel over the slot cache leaves,
            # chunk or decode step alike: same per-row
            # absolute-position masks as the dense attend below (which
            # stays the bit-parity reference), KV tiles beyond each
            # row's last valid token never copied in
            # (ops/splash_prefill.py)
            from distkeras_tpu.ops.splash_prefill import (
                splash_prefill_attention,
            )

            starts = (cur if self.slot_cursor
                      else jnp.broadcast_to(cur, (B,)))
            return splash_prefill_attention(q, keys, vals, starts,
                                            valid_lens)
        scale = 1.0 / np.sqrt(hd)
        qg = q.reshape(B, T, Hk, G, hd)
        s = jnp.einsum(
            "bqkgd,blkd->bkgql", qg, keys
        ).astype(jnp.float32) * scale
        if self.slot_cursor:
            q_pos = cur[:, None] + jnp.arange(T)[None]  # [B, T]
            mask = jnp.arange(L)[None, None, :] <= q_pos[..., None]
            s = jnp.where(mask[:, None, None], s, -1e30)  # [B,1,1,T,L]
        else:
            q_pos = cur + jnp.arange(T)
            mask = jnp.arange(L)[None, :] <= q_pos[:, None]  # [T, L]
            s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgql,blkd->bqkgd", p.astype(self.dtype), vals)
        return out.reshape(B, T, H, hd)

    @nn.compact
    def __call__(self, x, block_tables=None, seq_lens=None,
                 valid_lens=None, packing=None):
        """``packing`` (a mixed tick's live tokens packed, see
        :meth:`TransformerLM.__call__`): ``x`` is ``[1, N, D]``, the
        projections run on those ``N`` rows, and only the attend sees
        the ``[S, C]`` layout — q, k and v go back to it with zeros
        where nothing was dealt, and its output is gathered to ``N``
        again."""
        B, T, D = x.shape
        H = self.num_heads
        hd = D // H
        if H % self.tp_size != 0:
            raise ValueError(
                f"num_heads={H} not divisible by tp_size={self.tp_size}"
            )
        if self.cache_dtype not in ("model", "int8"):
            # fail fast like remat/pos_emb/attention — not only when a
            # decode clone finally hits the cache path (r5 review)
            raise ValueError(
                f"Unknown cache_dtype '{self.cache_dtype}'. "
                "Known: model, int8"
            )
        if self.slot_cursor and not self.decode:
            raise ValueError(
                "slot_cursor=True (per-row cache cursors) only makes "
                "sense with decode=True"
            )
        if self.prefill_kernel not in ("auto", "splash", "gather"):
            raise ValueError(
                f"Unknown prefill_kernel '{self.prefill_kernel}'. "
                "Known: auto, splash, gather"
            )
        if valid_lens is not None and not (self.slot_cursor or self.paged):
            raise ValueError(
                "valid_lens (chunked mixed prefill/decode) needs per-row "
                "cursors: slot_cursor=True or paged=True"
            )
        if self.paged:
            if not self.decode:
                raise ValueError(
                    "paged=True (block-pooled KV cache) requires "
                    "decode=True"
                )
            if self.slot_cursor:
                raise ValueError(
                    "paged and slot_cursor are mutually exclusive cache "
                    "layouts"
                )
            if self.paged_kernel not in ("auto", "pallas", "gather"):
                raise ValueError(
                    f"Unknown paged_kernel '{self.paged_kernel}'. "
                    "Known: auto, pallas, gather"
                )
            if self.num_pages < 2:
                raise ValueError(
                    f"paged mode needs num_pages >= 2 (block 0 is the "
                    f"reserved trash block); got {self.num_pages}"
                )
            if block_tables is None or seq_lens is None:
                raise ValueError(
                    "paged mode needs block_tables [B, max_blocks] and "
                    "seq_lens [B] passed per call"
                )
        Hk = self.num_kv_heads or H
        if H % Hk != 0:
            raise ValueError(
                f"num_heads={H} not divisible by num_kv_heads={Hk}"
            )
        if Hk % self.tp_size != 0:
            raise ValueError(
                f"num_kv_heads={Hk} not divisible by tp_size="
                f"{self.tp_size} (each tp shard needs whole KV heads)"
            )
        if Hk == H:
            qkv = TPDenseGeneral(
                features=(3, H, hd), in_axes=1, mode="col", shard_dim=1,
                tp_size=self.tp_size, tp_axis=self.tp_axis,
                dtype=self.dtype, name="qkv",
            )(x)  # [B, T, 3, H_local, hd]
            if packing is not None:
                qkv = unpack_live(qkv, packing)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            # GQA: separate projections (a fused qkv would force equal
            # head counts). Param names are new ('q_proj'/'kv_proj') so
            # an MHA checkpoint can't silently restore into a GQA model.
            q = TPDenseGeneral(
                features=(H, hd), in_axes=1, mode="col", shard_dim=0,
                tp_size=self.tp_size, tp_axis=self.tp_axis,
                dtype=self.dtype, name="q_proj",
            )(x)  # [B, T, H_local, hd]
            kv = TPDenseGeneral(
                features=(2, Hk, hd), in_axes=1, mode="col", shard_dim=1,
                tp_size=self.tp_size, tp_axis=self.tp_axis,
                dtype=self.dtype, name="kv_proj",
            )(x)  # [B, T, 2, Hk_local, hd]
            if packing is not None:
                q, kv = unpack_live(q, packing), unpack_live(kv, packing)
            k, v = kv[:, :, 0], kv[:, :, 1]
        if self.rope and not self.decode:
            # global positions: ring shards offset by their shard index;
            # the decode branch applies rope at the cache cursor instead
            pos = jnp.arange(T)
            if self.attention == "ring":
                pos = pos + jax.lax.axis_index(self.seq_axis) * T
            q = apply_rope(q, pos)
            k = apply_rope(k, pos)
        if self.decode:
            if self.attention == "ring":
                raise ValueError(
                    "decode mode needs a single-host attention mode "
                    "(sequence-parallel decoding is not supported)"
                )
            if self.cache_len <= 0:
                raise ValueError("decode mode needs cache_len > 0")
            if self.paged:
                out = self._paged_attend(q, k, v, block_tables, seq_lens,
                                         valid_lens)
            else:
                out = self._cached_attend(q, k, v, valid_lens)
            if packing is not None:
                out = pack_live(out, packing)
            return TPDenseGeneral(
                features=(D,), in_axes=2, mode="row",
                tp_size=self.tp_size, tp_axis=self.tp_axis,
                dtype=self.dtype, name="out",
            )(out)
        if Hk != H:
            # training/prefill kernels attend over full query heads:
            # broadcast each KV head across its G query heads (XLA fuses
            # the repeat into the consuming matmul; the HBM win of GQA is
            # the decode cache, handled grouped in _cached_attend)
            k = jnp.repeat(k, H // Hk, axis=2)
            v = jnp.repeat(v, H // Hk, axis=2)
        mode = self.attention
        if mode == "standard":
            if T <= self._DENSE_MAX_T:
                mode = "dense"
            else:
                from distkeras_tpu.ops import pallas_attention

                # the Pallas kernel skips the masked causal tiles the
                # blocked kernel computes (measured 1.6-2.4x at
                # T=2048-8192); interpret mode off-TPU is correct but
                # slow, so only TPU auto-selects it, via the shared
                # predicate
                mode = ("pallas"
                        if pallas_attention.preferred(
                            T, hd,
                            itemsize=jnp.dtype(self.dtype).itemsize)
                        else "blocked")
        if mode == "ring":
            from distkeras_tpu.ops.ring_attention import ring_attention

            out = ring_attention(q, k, v, axis_name=self.seq_axis, causal=True)
        elif mode == "pallas":
            from distkeras_tpu.ops import pallas_attention
            from distkeras_tpu.ops.pallas_attention import (
                pallas_causal_attention,
            )

            # run at the block choose_block picked (the preferred() gate
            # above guarantees one exists); T=1536/3072 etc. land on a
            # non-default block instead of losing the kernel
            out = pallas_causal_attention(
                q, k, v,
                block=pallas_attention.choose_block(
                    T, hd, itemsize=jnp.dtype(self.dtype).itemsize
                ) or pallas_attention.DEFAULT_BLOCK,
            )
        elif mode == "blocked":
            from distkeras_tpu.ops.flash_attention import blocked_causal_attention

            out = blocked_causal_attention(q, k, v, causal=True)
        elif mode == "dense":
            scale = 1.0 / np.sqrt(hd)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            logits = jnp.where(mask[None, None], logits, -1e30)
            probs = jnp.exp(logits - logits.max(-1, keepdims=True))
            probs = probs / probs.sum(-1, keepdims=True)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(self.dtype), v)
        else:
            raise ValueError(
                f"Unknown attention mode '{self.attention}'. "
                "Known: standard, dense, blocked, pallas, ring"
            )
        return TPDenseGeneral(
            features=(D,), in_axes=2, mode="row",
            tp_size=self.tp_size, tp_axis=self.tp_axis, dtype=self.dtype,
            name="out",
        )(out)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "standard"
    seq_axis: str = "sp"
    tp_size: int = 1
    tp_axis: str = "tp"
    # expert parallelism: >0 replaces the dense MLP with a SwitchMoE of
    # this many (global) experts, sharded over ep_axis when ep_size > 1
    moe_experts: int = 0
    ep_size: int = 1
    ep_axis: str = "ep"
    moe_top_k: int = 1  # 1 = Switch, 2 = GShard-style routing
    decode: bool = False
    cache_len: int = 0
    rope: bool = False
    num_kv_heads: Optional[int] = None  # GQA; None = MHA
    cache_dtype: str = "model"  # decode KV cache: 'model' | 'int8'
    slot_cursor: bool = False  # per-row cache cursors (serving engine)
    paged: bool = False  # block-pooled KV cache (serving/kvpool.py)
    page_block_size: int = 16
    num_pages: int = 0
    paged_kernel: str = "auto"  # paged attend: auto | pallas | gather
    prefill_kernel: str = "auto"  # cache attend: auto | splash | gather

    @nn.compact
    def __call__(self, x, block_tables=None, seq_lens=None,
                 valid_lens=None, packing=None):
        D = x.shape[-1]
        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + CausalSelfAttention(
            self.num_heads, self.dtype, self.attention, self.seq_axis,
            self.tp_size, self.tp_axis,
            decode=self.decode, cache_len=self.cache_len, rope=self.rope,
            num_kv_heads=self.num_kv_heads,
            cache_dtype=self.cache_dtype,
            slot_cursor=self.slot_cursor,
            paged=self.paged,
            page_block_size=self.page_block_size,
            num_pages=self.num_pages,
            paged_kernel=self.paged_kernel,
            prefill_kernel=self.prefill_kernel,
        )(h, block_tables, seq_lens, valid_lens, packing)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.moe_experts > 0:
            from distkeras_tpu.ops.moe import SwitchMoE

            h = SwitchMoE(
                num_experts=self.moe_experts,
                hidden=D * self.mlp_ratio,
                ep_size=self.ep_size,
                ep_axis=self.ep_axis,
                dtype=self.dtype,
                top_k=self.moe_top_k,
                name="moe",
            )(h)
        else:
            h = TPDenseGeneral(
                features=(D * self.mlp_ratio,), in_axes=1, mode="col",
                tp_size=self.tp_size, tp_axis=self.tp_axis, dtype=self.dtype,
                name="mlp_up",
            )(h)
            h = nn.gelu(h)
            h = TPDenseGeneral(
                features=(D,), in_axes=1, mode="row",
                tp_size=self.tp_size, tp_axis=self.tp_axis, dtype=self.dtype,
                name="mlp_down",
            )(h)
        return x + h


@register_model("transformer_lm")
class TransformerLM(nn.Module):
    """Decoder-only LM: tokens [B, T] int32 → logits [B, T, vocab] f32.

    ``tp_size``/``tp_axis`` shard heads + MLP hidden tensor-parallel (only
    meaningful inside a ``shard_map`` over ``tp_axis``); ``attention='ring'``
    shards the sequence over ``seq_axis``. Both compose — see
    :func:`distkeras_tpu.parallel.spmd.make_lm_train_step`.
    """

    vocab_size: int = 1024
    d_model: int = 256
    num_heads: int = 4
    num_layers: int = 4
    max_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "standard"
    seq_axis: str = "sp"
    tp_size: int = 1
    tp_axis: str = "tp"
    moe_experts: int = 0
    ep_size: int = 1
    ep_axis: str = "ep"
    moe_top_k: int = 1
    # activation checkpointing (VERDICT r3 next #3): 'block' recomputes
    # each Block's internals during backward, so the autodiff residual
    # per layer shrinks from O(T * d_model * ~10) activation tensors to
    # the block's input — HBM stops being the long-context ceiling
    # (T=8192 trains at 4x the batch; T=16384 becomes trainable at all).
    # ~1/3 extra forward FLOPs; the math is unchanged (equality-tested).
    remat: str = "none"  # 'none' | 'block'
    # incremental decoding (see generate()): K/V cached per layer in a
    # 'cache' collection of length max_len; apply with mutable=["cache"]
    decode: bool = False
    # positional encoding: 'sinusoidal' (additive table, the default) or
    # 'rope' (rotary on q/k — relative positions in the attention product
    # itself; composes with ring/tp/pp/decode, no additive table;
    # measured ~6% flagship throughput for the per-layer q/k rotations)
    pos_emb: str = "sinusoidal"
    # grouped-query attention (VERDICT r4 next #5): KV heads shared by
    # num_heads/num_kv_heads query heads each — the decode KV cache and
    # its bandwidth-bound per-token stream shrink by that factor. None =
    # MHA. Train/decode parity and the decode roofline gain are tested
    # (tests/test_gqa.py) and measured (benchmarks/decode_bench.py).
    num_kv_heads: Optional[int] = None
    # decode KV-cache storage: 'model' (bf16) or 'int8' (per-row
    # symmetric quantization + f32 scales — halves the bandwidth-bound
    # cache stream again on top of GQA; decode-parity tested at ~1e-2
    # logit tolerance)
    cache_dtype: str = "model"
    # per-row cache cursors for the continuous-batching serving engine
    # (serving/engine.py): each batch row is an independent slot with its
    # own cursor — prefills scatter into a slot, EOS'd slots refill
    # without touching neighbours. decode=True only.
    slot_cursor: bool = False
    # paged KV cache (serving/kvpool.py + serving/prefix.py): per-layer
    # caches become one pool of num_pages fixed-size token blocks
    # [num_pages, page_block_size, Hk, hd] shared by every sequence.
    # Each apply() carries block_tables [B, max_blocks] (physical block
    # ids per row) and seq_lens [B] (host-owned cursors); blocks holding
    # a shared prompt prefix appear in many tables at once, which is
    # what lets the radix prefix index skip their prefill entirely.
    # decode=True only; exclusive with slot_cursor.
    paged: bool = False
    page_block_size: int = 16
    num_pages: int = 0
    # paged attend implementation: 'auto' (Pallas paged-attention kernel
    # where the shape tiles on this backend — pages DMA'd off the block
    # table, int8 dequant fused in VMEM), 'pallas' (force; interpret
    # mode off-TPU), 'gather' (the XLA gather+einsum reference)
    paged_kernel: str = "auto"
    # attend implementation over the decode cache, every query width
    # (chunk, decode step, verify window; both cache layouts): 'auto'
    # (the Pallas kernel of ops/splash_prefill.py where the shape tiles
    # on this backend — each row's K/V read up to its cursor only),
    # 'splash' (force; interpret mode off-TPU), 'gather' (the dense
    # masked reference)
    prefill_kernel: str = "auto"
    # features_only=True returns the backbone's ln_f output [B, T, D]
    # instead of logits, for the fused chunked cross-entropy
    # (ops/fused_ce.py): the head matmul then happens INSIDE the loss,
    # chunk-by-chunk, and [B, T, V] logits never materialize. The head's
    # params are untouched (init with the default model so they exist);
    # toggle with ``model.copy(features_only=True)`` — flax module
    # attributes are config, not state, so the param tree is shared.
    features_only: bool = False

    @property
    def packs_live_tokens(self) -> bool:
        """Whether a decode apply takes ``live_tokens`` (the serving
        engine asks before it passes one). Not with routed experts:
        ``SwitchMoE`` sizes its capacity from the tokens it is given,
        so leaving the padding out would change which tokens it
        drops."""
        return self.moe_experts == 0

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 block_tables=None, seq_lens=None, valid_lens=None,
                 live_tokens: Optional[int] = None):
        """``live_tokens`` (a static count ``N``, with ``valid_lens``
        on a per-row-cursor decode module) is the PACKED form of a
        chunked mixed tick: of the ``[S, C]`` ``tokens`` only the
        ``sum(valid_lens) <= N`` live ones are embedded, and every
        per-token layer (LayerNorms, projections, MLP) runs on ``[1,
        N, D]``; attention alone puts q, k and v back where they lie in
        ``[S, C]``, so cache writes, cursors and the attend are those
        of the unpacked call. ``ln_f`` and the head run on each row's
        LAST VALID token only: the result is ``[S, 1, vocab]`` (a row
        with ``valid_lens`` 0 holds another row's, never read)."""
        if self.remat not in ("none", "block"):
            raise ValueError(
                f"Unknown remat policy '{self.remat}'. Known: none, block"
            )
        if self.pos_emb not in ("sinusoidal", "rope"):
            raise ValueError(
                f"Unknown pos_emb '{self.pos_emb}'. Known: sinusoidal, rope"
            )
        if self.slot_cursor and not self.decode:
            raise ValueError(
                "slot_cursor=True (per-row cache cursors) requires "
                "decode=True"
            )
        if self.paged and not self.decode:
            raise ValueError(
                "paged=True (block-pooled KV cache) requires decode=True"
            )
        rope = self.pos_emb == "rope"
        packing = None
        if live_tokens is not None:
            if valid_lens is None or not self.packs_live_tokens:
                raise ValueError(
                    "live_tokens (the packed mixed tick) needs valid_lens "
                    "and dense MLPs (moe_experts=0)"
                )
            chunk = tokens.shape[1]
            packing = live_packing(valid_lens, chunk, live_tokens)
            # the row and column each packed token came from
            live_row, live_col = packing.idx // chunk, packing.idx % chunk
            tokens = tokens.reshape(-1)[packing.idx][None]  # [1, N]
        # explicit submodule names: the pipeline-parallel path addresses
        # param subtrees by name (parallel/pipeline.py), so these are API
        x = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.dtype, name="embed"
        )(tokens)
        if not rope:
            # With ring attention each shard holds a T/sp slice of the
            # sequence, so positions must be *global*: shard_index *
            # T_local + local offset. (rope handles positions inside
            # attention instead.)
            pos_table = jnp.asarray(
                sinusoidal_positions(self.max_len, self.d_model)
            )
            local_pos = jnp.arange(x.shape[1])
            if self.attention == "ring":
                offset = jax.lax.axis_index(self.seq_axis) * x.shape[1]
                local_pos = local_pos + offset
            if self.decode:
                if self.paged:
                    # paged cursors are host-owned and arrive per call:
                    # positions start at each row's seq_lens entry (no
                    # pos_index cache variable to keep in sync)
                    if packing is not None:
                        local_pos = (seq_lens[live_row] + live_col)[None]
                    else:
                        local_pos = local_pos[None, :] + seq_lens[:, None]
                else:
                    # decode steps see only the new tokens; their
                    # positions start at the running cursor (kept with
                    # the KV caches) — a scalar, or one cursor per slot
                    # under slot_cursor
                    pos_idx = self.variable(
                        "cache", "pos_index",
                        lambda: jnp.zeros(
                            (x.shape[0],) if self.slot_cursor else (),
                            jnp.int32,
                        ),
                    )
                    if packing is not None:
                        local_pos = (pos_idx.value[live_row]
                                     + live_col)[None]
                    elif self.slot_cursor:
                        local_pos = (local_pos[None, :]
                                     + pos_idx.value[:, None])
                    else:
                        local_pos = local_pos + pos_idx.value
                    # chunked mixed tick: each row advances by its own
                    # valid count (padding consumes no positions);
                    # padded tail positions may run past max_len —
                    # jnp.take clips, and those rows' outputs are
                    # garbage the engine never reads
                    pos_idx.value = pos_idx.value + (
                        x.shape[1] if valid_lens is None else valid_lens
                    )
            # mode="clip": a chunked mixed tick's padding positions can
            # run past max_len; the default OOB fill would hand those
            # tokens NaN embeddings, whose K/V lands in the paged trash
            # block and 0·NaN-poisons every row that gathers it. Clipped
            # garbage is finite, so masked positions contribute exactly 0.
            taken = jnp.take(pos_table, local_pos, axis=0, mode="clip")
            if taken.ndim == 2:  # shared positions: broadcast over batch
                taken = taken[None]
            x = x + taken.astype(self.dtype)
        # nn.remat is param-structure-transparent: checkpoints keep the
        # same tree either way, so remat can be toggled on restore
        BlockCls = nn.remat(Block) if self.remat == "block" else Block
        for i in range(self.num_layers):
            x = BlockCls(
                self.num_heads,
                dtype=self.dtype,
                attention=self.attention,
                seq_axis=self.seq_axis,
                tp_size=self.tp_size,
                tp_axis=self.tp_axis,
                moe_experts=self.moe_experts,
                ep_size=self.ep_size,
                ep_axis=self.ep_axis,
                moe_top_k=self.moe_top_k,
                decode=self.decode,
                cache_len=self.max_len if self.decode else 0,
                rope=rope,
                num_kv_heads=self.num_kv_heads,
                cache_dtype=self.cache_dtype,
                slot_cursor=self.slot_cursor,
                paged=self.paged,
                page_block_size=self.page_block_size,
                num_pages=self.num_pages,
                paged_kernel=self.paged_kernel,
                prefill_kernel=self.prefill_kernel,
                name=f"Block_{i}",
            )(x, block_tables, seq_lens, valid_lens, packing)
        if packing is not None:
            # [S, 1, D]: each row's last valid token, where its packed
            # run ends
            x = x[0][jnp.maximum(jnp.cumsum(valid_lens) - 1, 0)][:, None]
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        if self.features_only:
            return x
        return VocabHead(self.vocab_size, self.dtype, name="head")(x)

    @staticmethod
    def casts_first(names) -> bool:
        """Whether the leaf at ``names`` (its dict keys, outermost first)
        is one its module casts to the compute dtype before anything
        else — the rule :func:`compute_params` applies."""
        return (len(names) >= 2
                and names[-1] in _CAST_FIRST.get(names[-2], ()))


# The leaves a module above casts to its ``dtype`` as the first thing it
# does with them, by the name TransformerLM gives the module (the idiom
# of parallel/spmd.py . lm_param_specs). What is not listed stays as
# handed: LayerNorm applies scale and bias in f32 and casts the result,
# VocabHead adds its bias to f32 logits, SwitchMoE routes in f32.
_CAST_FIRST = {
    **{name: ("kernel", "bias")  # TPDenseGeneral
       for name in ("qkv", "q_proj", "kv_proj", "out", "mlp_up",
                    "mlp_down")},
    "head": ("kernel",),  # VocabHead
    "embed": ("embedding",),  # nn.Embed promotes the table, then gathers
    "moe": ("w1", "b1", "w2", "b2"),  # SwitchMoE's expert banks
}


@functools.lru_cache(maxsize=None)
def _cast_program(dtype, shardings):
    """ONE program that casts a list of leaves to ``dtype``, each
    result placed as ``shardings`` says (None: where the compiler puts
    it, its operand's device). One program where a cast a leaf was one
    small compile a distinct shape in every process, none of them long
    enough for the persistent cache to keep."""
    return jax.jit(lambda leaves: [x.astype(dtype) for x in leaves],
                   out_shardings=list(shardings))


def compute_params(model, params):
    """``params`` with every leaf that ``model`` would cast to its
    compute dtype on first use holding the result of that cast, so a
    program that takes the tree as an argument neither reads the wider
    leaf nor converts it on every call. ``model.apply`` gives the same
    bits on either tree: the cast is the one the module makes.

    The rule is the model's (``model.casts_first``); a model that
    brings none, a ``float32`` model, and a leaf already in the compute
    dtype come back as handed, leaf objects included. The casts are one
    jitted program over the leaves that need one (:func:`_cast_program`;
    the handed leaves are not donated: they are the caller's), and a
    leaf spread over several devices keeps the sharding it was handed
    with."""
    from jax.tree_util import (DictKey, tree_flatten_with_path,
                               tree_unflatten)

    rule = getattr(model, "casts_first", None)
    dtype = jnp.dtype(getattr(model, "dtype", jnp.float32))
    if rule is None or dtype == jnp.float32:
        return params
    flat, treedef = tree_flatten_with_path(params)
    leaves = [leaf for _, leaf in flat]
    cast = [i for i, (path, leaf) in enumerate(flat)
            if leaf.dtype != dtype and rule(
                [k.key for k in path if isinstance(k, DictKey)])]
    if not cast:
        return params

    def placed(leaf):
        # only a concrete array that spans devices has a placement the
        # compiler could lose; anything else follows its operand
        sharding = (None if isinstance(leaf, jax.core.Tracer)
                    else getattr(leaf, "sharding", None))
        return (sharding if sharding is not None
                and len(sharding.device_set) > 1 else None)

    program = _cast_program(dtype, tuple(placed(leaves[i]) for i in cast))
    for i, held in zip(cast, program([leaves[i] for i in cast])):
        leaves[i] = held
    return tree_unflatten(treedef, leaves)


def generate(model, params, prompt, max_new_tokens: int,
             temperature: float = 0.0, seed: int = 0,
             eos_id: Optional[int] = None,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             return_steps: bool = False) -> jnp.ndarray:
    """Autoregressive sampling from a trained :class:`TransformerLM`
    (VERDICT r3 next #8 — a framework that headlines LM training must be
    able to emit tokens).

    TPU-first shape: one prefill pass writes the prompt's K/V into a
    preallocated per-layer cache (length ``model.max_len``), then a
    ``lax.scan`` of one-token decode steps attends over the cache — the
    whole decode loop is ONE jitted dispatch, no per-token host round
    trips, no recompute of the prefix.

    Args:
      model: the TRAINING-mode module (``decode=False``); a decode twin
        is cloned internally — param trees are identical, so trained
        checkpoints work as-is.
      params: trained variables (``{"params": ...}``).
      prompt: ``[B, T_prompt]`` int32 token ids, ``T_prompt >= 1``.
      max_new_tokens: tokens to append.
      temperature: 0.0 = greedy argmax; > 0 samples from
        ``softmax(logits / temperature)``.
      seed: PRNG seed for sampled decoding.
      eos_id: optional stop token — finished rows keep emitting it.
      top_k: restrict sampling to the k highest-logit tokens.
      top_p: nucleus sampling — restrict to the smallest set of tokens
        whose cumulative probability exceeds ``top_p``. Composes with
        ``top_k`` (k-filter first, then the nucleus).
      return_steps: also return the number of decode steps actually run.
        With ``eos_id`` set the decode loop is a ``lax.while_loop`` that
        exits as soon as every row has finished — finished output is
        still eos-padded to ``max_new_tokens``, but the padding costs no
        decode steps.

    Returns:
      ``[B, T_prompt + max_new_tokens]`` int32 (and, with
      ``return_steps``, the int decode-step count).
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2 or prompt.shape[1] < 1:
        raise ValueError(f"prompt must be [B, T>=1]; got {prompt.shape}")
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1; got {top_k}")
        # k >= vocab keeps everything; clamp instead of crashing at trace
        top_k = min(top_k, model.vocab_size)
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1]; got {top_p}")
    B, Tp = prompt.shape
    if Tp + max_new_tokens > model.max_len:
        raise ValueError(
            f"prompt ({Tp}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_len={model.max_len} (the KV-cache length)"
        )
    dm = model.clone(decode=True, parent=None)
    run = _generate_fn(dm, B, max_new_tokens, temperature, eos_id,
                       top_k, top_p)
    new, steps = run({"params": params["params"]}, prompt,
                     jax.random.PRNGKey(seed))
    out = jnp.concatenate([prompt, new], axis=1)
    if return_steps:
        return out, int(steps)
    return out


def filter_logits(logits, temperature, top_k=None, top_p=None):
    """The sampling transform of :func:`sample_tokens` WITHOUT the draw:
    ``[..., vocab]`` logits → temperature-scaled, top-k/top-p-masked
    logits (``-inf`` outside the kept set). ``softmax(filter_logits(x))``
    is therefore exactly the distribution ``sample_tokens`` draws from at
    ``temperature > 0`` — the speculative-decoding verify tick
    (serving/engine.py) needs those probabilities explicitly: the
    accept ratio ``min(1, p/q)`` and the residual ``max(p - q, 0)`` of
    rejection sampling must be computed on the *identical* filtered
    distributions the solo sampler uses, or the accepted streams drift
    from ``generate()``'s marginals. Requires ``temperature > 0``
    (greedy has no distribution to filter; callers branch to argmax)."""
    logits = logits / temperature
    if top_k is not None or top_p is not None:
        # ONE descending sort serves both filters (this runs per
        # decoded token): the k-filter folds into the sorted view as
        # an -inf tail, which is exactly the sorted masked
        # distribution the nucleus then operates on
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        if top_k is not None:
            kth = sorted_desc[..., top_k - 1, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
            sorted_desc = jnp.where(
                jnp.arange(sorted_desc.shape[-1]) >= top_k,
                -jnp.inf, sorted_desc,
            )
        if top_p is not None:
            # nucleus: keep the smallest prefix of the sorted
            # distribution whose mass exceeds top_p (the top token
            # always survives: its cum - prob is 0 <= top_p)
            probs = jax.nn.softmax(sorted_desc, axis=-1)
            beyond = jnp.cumsum(probs, axis=-1) - probs > top_p
            kept = jnp.where(beyond, jnp.inf, sorted_desc)
            thresh = jnp.min(kept, axis=-1, keepdims=True)
            logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return logits


def sample_tokens(logits, rng, temperature=0.0, top_k=None, top_p=None):
    """One sampling step: ``[B, vocab]`` logits → ``[B]`` int32 tokens.

    Greedy argmax at temperature 0, else temperature softmax with
    optional top-k / nucleus filtering (:func:`filter_logits`).
    Module-level (factored out of :func:`_generate_fn`) so the
    continuous-batching engine (serving/engine.py) samples each slot
    with bit-identical math and RNG usage to a solo :func:`generate`
    call — that identity is what the slot-refill parity test asserts."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        rng, filter_logits(logits, temperature, top_k, top_p)
    ).astype(jnp.int32)


@functools.lru_cache(maxsize=32)
def _generate_fn(dm, B, max_new_tokens, temperature, eos_id,
                 top_k=None, top_p=None):
    """Compiled prefill + decode-loop closure, cached per (decode module,
    batch, token count, sampling config) — flax modules hash by config,
    so repeated generate() calls (sampling loops, serving) hit the jit
    cache instead of retracing the whole loop. Prompt length stays a
    jit-traced dimension: each distinct T_prompt compiles its own prefill
    once, as any jitted shape does.

    The decode loop is a fixed-length ``lax.scan`` without an eos, and an
    early-exit ``lax.while_loop`` with one: once every row has finished,
    the remaining steps would only emit pad eos tokens, so the loop stops
    instead of burning them. ``run`` returns ``(tokens [B, max_new],
    steps_taken)`` — the buffer is eos-initialized, so the early-exit
    path keeps the exact eos-padded contract of the scan."""

    def sample(logits, rng):
        return sample_tokens(logits, rng, temperature, top_k, top_p)

    @jax.jit
    def run(params_only, prompt, rng):
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(
                dm.init, jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32)
            )["cache"],
        )
        logits, vs = dm.apply(
            {**params_only, "cache": cache}, prompt, mutable=["cache"]
        )
        cache = vs["cache"]
        done0 = jnp.zeros((B,), bool)

        def step(carry, _):
            cache, last_logits, rng, done = carry
            rng, sub = jax.random.split(rng)
            tok = sample(last_logits, sub)
            if eos_id is not None:
                tok = jnp.where(done, jnp.int32(eos_id), tok)
                done = done | (tok == eos_id)
            logits, vs = dm.apply(
                {**params_only, "cache": cache}, tok[:, None],
                mutable=["cache"],
            )
            return (vs["cache"], logits[:, -1], rng, done), tok

        carry0 = (cache, logits[:, -1], rng, done0)
        if eos_id is None:
            (_, _, _, _), toks = jax.lax.scan(
                step, carry0, None, length=max_new_tokens,
            )
            return toks.T, jnp.int32(max_new_tokens)

        # eos set: early-exit once ALL rows are done (the rest of the
        # fixed-length loop would only re-emit eos padding). The token
        # buffer starts as eos, so unwritten tail columns equal what the
        # scan would have produced.
        toks0 = jnp.full((B, max_new_tokens), jnp.int32(eos_id))

        def cond(c):
            _, _, i = c
            done = c[0][3]
            return (i < max_new_tokens) & ~jnp.all(done)

        def body(c):
            carry, toks, i = c
            carry, tok = step(carry, None)
            toks = jax.lax.dynamic_update_index_in_dim(
                toks, tok, i, axis=1
            )
            return (carry, toks, i + 1)

        _, toks, steps = jax.lax.while_loop(
            cond, body, (carry0, toks0, jnp.int32(0))
        )
        return toks, steps

    return run


def beam_search(model, params, prompt, max_new_tokens: int,
                beam_size: int = 4, length_penalty: float = 0.0,
                eos_id: Optional[int] = None) -> jnp.ndarray:
    """Beam-search decoding on the KV-cache decode path.

    Standard fixed-width beam search: prefill once on the B prompt rows,
    tile each layer's cache ``beam_size``× along the batch axis, then one
    ``lax.scan`` where every step scores all ``beam_size × vocab``
    continuations per row, keeps the top ``beam_size`` by cumulative
    log-probability, and gathers the KV caches of the surviving beams'
    parents. The whole search is ONE jitted dispatch, like
    :func:`generate`.

    Args:
      length_penalty: GNMT-style α — candidates are ranked by
        ``logprob / ((5 + len) / 6) ** α``; 0 ranks by raw logprob.
      eos_id: finished beams freeze (their only continuation is ``eos``
        at zero cost), so shorter completed hypotheses compete with
        longer live ones.

    Returns:
      ``[B, T_prompt + max_new_tokens]`` int32 — each row's best beam.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2 or prompt.shape[1] < 1:
        raise ValueError(f"prompt must be [B, T>=1]; got {prompt.shape}")
    if beam_size < 1:
        raise ValueError(f"beam_size must be >= 1; got {beam_size}")
    B, Tp = prompt.shape
    if Tp + max_new_tokens > model.max_len:
        raise ValueError(
            f"prompt ({Tp}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_len={model.max_len} (the KV-cache length)"
        )
    dm = model.clone(decode=True, parent=None)
    run = _beam_fn(dm, B, max_new_tokens, beam_size, length_penalty,
                   eos_id)
    best = run({"params": params["params"]}, prompt)
    return jnp.concatenate([prompt, best], axis=1)


@functools.lru_cache(maxsize=32)
def _beam_fn(dm, B, max_new_tokens, K, length_penalty, eos_id):
    def penalize(scores, lengths):
        # GNMT: logprob / ((5 + true_hypothesis_length) / 6)^alpha —
        # lengths are PER HYPOTHESIS (frozen when a beam finishes), so
        # early-eos beams aren't over-favored by a shared step count
        if length_penalty == 0.0:
            return scores
        return scores / (
            ((5.0 + lengths.astype(jnp.float32)) / 6.0) ** length_penalty
        )

    @jax.jit
    def run(params_only, prompt):
        V = dm.vocab_size
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(
                dm.init, jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32)
            )["cache"],
        )
        logits, vs = dm.apply(
            {**params_only, "cache": cache}, prompt, mutable=["cache"]
        )
        # tile caches K× along batch: row b's beams live at rows b*K..;
        # every per-batch cache leaf (cached K/V) repeats, scalars
        # (cursors) are shared across rows already
        cache = jax.tree.map(
            lambda c: (jnp.repeat(c, K, axis=0)
                       if c.ndim > 0 and c.shape[0] == B else c),
            vs["cache"],
        )
        logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
        # beam 0 is live, the rest start at -inf so step 1 seeds K
        # DISTINCT tokens from the top of the prompt distribution
        init_scores = jnp.full((B, K), -jnp.inf).at[:, 0].set(0.0)
        done0 = jnp.zeros((B, K), bool)
        lens0 = jnp.zeros((B, K), jnp.int32)
        toks_buf = jnp.zeros((B, K, max_new_tokens), jnp.int32)

        def expand(scores, logp, done, lens, step):
            # scores [B,K] + per-beam next-token logprobs [B,K,V] ->
            # top-K flat candidates per row, ranked by length-penalized
            # score (candidate length = frozen for finished parents,
            # step+1 for live ones)
            if eos_id is not None:
                # finished beams: only eos continues, at zero cost
                only_eos = jnp.full((V,), -jnp.inf).at[eos_id].set(0.0)
                logp = jnp.where(done[..., None], only_eos, logp)
            cand_len = jnp.where(done, lens, step + 1)  # [B, K]
            total = scores[..., None] + logp  # [B, K, V]
            flat = total.reshape(B, K * V)
            flat_len = jnp.broadcast_to(
                cand_len[..., None], (B, K, V)
            ).reshape(B, K * V)
            _, idx = jax.lax.top_k(penalize(flat, flat_len), K)  # [B, K]
            parent = idx // V
            token = (idx % V).astype(jnp.int32)
            new_scores = jnp.take_along_axis(flat, idx, axis=1)
            new_lens = jnp.take_along_axis(flat_len, idx, axis=1)
            return parent, token, new_scores, new_lens

        def step(carry, i):
            cache, scores, toks_buf, done, lens, last_logp = carry
            parent, token, scores, lens = expand(
                scores, last_logp, done, lens, i
            )
            # gather surviving parents' state: global cache row b*K+parent
            rows = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
            cache = jax.tree.map(
                lambda c: (jnp.take(c, rows, axis=0)
                           if c.ndim > 0 and c.shape[0] == B * K else c),
                cache,
            )
            toks_buf = jnp.take_along_axis(
                toks_buf, parent[..., None], axis=1
            )
            toks_buf = jax.lax.dynamic_update_index_in_dim(
                toks_buf, token, i, axis=2
            )
            if eos_id is not None:
                done = jnp.take_along_axis(done, parent, axis=1)
                done = done | (token == eos_id)
            logits, vs = dm.apply(
                {**params_only, "cache": cache},
                token.reshape(B * K)[:, None], mutable=["cache"],
            )
            logp = jax.nn.log_softmax(
                logits[:, -1].astype(jnp.float32)
            ).reshape(B, K, V)
            return (vs["cache"], scores, toks_buf, done, lens, logp), None

        logp_init = jnp.broadcast_to(logp0[:, None], (B, K, V))
        (cache, scores, toks_buf, done, lens, _), _ = jax.lax.scan(
            step,
            (cache, init_scores, toks_buf, done0, lens0, logp_init),
            jnp.arange(max_new_tokens),
        )
        best = jnp.argmax(penalize(scores, lens), axis=1)
        return jnp.take_along_axis(
            toks_buf, best[:, None, None], axis=1
        )[:, 0]

    return run


@register_model("moe_lm")
class MoeLM(TransformerLM):
    """TransformerLM with Switch-MoE MLPs (expert parallelism over ``ep``).

    Same decoder skeleton; each block's dense MLP becomes a top-1-routed
    bank of ``moe_experts`` experts. Train with
    :func:`distkeras_tpu.parallel.spmd.make_moe_lm_train_step` over a
    (dp, ep) mesh — batch sharded over dp x ep jointly, experts over ep.
    """

    moe_experts: int = 8
