"""MiMo-V2.5's language model (family MiMo-V2-Flash) for the serving
path: RMSNorm, a leading dense SwiGLU layer then expert layers, and
attention layers of two kinds in one stack — full layers of few KV
heads over the whole context, sliding-window layers of more KV heads
over the last ``sliding_window`` positions with a learned sink — whose
keys are wider than their values.

The layer equations are ISSUE 34's and ``chipbench/references/
mimo_v2.py`` follows them in plain float32; this module is the program.
What it does differently from the plain form, with the same
mathematics:

- **Two kinds of cache leaf in one model.** A full layer keeps ``k [S,
  L, Hk * 192]`` and ``v [S, L, Hk * 128]`` (a position's KV heads side
  by side: whole lanes, so the pool has no padding on the chip); a
  window layer keeps a ring ``k [S, R, Hk, 192]``, ``v [S, R, Hk, 128]``
  of ``window_ring`` positions, position ``p`` at ``p % R``. Each layer
  has one ``[S]`` cursor, as every served model has. A ring entry is
  attended by the position arithmetic says it holds, never by whether it
  was written (:mod:`distkeras_tpu.ops.hybrid_attend`).
- **The attends.** Full layers walk their cache up to each row's cursor
  in a Pallas kernel (``full_attend``; ``full_decode_attend`` at one
  token a row); window layers attend their ring in XLA
  (``window_attend``). ``attend_kernel="dense"`` keeps the plain masked
  attend over every position: the parity path of the tests.
- **One chip's share of the experts**
  (:class:`~distkeras_tpu.models.blocks.RoutedExperts` with no shared
  expert and no group limit): the gate's normalisation runs over all
  chosen experts; what the absent experts would add is left out.
- **The packed mixed tick** (``live_tokens``, as ``transformer_lm``):
  norms, projections, experts and head run over the tick's live tokens
  packed to ``N`` rows, the attend over ``[S, C]``, the head on each
  row's last valid token.

Departures from the published model (the reference has the same): no
vision or audio tower and no multi-token-prediction module;
``attention_chunk_size`` is carried by the configuration and unused
(the window slides); rotary pairs channel ``i`` with ``i + 32`` of the
first 64; the value scale is applied to ``v``.

The residual stream and the norms are float32; matmul operands are
``dtype`` with float32 accumulation; router scores and logits are
float32. The serving engine reads off the class, besides the module
fields: ``tick_counters``, ``packs_live_tokens``,
:meth:`serving_refusals`, :meth:`kv_positions_fetched`,
:meth:`kv_positions_by_kind` and :meth:`cache_bytes_by_kind`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.blocks import (
    RoutedExperts, SwiGLU, _dot, _normal, expert_counter_units, live_packing,
    pack_live, rms_norm, unpack_live)
from distkeras_tpu.models.registry import register_model
from distkeras_tpu.ops import hybrid_attend, splash_prefill
from distkeras_tpu.ops.mla import rope_half


def _rope_first(x, pos, rotary_dim: int, theta: float):
    """Rotary on channels ``0 .. rotary_dim - 1`` of ``x [B, T, H, hd]``
    at ``pos [B, T]`` (half-split pairs, angle ``pos * theta ** (-2i /
    rotary_dim)``); the rest pass unrotated."""
    inv_freq = 1.0 / theta ** (
        np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim)
    return jnp.concatenate(
        [rope_half(x[..., :rotary_dim], pos, inv_freq.astype(np.float32)),
         x[..., rotary_dim:]], axis=-1)


class HybridAttention(nn.Module):
    """One attention layer of either kind: ``window`` 0 is a full layer
    (cache of ``cache_len`` positions), else a sliding-window layer
    (ring of ``cache_len`` positions) with a sink where ``sink``."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    v_head_dim: int
    rotary_dim: int
    rope_theta: float
    value_scale: float
    window: int = 0
    sink: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    cache_len: int = 0
    attend_kernel: str = "auto"

    @nn.compact
    def __call__(self, u, valid_lens=None, packing=None):
        d = u.shape[-1]
        H, Hk, dk, dv = (self.num_heads, self.num_kv_heads, self.head_dim,
                         self.v_head_dim)
        pd, dt = self.param_dtype, self.dtype
        if H % Hk:
            raise ValueError(f"num_heads={H} not divisible by the "
                             f"{Hk} KV heads of a "
                             f"{'window' if self.window else 'full'} layer")
        wq = self.param("wq", _normal(), (d, H, dk), pd)
        wk = self.param("wk", _normal(), (d, Hk, dk), pd)
        wv = self.param("wv", _normal(), (d, Hk, dv), pd)
        wo = self.param("wo", _normal(2), (H, dv, d), pd)
        sink = (self.param("sink", nn.initializers.zeros, (H,), jnp.float32)
                if self.sink else None)
        with jax.named_scope("attn_project"):
            q = _dot(u, wq, dt).astype(dt)
            k = _dot(u, wk, dt).astype(dt)
            v = (_dot(u, wv, dt) * self.value_scale).astype(dt)
            if packing is not None:
                # only the attend sees [S, C]: zeros where nothing was dealt
                q, k, v = (unpack_live(t, packing) for t in (q, k, v))
        B, T = q.shape[:2]
        if self.decode:
            L = self.cache_len
            leaf = ((B, L, Hk, dk), (B, L, Hk, dv)) if self.window else (
                (B, L, Hk * dk), (B, L, Hk * dv))
            ck = self.variable("cache", "cached_key", jnp.zeros, leaf[0], dt)
            cv = self.variable("cache", "cached_value", jnp.zeros, leaf[1],
                               dt)
            cursor = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((B,), jnp.int32))
            starts = cursor.value
        else:
            starts = jnp.zeros((B,), jnp.int32)
        pos = starts[:, None] + jnp.arange(T)[None]  # [B, T]
        with jax.named_scope("attn_project"):
            q = _rope_first(q, pos, self.rotary_dim, self.rope_theta)
            k = _rope_first(k, pos, self.rotary_dim, self.rope_theta)
        if not self.decode:
            # no cache: the sequence itself
            out = hybrid_attend.dense_attention(
                q, k, v, pos, starts, self.window or None, sink)
        else:
            with jax.named_scope("cache_update"):
                # each row's valid tokens land at its cursor (a ring's at
                # position % R); a chunk's padding is pushed past the
                # leaf and dropped
                fed = (jnp.full((B,), T, jnp.int32) if valid_lens is None
                       else valid_lens)
                at = jnp.where(jnp.arange(T)[None, :] < fed[:, None],
                               pos % L if self.window else pos, L)
                rows = jnp.arange(B)[:, None]
                ck.value = ck.value.at[rows, at].set(
                    k.reshape((B, T) + leaf[0][2:]), mode="drop")
                cv.value = cv.value.at[rows, at].set(
                    v.reshape((B, T) + leaf[1][2:]), mode="drop")
                cursor.value = starts + fed
            if self.window:
                out = hybrid_attend.window_attention(
                    q, ck.value, cv.value, starts, valid_lens, sink,
                    self.window)
            else:
                with jax.named_scope("full_attend"):
                    if hybrid_attend.resolves_to_kernel(
                            self.attend_kernel, T, H // Hk, dk, dv, L, Hk):
                        out = hybrid_attend.full_attention(
                            q, ck.value, cv.value, starts, valid_lens)
                    else:
                        out = hybrid_attend.dense_attention(
                            q, ck.value.reshape(B, L, Hk, dk),
                            cv.value.reshape(B, L, Hk, dv),
                            jnp.broadcast_to(jnp.arange(L), (B, L)), starts)
        with jax.named_scope("attn_project"):
            if packing is not None:
                out = pack_live(out, packing)
            return jax.lax.dot_general(
                out.astype(dt), wo.astype(dt),
                (((2, 3), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32)


class DecoderLayer(nn.Module):
    attn: tuple  # HybridAttention's fields as sorted items (hashable)
    ffn: tuple   # SwiGLU's, or RoutedExperts' where the layer is not dense
    dense: bool
    rms_eps: float
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, live, valid_lens=None, packing=None):
        d = x.shape[-1]
        n1 = self.param("attn_norm", nn.initializers.ones, (d,),
                        self.param_dtype)
        n2 = self.param("ffn_norm", nn.initializers.ones, (d,),
                        self.param_dtype)
        x = x + HybridAttention(**dict(self.attn), name="attn")(
            rms_norm(x, n1, self.rms_eps), valid_lens, packing)
        u = rms_norm(x, n2, self.rms_eps)
        if self.dense:
            return x + SwiGLU(**dict(self.ffn), name="mlp")(u)
        return x + RoutedExperts(**dict(self.ffn), name="moe")(u, live)


@register_model("mimo_v2_lm")
class MiMoV2LM(nn.Module):
    """Decoder-only LM of the MiMo-V2.5 architecture. Defaults are the
    published widths; ``num_layers``, the two layer patterns,
    ``experts_held`` / ``expert_rank`` and ``vocab_size`` are what a
    configuration cuts."""

    vocab_size: int = 152576
    d_model: int = 4096
    num_layers: int = 48
    num_heads: int = 64
    head_dim: int = 192
    v_head_dim: int = 128
    num_kv_heads: int = 4          # full layers
    swa_num_kv_heads: int = 8      # window layers
    sliding_window: int = 128
    # positions a window layer's ring holds: at least sliding_window +
    # the engine's prefill chunk - 1
    window_ring: int = 256
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7        # full layers
    swa_rope_theta: float = 1e4    # window layers
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # a layer's kind, 0 full and 1 window; None: the published pattern
    # (layer 0 and every sixth layer from 5 on are full). The first
    # num_layers entries are read
    hybrid_layer_pattern: Optional[Tuple[int, ...]] = None
    # 0 where a layer's FFN is dense; None: layer 0 alone is
    moe_layer_freq: Optional[Tuple[int, ...]] = None
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # this chip's share of each expert layer: experts
    # expert_rank * experts_held .. + experts_held - 1 (None: all)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    rms_eps: float = 1e-5
    max_len: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    # per-row cache cursors: the only decode mode this model has
    slot_cursor: bool = False
    cache_dtype: str = "model"
    # accepted because the engine hands it to every model it clones
    prefill_kernel: str = "auto"
    # 'auto' (the kernel on a TPU where the shape tiles), 'pallas'
    # (force; interpret mode off the chip), 'dense' (the parity path)
    attend_kernel: str = "auto"
    expert_tile: int = 128   # rows a step of the grouped matmul runs

    # sown into the "counters" collection by every expert layer; the
    # serving tick returns their sums with the tick's tokens
    tick_counters = ("routed_here", "routed_total", "expert_rows_computed",
                     "experts_read")
    # name -> (the name the host keeps it under, times what)
    tick_counter_units = property(expert_counter_units)
    # a decode apply takes ``live_tokens``: the dropless experts give
    # each token what they would give it alone, so leaving a tick's
    # padding out changes no result
    packs_live_tokens = True

    def __post_init__(self):
        # a configuration file hands lists; a module is hashed by its
        # fields (the engine keys its compiled ticks on it)
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            value = getattr(self, name)
            if isinstance(value, list):
                object.__setattr__(self, name, tuple(value))
        super().__post_init__()

    def layer_kinds(self) -> Tuple[str, ...]:
        """``"full"`` or ``"window"`` for each of ``num_layers``."""
        pattern = self.hybrid_layer_pattern
        if pattern is None:
            pattern = [int(not (i == 0 or i % 6 == 5))
                       for i in range(self.num_layers)]
        if len(pattern) < self.num_layers:
            raise ValueError(
                f"hybrid_layer_pattern names {len(pattern)} layers of "
                f"{self.num_layers}")
        return tuple("window" if kind else "full"
                     for kind in pattern[:self.num_layers])

    def serving_refusals(self, **options):
        """Raise for each :class:`ServingEngine` option this model does
        not have yet (the engine calls this with what it was given),
        rather than run wrong."""
        lacks = {
            "paged": "a paged cache over two kinds of layer: one block "
                     "table and one pool chain (serving/kvpool.py) serve "
                     "all layers, and a window layer keeps a ring, not a "
                     "chain",
            "draft": "speculative decoding: a rejected suffix is undone "
                     "by rewinding the cursor, and a ring has by then "
                     "overwritten what the rewound window needs; the "
                     "multi-token-prediction module that would draft is "
                     "not built",
            "mesh": "tensor parallelism: 4 KV heads a full layer and 8 a "
                    "window layer do not split one way; replicas take "
                    "batches",
            "multi_step": "multi-step decode windows: the expert layers' "
                          "counters are returned once a tick",
            "monolithic_prefill": "whole-prompt prefill (prefill_chunk="
                                  "None): a prompt longer than the ring "
                                  "overwrites keys its own first tokens "
                                  "attend",
        }
        for name, why in lacks.items():
            if options.get(name):
                raise ValueError(
                    f"mimo_v2_lm cannot be served with {name}: it lacks "
                    f"{why}")
        self._refuse_cache_dtype()
        chunk = options.get("prefill_chunk") or 1
        if self.window_ring < self.sliding_window + chunk - 1:
            raise ValueError(
                f"mimo_v2_lm: window_ring={self.window_ring} cannot hold a "
                f"window of {self.sliding_window} behind a prefill chunk "
                f"of {chunk}")

    def _refuse_cache_dtype(self):
        if self.cache_dtype != "model":
            raise ValueError(
                f"mimo_v2_lm keeps both kinds of cache in the model's "
                f"dtype; cache_dtype={self.cache_dtype!r} (an int8 cache "
                f"and its scales, in a ring as well) is not built")

    def kv_positions_fetched(self, starts, valid, chunk: int) -> int:
        """Cache positions ONE full layer's attend of a tick copies in
        (beside the engine's ``cache_positions``, one layer's ``S x
        max_len``): the kernel's walk where the shape resolves to it,
        else every position of every row."""
        H, Hk = self.num_heads, self.num_kv_heads
        if hybrid_attend.resolves_to_kernel(
                self.attend_kernel, chunk, H // Hk, self.head_dim,
                self.v_head_dim, self.max_len, Hk):
            return splash_prefill.fetched_positions(starts, valid,
                                                    self.max_len)
        return len(starts) * self.max_len

    def kv_positions_by_kind(self, starts, valid, chunk: int) -> dict:
        """K/V positions a tick's attends copy in, by kind of layer,
        summed over the layers of the kind: the full layers' walks, and
        every window layer's whole ring of every row (XLA reads it all)."""
        kinds = self.layer_kinds()
        return {
            "full_key_positions": kinds.count("full")
            * self.kv_positions_fetched(starts, valid, chunk),
            "window_key_positions": kinds.count("window") * len(starts)
            * self.window_ring}

    def cache_bytes_by_kind(self, cache) -> dict:
        """Bytes of the ``cache`` collection (as the engine holds it)
        by kind of layer."""
        out = {"full": 0, "window": 0}
        for i, kind in enumerate(self.layer_kinds()):
            out[kind] += sum(x.nbytes for x in jax.tree.leaves(
                cache[f"layers_{i}"]))
        return out

    @nn.compact
    def __call__(self, tokens, train: bool = False, block_tables=None,
                 seq_lens=None, valid_lens=None,
                 live_tokens: Optional[int] = None):
        """``live_tokens`` (a static count ``N``, with ``valid_lens`` on
        a decode module) is the packed form of a mixed tick, as in
        :meth:`TransformerLM.__call__`: the result is ``[S, 1, vocab]``,
        each row's last valid token's logits."""
        if block_tables is not None or seq_lens is not None:
            raise ValueError("mimo_v2_lm has no paged cache")
        self._refuse_cache_dtype()
        if self.decode and not self.slot_cursor:
            raise ValueError("mimo_v2_lm decodes with per-row cursors "
                             "only (slot_cursor=True)")
        if live_tokens is not None and (valid_lens is None
                                        or not self.decode):
            raise ValueError("live_tokens (the packed mixed tick) needs "
                             "valid_lens on a decode module")
        S, C = tokens.shape
        kinds = self.layer_kinds()
        dense = (tuple(i == 0 for i in range(self.num_layers))
                 if self.moe_layer_freq is None
                 else tuple(not f for f in self.moe_layer_freq))
        held = (self.n_routed_experts if self.experts_held is None
                else self.experts_held)
        attn = dict(
            num_heads=self.num_heads, head_dim=self.head_dim,
            v_head_dim=self.v_head_dim,
            rotary_dim=int(self.head_dim * self.partial_rotary_factor),
            value_scale=self.attention_value_scale, dtype=self.dtype,
            param_dtype=self.param_dtype, decode=self.decode,
            attend_kernel=self.attend_kernel)
        by_kind = {
            "full": dict(attn, num_kv_heads=self.num_kv_heads,
                         rope_theta=self.rope_theta, window=0,
                         sink=self.add_full_attention_sink_bias,
                         cache_len=self.max_len if self.decode else 0),
            "window": dict(attn, num_kv_heads=self.swa_num_kv_heads,
                           rope_theta=self.swa_rope_theta,
                           window=self.sliding_window,
                           sink=self.add_swa_attention_sink_bias,
                           cache_len=self.window_ring if self.decode
                           else 0)}
        moe = dict(
            n_routed_experts=self.n_routed_experts, experts_held=held,
            expert_rank=self.expert_rank,
            num_experts_per_tok=self.num_experts_per_tok,
            n_group=self.n_group, topk_group=self.topk_group,
            routed_scaling_factor=self.routed_scaling_factor,
            width=self.moe_intermediate_size, n_shared_experts=0,
            dtype=self.dtype, param_dtype=self.param_dtype,
            expert_tile=self.expert_tile)
        mlp = dict(width=self.intermediate_size, dtype=self.dtype,
                   param_dtype=self.param_dtype)
        packing = None
        if live_tokens is not None:
            packing = live_packing(valid_lens, C, live_tokens)
            tokens = tokens.reshape(-1)[packing.idx][None]  # [1, N]
            live = (jnp.arange(live_tokens) < valid_lens.sum())[None]
        else:
            live = (jnp.ones((S, C), bool) if valid_lens is None
                    else jnp.arange(C)[None, :] < valid_lens[:, None])
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     name="embed")(tokens).astype(jnp.float32)
        for i in range(self.num_layers):
            x = DecoderLayer(
                tuple(sorted(by_kind[kinds[i]].items())),
                tuple(sorted((mlp if dense[i] else moe).items())),
                dense[i], self.rms_eps, self.param_dtype,
                name=f"layers_{i}")(x, live, valid_lens, packing)
        if packing is not None:
            # [S, 1, d]: each row's last valid token, where its packed
            # run ends
            x = x[0][jnp.maximum(jnp.cumsum(valid_lens) - 1, 0)][:, None]
        norm = self.param("norm", nn.initializers.ones,
                          (self.d_model,), self.param_dtype)
        head = self.param("head", _normal(),
                          (self.d_model, self.vocab_size),
                          self.param_dtype)
        # untied head, float32 logits
        return _dot(rms_norm(x, norm, self.rms_eps), head, self.dtype)
