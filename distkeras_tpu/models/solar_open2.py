"""Solar-Open2-250B's language model for the serving path: RMSNorm, an
expert layer in every block, and mixing layers of two kinds in one
stack — softmax GQA layers with no positional signal and an output gate
(``gqa_layers``: every fourth), and between them KDA layers (Kimi Delta
Attention, the gated delta rule with a decay per key channel) that keep
**a recurrent state of fixed size** in place of keys stored by position.

The layer equations are ISSUE 36's and ``chipbench/references/
solar_open2.py`` follows them in plain float32, a KDA layer token by
token; this module is the program. What it does differently from the
plain form, with the same mathematics:

- **A cache leaf that is not indexed by position.** A GQA layer keeps
  ``k``, ``v [S, L, Hk * 128]`` (a position's KV heads side by side, the
  layout :func:`~distkeras_tpu.ops.hybrid_attend.full_attention`
  walks); a KDA layer keeps ``state [S, H, 128, 128]`` float32 and
  ``conv_tail [S, 3, 3 * H * 128]``, the last three inputs of its short
  convolutions. Each layer has one ``[S]`` cursor, as every served model
  has. The engine parks a slot by zeroing its cursors and nothing else,
  so a KDA layer **reads state and tail as zero where the row's cursor
  is 0** (by arithmetic on the cursor, never by whether the leaf was
  cleared), leaves both untouched for a row that was dealt nothing, and
  a chunk's padding changes neither.
- **The work follows the tokens dealt**
  (:func:`~distkeras_tpu.ops.delta_rule.delta_ragged`): the rows of a
  tick that fed one token take the recurrent step, the rows that fed
  more the chunk form, one row a trip.
- **One chip's share of the experts**
  (:class:`~distkeras_tpu.models.blocks.RoutedExperts` with the shared
  expert and no group limit).
- **The packed mixed tick** (``live_tokens``, as ``mimo_v2_lm``):
  norms, projections, convolutions, gates, experts and head run over the
  tick's live tokens packed to ``N`` rows; only the full attend sees
  ``[S, C]``, and the delta rule sees the rows by their runs in the
  packed order.

Departures from the published model (the reference has the same):
``intermediate_size`` has no dense layer to apply to, ``rope_theta`` and
``partial_rotary_factor`` no rotary (``use_rope`` false): carried by the
configuration and unused. Assumed, where the config has no key: the
GQA gate is elementwise from the layer's normed input before ``wo``; the
decay's and the output gate's projections are low-rank pairs
(``kda_use_full_proj`` false); sigmoid routing with a selection-only
bias.

The residual stream and the norms are float32; matmul operands are
``dtype`` with float32 accumulation; the convolutions, the delta rule
and its state, router scores and logits are float32. The serving engine
reads off the class, besides the module fields: ``tick_counters``,
``packs_live_tokens``, :meth:`serving_refusals`,
:meth:`kv_positions_fetched`, :meth:`kv_positions_by_kind` and
:meth:`cache_bytes_by_kind`.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.blocks import (
    RoutedExperts, _dot, _normal, expert_counter_units, live_packing,
    pack_live, rms_norm, unpack_live)
from distkeras_tpu.models.registry import register_model
from distkeras_tpu.ops import delta_rule, hybrid_attend, splash_prefill

NORM_EPS = 1e-6  # of q's and k's 2-norm in a KDA layer


class GatedAttention(nn.Module):
    """A softmax GQA layer with no positional signal and an elementwise
    sigmoid gate on the attend's output."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    cache_len: int = 0
    attend_kernel: str = "auto"

    @nn.compact
    def __call__(self, u, valid_lens=None, packing=None):
        d = u.shape[-1]
        H, Hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        pd, dt = self.param_dtype, self.dtype
        if H % Hk:
            raise ValueError(f"num_heads={H} not divisible by the {Hk} KV "
                             f"heads of a GQA layer")
        wq = self.param("wq", _normal(), (d, H, hd), pd)
        wk = self.param("wk", _normal(), (d, Hk, hd), pd)
        wv = self.param("wv", _normal(), (d, Hk, hd), pd)
        wg = self.param("wg", _normal(), (d, H, hd), pd)
        wo = self.param("wo", _normal(2), (H, hd, d), pd)
        with jax.named_scope("attn_project"):
            q = _dot(u, wq, dt).astype(dt)
            k = _dot(u, wk, dt).astype(dt)
            v = _dot(u, wv, dt).astype(dt)
            gate = jax.nn.sigmoid(_dot(u, wg, dt))
            if packing is not None:
                # only the attend sees [S, C]: zeros where nothing was dealt
                q, k, v = (unpack_live(t, packing) for t in (q, k, v))
        B, T = q.shape[:2]
        if not self.decode:
            starts = jnp.zeros((B,), jnp.int32)
            out = hybrid_attend.dense_attention(
                q, k, v, jnp.broadcast_to(jnp.arange(T), (B, T)), starts)
        else:
            L = self.cache_len
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (B, L, Hk * hd), dt)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (B, L, Hk * hd), dt)
            cursor = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((B,), jnp.int32))
            starts = cursor.value
            with jax.named_scope("cache_update"):
                # each row's valid tokens land at its cursor; a chunk's
                # padding is pushed past the leaf and dropped
                fed = (jnp.full((B,), T, jnp.int32) if valid_lens is None
                       else valid_lens)
                at = jnp.where(jnp.arange(T)[None, :] < fed[:, None],
                               starts[:, None] + jnp.arange(T)[None], L)
                rows = jnp.arange(B)[:, None]
                ck.value = ck.value.at[rows, at].set(
                    k.reshape(B, T, Hk * hd), mode="drop")
                cv.value = cv.value.at[rows, at].set(
                    v.reshape(B, T, Hk * hd), mode="drop")
                cursor.value = starts + fed
            with jax.named_scope("full_attend"):
                if hybrid_attend.resolves_to_kernel(
                        self.attend_kernel, T, H // Hk, hd, hd, L, Hk):
                    out = hybrid_attend.full_attention(
                        q, ck.value, cv.value, starts, valid_lens)
                else:
                    out = hybrid_attend.dense_attention(
                        q, ck.value.reshape(B, L, Hk, hd),
                        cv.value.reshape(B, L, Hk, hd),
                        jnp.broadcast_to(jnp.arange(L), (B, L)), starts)
        with jax.named_scope("attn_project"):
            if packing is not None:
                out = pack_live(out, packing)
            return jax.lax.dot_general(
                (out * gate).astype(dt), wo.astype(dt),
                (((2, 3), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32)


class _Runs(NamedTuple):
    """Where each row's tokens lie among a call's tokens laid flat
    (``[M]``: the packed order of a mixed tick, else row-major)."""

    slot: jnp.ndarray    # [M]: the row a flat token belongs to
    offset: jnp.ndarray  # [M]: its place among the row's tokens
    first: jnp.ndarray   # [S]: the flat index of the row's first token


def _runs(rows: int, chunk: int, valid_lens, packing) -> _Runs:
    if packing is None:
        flat = jnp.arange(rows * chunk)
        return _Runs(flat // chunk, flat % chunk, jnp.arange(rows) * chunk)
    return _Runs(packing.idx // chunk, packing.idx % chunk,
                 jnp.cumsum(valid_lens) - valid_lens)


def _short_conv(z, weights, tail, runs: _Runs):
    """The causal depthwise convolution of ``z [M, ch]`` (flat tokens)
    with ``weights [W, ch]``: a token's ``W - 1`` predecessors are the
    tokens before it in its own row's run and, before the run's start,
    the row's ``tail [S, W - 1, ch]`` (the last inputs of earlier
    calls, already read as zero where the row starts at position 0)."""
    W = weights.shape[0]
    zf = z.astype(jnp.float32)
    tail = tail.astype(jnp.float32)
    y = weights[W - 1] * zf
    for back in range(1, W):
        inside = runs.offset >= back
        from_tail = tail[runs.slot, jnp.clip(
            W - 1 + runs.offset - back, 0, W - 2)]
        y = y + weights[W - 1 - back] * jnp.where(
            inside[:, None], jnp.roll(zf, back, axis=0), from_tail)
    return y


def _next_tail(z, tail, raw_tail, runs: _Runs, valid_lens):
    """The tail after this call: the last ``W - 1`` inputs of each row
    that fed a token (from this call's ``z`` where it reaches that far
    back, else from ``tail`` as read); ``raw_tail`` untouched for a row
    that fed none."""
    keep = tail.shape[1]
    rel = valid_lens[:, None] - keep + jnp.arange(keep)[None]  # [S, W - 1]
    mine = z[jnp.maximum(runs.first[:, None] + rel, 0)]
    older = jnp.take_along_axis(
        tail, jnp.clip(valid_lens[:, None] + jnp.arange(keep)[None], 0,
                       keep - 1)[..., None], axis=1)
    new = jnp.where((rel >= 0)[..., None], mine, older)
    return jnp.where((valid_lens > 0)[:, None, None], new, raw_tail)


class KimiDeltaAttention(nn.Module):
    """A KDA layer: short convolutions on q, k and v, a decay per key
    channel, ``beta`` in (0, 2), the gated delta rule over a recurrent
    state, a normed and gated output."""
    num_heads: int
    head_dim: int
    conv_size: int
    gate_rank: int
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    sequence_chunk: int = 64  # of the chunk form where there is no cache

    @nn.compact
    def __call__(self, u, valid_lens=None, packing=None):
        d = u.shape[-1]
        H, hd, W, r = (self.num_heads, self.head_dim, self.conv_size,
                       self.gate_rank)
        pd, dt = self.param_dtype, self.dtype
        f32 = jnp.float32
        wq, wk, wv = (self.param(n, _normal(), (d, H, hd), pd)
                      for n in ("wq", "wk", "wv"))
        conv = jnp.concatenate([
            self.param(n, _normal(), (W, H, hd), pd).astype(f32)
            for n in ("conv_q", "conv_k", "conv_v")], 1).reshape(W, -1)
        wa1 = self.param("wa1", _normal(), (d, r), pd)
        wa2 = self.param("wa2", _normal(), (r, H, hd), pd)
        a_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H, hd), f32)
        wb = self.param("wb", _normal(), (d, H), pd)
        wg1 = self.param("wg1", _normal(), (d, r), pd)
        wg2 = self.param("wg2", _normal(), (r, H, hd), pd)
        out_norm = self.param("out_norm", nn.initializers.ones, (hd,), pd)
        wo = self.param("wo", _normal(2), (H, hd, d), pd)
        # rows of the cache, tokens a row (a packed tick's u is [1, N, d])
        S, C = u.shape[:2] if packing is None else packing.inv.shape
        if valid_lens is None:
            valid_lens = jnp.full((S,), C, jnp.int32)
        if self.decode:
            state = self.variable("cache", "state", jnp.zeros,
                                  (S, H, hd, hd), f32)
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (S, W - 1, 3 * H * hd), dt)
            cursor = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((S,), jnp.int32))
            fresh = cursor.value == 0
            tail_read = jnp.where(fresh[:, None, None], 0, tail.value)
        else:
            tail_read = jnp.zeros((S, W - 1, 3 * H * hd), dt)
        runs = _runs(S, C, valid_lens, packing)
        with jax.named_scope("delta_project"):
            x = u.reshape(-1, d)  # [M, d]: the tokens laid flat
            z = jnp.concatenate(
                [_dot(x, w, dt).reshape(-1, H * hd) for w in (wq, wk, wv)],
                1).astype(dt)
            y = jax.nn.silu(_short_conv(z, conv, tail_read, runs))
            q, k, v = (y[:, i * H * hd:(i + 1) * H * hd].reshape(-1, H, hd)
                       for i in range(3))
            q, k = (t * jax.lax.rsqrt(
                jnp.sum(t * t, -1, keepdims=True) + NORM_EPS) for t in (q, k))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                _dot(_dot(x, wa1, dt), wa2, dt) + dt_bias)
            beta = 2.0 * jax.nn.sigmoid(_dot(x, wb, dt))
            gate = jax.nn.sigmoid(_dot(_dot(x, wg1, dt), wg2, dt))
            if self.decode:
                tail.value = _next_tail(z, tail_read, tail.value, runs,
                                        valid_lens)
        if self.decode:
            o, state.value = delta_rule.delta_ragged(
                state.value, q, k, v, g, beta, runs.first, valid_lens,
                fresh, C)
            cursor.value = cursor.value + valid_lens
        else:
            o = delta_rule.delta_sequence(
                *(t.reshape((S, C) + t.shape[1:])
                  for t in (q, k, v, g, beta)),
                chunk=self.sequence_chunk).reshape(-1, H, hd)
        with jax.named_scope("delta_project"):
            o = rms_norm(o, out_norm, self.rms_eps) * gate
            out = jax.lax.dot_general(
                o.astype(dt), wo.astype(dt), (((1, 2), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32)
            return out.reshape(u.shape)


class DecoderLayer(nn.Module):
    mixer: tuple  # GatedAttention's or KimiDeltaAttention's fields, sorted
    moe: tuple    # RoutedExperts' fields, sorted
    gqa: bool
    rms_eps: float
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, live, valid_lens=None, packing=None):
        d = x.shape[-1]
        n1 = self.param("attn_norm", nn.initializers.ones, (d,),
                        self.param_dtype)
        n2 = self.param("ffn_norm", nn.initializers.ones, (d,),
                        self.param_dtype)
        mix = (GatedAttention(**dict(self.mixer), name="attn") if self.gqa
               else KimiDeltaAttention(**dict(self.mixer), name="kda"))
        x = x + mix(rms_norm(x, n1, self.rms_eps), valid_lens, packing)
        return x + RoutedExperts(**dict(self.moe), name="moe")(
            rms_norm(x, n2, self.rms_eps), live)


@register_model("solar_open2_lm")
class SolarOpen2LM(nn.Module):
    """Decoder-only LM of the Solar-Open2 architecture. Defaults are the
    published widths; ``num_layers``, ``gqa_layers``, ``experts_held`` /
    ``expert_rank`` and ``vocab_size`` are what a configuration cuts."""

    vocab_size: int = 196608
    d_model: int = 4096
    num_layers: int = 48
    num_heads: int = 64            # GQA layers
    head_dim: int = 128
    num_kv_heads: int = 8
    # the layers that are softmax GQA; None: the published pattern
    # (gqa_interval 3: layer 0 and every fourth). The others are KDA
    gqa_layers: Optional[Tuple[int, ...]] = None
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_gate_rank: int = 128       # of the decay's and the gate's pairs
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
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
    # a decode apply takes ``live_tokens``: experts and delta rule give
    # each token what they would give it alone in its row's run, so
    # leaving a tick's padding out changes no result
    packs_live_tokens = True

    def __post_init__(self):
        # a configuration file hands a list; a module is hashed by its
        # fields (the engine keys its compiled ticks on it)
        if isinstance(self.gqa_layers, list):
            object.__setattr__(self, "gqa_layers", tuple(self.gqa_layers))
        super().__post_init__()

    def layer_kinds(self) -> Tuple[str, ...]:
        """``"full"`` (a GQA layer: a full-length K/V cache) or
        ``"state"`` (a KDA layer: a recurrent state) for each of
        ``num_layers``."""
        gqa = (range(0, self.num_layers, 4) if self.gqa_layers is None
               else self.gqa_layers)
        return tuple("full" if i in gqa else "state"
                     for i in range(self.num_layers))

    def serving_refusals(self, **options):
        """Raise for each :class:`ServingEngine` option this model does
        not have yet (the engine calls this with what it was given),
        rather than run wrong."""
        lacks = {
            "paged": "a paged cache beside a recurrent state: a block "
                     "table (serving/kvpool.py) maps positions to blocks, "
                     "and a KDA layer's state has no positions; prefix "
                     "reuse would need the state at the prefix's end",
            "draft": "speculative decoding: a rejected suffix is undone "
                     "by rewinding the cursor, and a token that entered a "
                     "recurrent state cannot be taken out of it",
            "mesh": "tensor parallelism: the program splits no heads (8 "
                    "KV heads a GQA layer, 64 states a KDA layer); "
                    "replicas take batches",
            "multi_step": "multi-step decode windows: the expert layers' "
                          "counters are returned once a tick",
            "monolithic_prefill": "whole-prompt prefill (prefill_chunk="
                                  "None): it runs a B=1 decode module with "
                                  "a scalar cursor, one program a prompt "
                                  "length; this model decodes with per-row "
                                  "cursors only",
        }
        for name, why in lacks.items():
            if options.get(name):
                raise ValueError(
                    f"solar_open2_lm cannot be served with {name}: it "
                    f"lacks {why}")
        self._refuse_cache_dtype()

    def _refuse_cache_dtype(self):
        if self.cache_dtype != "model":
            raise ValueError(
                f"solar_open2_lm keeps K/V in the model's dtype and the "
                f"recurrent state in float32; cache_dtype="
                f"{self.cache_dtype!r} (an int8 cache and its scales, a "
                f"quantised state) is not built")

    def kv_positions_fetched(self, starts, valid, chunk: int) -> int:
        """Cache positions ONE GQA layer's attend of a tick copies in
        (beside the engine's ``cache_positions``, one layer's ``S x
        max_len``): the kernel's walk where the shape resolves to it,
        else every position of every row."""
        H, Hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        if hybrid_attend.resolves_to_kernel(
                self.attend_kernel, chunk, H // Hk, hd, hd, self.max_len,
                Hk):
            return splash_prefill.fetched_positions(starts, valid,
                                                    self.max_len)
        return len(starts) * self.max_len

    def kv_positions_by_kind(self, starts, valid, chunk: int) -> dict:
        """What a tick's mixers touch, by kind of layer and summed over
        the layers of the kind: the K/V positions the GQA layers' attends
        copy in; the rows whose state took the recurrent step (they fed
        exactly one token); the tokens of the rows that fed more, and
        the positions the chunk form ran over for them (a whole chunk,
        padded to a power of two, a row)."""
        kinds = self.layer_kinds()
        valid = np.asarray(valid)
        more = valid > 1
        width = 1 << (chunk - 1).bit_length()
        n = kinds.count("state")
        return {
            "full_key_positions": kinds.count("full")
            * self.kv_positions_fetched(starts, valid, chunk),
            "state_rows_stepped": n * int((valid == 1).sum()),
            "chunk_positions_live": n * int(valid[more].sum()),
            "chunk_positions_computed": n * int(more.sum()) * width}

    def cache_bytes_by_kind(self, cache) -> dict:
        """Bytes of the ``cache`` collection (as the engine holds it)
        by kind of layer: ``full`` the GQA layers' K and V, ``state``
        the KDA layers' states and convolution tails."""
        out = {"full": 0, "state": 0}
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
            raise ValueError("solar_open2_lm has no paged cache")
        self._refuse_cache_dtype()
        if self.decode and not self.slot_cursor:
            raise ValueError("solar_open2_lm decodes with per-row cursors "
                             "only (slot_cursor=True)")
        if live_tokens is not None and (valid_lens is None
                                        or not self.decode):
            raise ValueError("live_tokens (the packed mixed tick) needs "
                             "valid_lens on a decode module")
        S, C = tokens.shape
        kinds = self.layer_kinds()
        held = (self.n_routed_experts if self.experts_held is None
                else self.experts_held)
        common = dict(dtype=self.dtype, param_dtype=self.param_dtype,
                      decode=self.decode)
        by_kind = {
            "full": dict(common, num_heads=self.num_heads,
                         num_kv_heads=self.num_kv_heads,
                         head_dim=self.head_dim,
                         cache_len=self.max_len if self.decode else 0,
                         attend_kernel=self.attend_kernel),
            "state": dict(common, num_heads=self.kda_num_heads,
                          head_dim=self.kda_head_dim,
                          conv_size=self.short_conv_kernel_size,
                          gate_rank=self.kda_gate_rank,
                          rms_eps=self.rms_eps)}
        moe = dict(
            n_routed_experts=self.n_routed_experts, experts_held=held,
            expert_rank=self.expert_rank,
            num_experts_per_tok=self.num_experts_per_tok,
            n_group=1, topk_group=1,
            routed_scaling_factor=self.routed_scaling_factor,
            width=self.moe_intermediate_size,
            n_shared_experts=self.n_shared_experts,
            dtype=self.dtype, param_dtype=self.param_dtype,
            expert_tile=self.expert_tile)
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
                tuple(sorted(moe.items())), kinds[i] == "full",
                self.rms_eps, self.param_dtype,
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
