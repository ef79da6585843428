"""DeepSeek-V3.2-Exp's language model for the serving path: RMSNorm,
leading dense SwiGLU layers then expert layers, multi-head latent
attention (MLA) with the lightning indexer's top-k selection, and
group-limited sigmoid routing over all experts of which this chip holds
a share.

The layer equations are ISSUE 28's and ``chipbench/references/
deepseek_v32.py`` follows them in plain float32; this module is the
program. What it does differently from the plain form, with the same
mathematics:

- **Absorbed attention.** The cache holds per position one latent entry
  ``[c_kv | rope(k_r)]`` (``kv_lora_rank + qk_rope_head_dim`` values,
  in two leaves) and one index key; ``W_ukv``'s key half is folded into
  the query and its value half into the output, so every tick kind
  attends the latent itself: one shared 576-wide key under all heads
  (:mod:`distkeras_tpu.ops.mla`).
- **Selection by threshold.** The positions a query may attend are
  those whose index score is at least the query's ``index_topk``-th
  largest, found exactly by a search on the score's bits.
- **One chip's share of the experts.** The router scores all
  ``n_routed_experts``; the layer holds ``experts_held`` of them
  (``expert_rank`` says which) and computes their part by a dropless
  grouped matmul (:func:`distkeras_tpu.ops.moe.dropless_held_experts`).
  The gate's normalisation runs over all chosen experts; the shared
  expert is whole; what the absent experts would add is left out.

Departures from the published model (the reference has the same): the
indexer's FP8 quantisation and the Hadamard rotation before it are left
out (an orthogonal rotation changes no score in exact arithmetic); rope
pairs channel ``i`` with ``i + half`` in MLA and indexer alike (the
published checkpoint interleaves MLA's pairs: a fixed permutation of
``wq_b``'s and ``wkv_a``'s rope columns at load); no multi-token
prediction module.

Cache leaves a layer (collection ``cache``, decode mode): ``latent [S,
L, 512]`` (the compressed values), ``rope_key [S, 64, L]`` (the rotated
key half, positions minor), ``index_key [S, L, 128]`` and the cursor
``cache_index [S]``; every minor axis is whole 128-lane groups, which
the chip stores as declared (see :meth:`LatentAttention.cache`). Rope
is applied at each row's own cursor. The residual stream and the
norms are float32; matmul operands are ``dtype`` with float32
accumulation; router scores and logits are float32.

- **The packed mixed tick** (``live_tokens = S * C``): a ``[S, C]``
  tick's live tokens are packed to the front
  (:func:`~distkeras_tpu.models.blocks.live_packing`) and every
  per-token layer runs over the blocks of 512 packed rows that hold one
  (:func:`~distkeras_tpu.models.blocks.map_live_blocks`: a loop whose
  trip count the device derives from ``valid_lens``). The modules'
  parameters are declared in ``setup`` so that the per-token halves are
  callable a block at a time; the walk reads its queries where they lie
  packed and leaves its results there. Called without ``live_tokens``
  (the ``[S, 1]`` tick, a full forward) the model is the program it was.

The serving engine reads four things off the class besides the module
fields: ``tick_counters`` (names sown into the ``counters`` collection,
returned with a tick's tokens), ``packs_live_tokens`` with
``live_block_rows`` (the packed tick, by blocks) and
:meth:`serving_refusals`.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.blocks import (  # noqa: F401 (re-exported)
    RoutedExperts, RoutedExpertsByPart, SwiGLU, _dot, _normal,
    expert_counter_units, live_block_rows, live_packing, map_live_blocks,
    pack_live, rms_norm, unpack_live)
from distkeras_tpu.models.registry import register_model
from distkeras_tpu.ops import mla


class LatentAttention(nn.Module):
    """MLA with the lightning indexer; see the module docstring. The
    per-token halves (:meth:`project` before the attend, :meth:`output`
    after it) are callable apart from the walk over the cache
    (:meth:`attend`), so that a packed mixed tick can run them a block
    of live rows at a time."""
    d_model: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    inv_freq: tuple  # YaRN's, one per rope pair
    softmax_scale: float
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    cache_len: int = 0
    kv_tile: int = 512

    def setup(self):
        d, H, R = self.d_model, self.num_heads, self.kv_lora_rank
        rope, nope = self.qk_rope_head_dim, self.qk_nope_head_dim
        J, Di, pd = self.index_n_heads, self.index_head_dim, self.param_dtype
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        self.wq_a = self.param("wq_a", _normal(), (d, self.q_lora_rank), pd)
        self.q_norm = self.param("q_norm", ones, (self.q_lora_rank,), pd)
        self.wq_b = self.param("wq_b", _normal(),
                               (self.q_lora_rank, H, nope + rope), pd)
        self.wkv_a = self.param("wkv_a", _normal(), (d, R + rope), pd)
        self.kv_norm = self.param("kv_norm", ones, (R,), pd)
        self.wkv_b = self.param("wkv_b", _normal(),
                                (R, H, nope + self.v_head_dim), pd)
        self.wo = self.param("wo", _normal(2), (H, self.v_head_dim, d), pd)
        self.iq_b = self.param("index_wq_b", _normal(),
                               (self.q_lora_rank, J, Di), pd)
        self.ik_w = self.param("index_wk", _normal(), (d, Di), pd)
        self.ik_scale = self.param("index_k_norm_scale", ones, (Di,), pd)
        self.ik_bias = self.param("index_k_norm_bias", zeros, (Di,), pd)
        self.iw = self.param("index_weights_proj", _normal(), (d, J), pd)

    @nn.compact
    def cache(self, B: int):
        """The decode cache's four leaves for ``B`` rows. A position's
        latent entry lies in two, each with whole 128-lane groups minor:
        the chip pads no minor axis, so one leaf of 512 + 64 it stored
        positions-minor, and every layer's walk copied the pool into the
        layout it reads and back. The 64 rope channels are stored
        positions-minor on purpose: a tile of them is the score's ``[d,
        t]`` operand as it lies (:func:`mla.write_positions_minor`
        writes it)."""
        L, dt = self.cache_len, self.dtype
        return (
            self.variable("cache", "latent", jnp.zeros,
                          (B, L, self.kv_lora_rank), dt),
            self.variable("cache", "rope_key", jnp.zeros,
                          (B, self.qk_rope_head_dim, L), dt),
            self.variable("cache", "index_key", jnp.zeros,
                          (B, L, self.index_head_dim), dt),
            self.variable("cache", "cache_index",
                          lambda: jnp.zeros((B,), jnp.int32)))

    def _starts(self, B: int):
        return (self.cache(B)[-1].value if self.decode
                else jnp.zeros((B,), jnp.int32))

    def positions(self, B: int, T: int):
        """``[B, T]``: the absolute position of each token of a ``[B,
        T]`` call, from each row's own cursor."""
        return self._starts(B)[:, None] + jnp.arange(T)[None]

    def projected(self):
        """Trailing shape and dtype of each of :meth:`project`'s five
        results."""
        D = self.kv_lora_rank + self.qk_rope_head_dim
        J, dt = self.index_n_heads, self.dtype
        return (((self.num_heads, D), dt), ((D,), dt),
                ((J, self.index_head_dim), dt),
                ((self.index_head_dim,), dt), ((J,), jnp.float32))

    def project(self, u, pos):
        """Per token (``u [..., T, d]`` at ``pos [..., T]``): the
        absorbed query, the latent entry, the index query, the index key
        and the index heads' weights."""
        R, rope, nope = (self.kv_lora_rank, self.qk_rope_head_dim,
                         self.qk_nope_head_dim)
        J, Di, dt = self.index_n_heads, self.index_head_dim, self.dtype
        inv_freq = np.asarray(self.inv_freq, np.float32)
        with jax.named_scope("mla_project"):
            cq = rms_norm(_dot(u, self.wq_a, dt), self.q_norm, self.rms_eps)
            q = _dot(cq, self.wq_b, dt).astype(dt)  # [..., H, nope + rope]
            q_rope = mla.rope_half(q[..., nope:], pos, inv_freq)
            # the key half of W_ukv folded into the query
            q_lat = jnp.einsum("...hn,rhn->...hr", q[..., :nope],
                               self.wkv_b[..., :nope].astype(dt),
                               preferred_element_type=jnp.float32)
            q_full = jnp.concatenate([q_lat.astype(dt), q_rope], axis=-1)
            kv = _dot(u, self.wkv_a, dt)
            entry = jnp.concatenate(
                [rms_norm(kv[..., :R], self.kv_norm, self.rms_eps),
                 mla.rope_half(kv[..., R:], pos, inv_freq)],
                axis=-1).astype(dt)  # [..., R + rope]
            # the indexer: rope on the first `rope` channels of both
            qi = _dot(cq, self.iq_b, dt).astype(dt)
            qi = jnp.concatenate(
                [mla.rope_half(qi[..., :rope], pos, inv_freq),
                 qi[..., rope:]], axis=-1)
            ki = _dot(u, self.ik_w, dt)
            mean = ki.mean(axis=-1, keepdims=True)
            ki = (ki - mean) * jax.lax.rsqrt(
                jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
                + self.rms_eps) * self.ik_scale.astype(jnp.float32) \
                + self.ik_bias.astype(jnp.float32)
            ki = jnp.concatenate(
                [mla.rope_half(ki[..., :rope], pos, inv_freq),
                 ki[..., rope:]], axis=-1).astype(dt)
            w = _dot(u, self.iw, dt) * (J ** -0.5 * Di ** -0.5)
        return q_full, entry, qi, ki, w

    def attend(self, q_full, entry, qi, ki, w, pos, valid_lens=None,
               offsets=None, out=None):
        """The ``[B, T]`` tokens' entries (``entry``, ``ki``) written at
        their rows' cursors (``pos``: :meth:`positions`), then every
        query attended over what the indexer selects of its row:
        ``[B, T, H, rank]`` float32. With ``offsets`` the queries
        (``q_full`` as rows of ``[H * D]``, ``qi``, ``w``) are PACKED
        rows, row ``b``'s tokens from ``offsets[b]`` on, and so is the
        result, rows of ``[H * rank]`` in the compute dtype, written
        over ``out`` if given
        (:func:`mla.sparse_latent_attention_packed`)."""
        B, T = entry.shape[:2]
        R = self.kv_lora_rank
        starts = self._starts(B)
        if self.decode:
            latent, rope_key, index_key, cursor = self.cache(B)
            L = self.cache_len
            with jax.named_scope("cache_update"):
                # each row's valid tokens land at its cursor; a chunk's
                # padding is pushed past the cache and dropped
                fed = (jnp.full((B,), T, jnp.int32) if valid_lens is None
                       else valid_lens)
                at = jnp.where(jnp.arange(T)[None, :] < fed[:, None], pos, L)
                rows = jnp.arange(B)[:, None]
                for leaf, new in ((latent, entry[..., :R]), (index_key, ki)):
                    leaf.value = leaf.value.at[rows, at].set(
                        new, mode="drop")
                rope_key.value = mla.write_positions_minor(
                    rope_key.value, entry[..., R:], starts, fed)
                cursor.value = starts + fed
            held, rot, keys = latent.value, rope_key.value, index_key.value
            tile = min(self.kv_tile, L)
        else:
            # no cache: the sequence itself, padded to whole tiles
            tile = min(self.kv_tile, T)
            pad = (-T) % tile
            held, rot, keys = (
                jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                for x in (entry[..., :R], entry[..., R:], ki))
            rot = rot.swapaxes(1, 2)
        walk = dict(topk=self.index_topk, tile=tile,
                    scale=self.softmax_scale)
        if offsets is None:
            return mla.sparse_latent_attention(
                q_full, qi, w, held, rot, keys, starts, valid_lens, **walk)
        return mla.sparse_latent_attention_packed(
            q_full, qi, w, held, rot, keys, starts, valid_lens, offsets, T,
            out, **walk)

    def output(self, out):
        """Per token: the attend's ``[..., H, rank]`` through the value
        half of W_ukv (folded into the output) and ``wo``; float32."""
        dt, nope = self.dtype, self.qk_nope_head_dim
        with jax.named_scope("mla_project"):
            o = jnp.einsum("...hr,rhv->...hv", out.astype(dt),
                           self.wkv_b[..., nope:].astype(dt),
                           preferred_element_type=jnp.float32)
            return jax.lax.dot_general(
                o.astype(dt), self.wo.astype(dt),
                (((o.ndim - 2, o.ndim - 1), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32)


class DecoderLayer(nn.Module):
    """Pre-norm attention and feed-forward, each added to the residual
    stream. Everything but the attend is per token: :meth:`before` and
    :meth:`after` are those two halves, which a packed mixed tick
    (``packing``) runs over the ``n_blocks`` blocks of packed rows that
    hold a token and a full-width call runs once over ``[B, T]``."""
    d_model: int
    attn_kw: tuple  # LatentAttention's fields as sorted items (hashable)
    ffn_kw: tuple   # SwiGLU's, or the expert layer's where not dense
    dense: bool
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        ones = nn.initializers.ones
        self.attn_norm = self.param("attn_norm", ones, (self.d_model,),
                                    self.param_dtype)
        self.ffn_norm = self.param("ffn_norm", ones, (self.d_model,),
                                   self.param_dtype)
        self.attn = LatentAttention(**dict(self.attn_kw))
        if self.dense:
            self.mlp = SwiGLU(**dict(self.ffn_kw))
        else:
            self.moe = RoutedExpertsByPart(**dict(self.ffn_kw))

    def before(self, x, pos):
        return self.attn.project(
            rms_norm(x, self.attn_norm, self.rms_eps), pos)

    def after(self, x, out):
        """The residual stream past the attention, then the dense
        feed-forward added, or (expert layer) what the held experts
        still need: the shared expert's result, the normed input in the
        compute dtype and the router's choice."""
        x = x + self.attn.output(out)
        u = rms_norm(x, self.ffn_norm, self.rms_eps)
        if self.dense:
            return (x + self.mlp(u),)
        experts, gates = self.moe.route(u[0])
        with jax.named_scope("moe_shared"):
            shared = self.moe.shared(u)
        return x, shared, u.astype(self.dtype), experts[None], gates[None]

    def __call__(self, x, live, valid_lens=None, packing=None,
                 n_blocks=None, scratch=None):
        """``scratch`` (packed ticks): the arrays the layer before left
        its stages' results in, by stage, to write this layer's over in
        place of 0.9 GB of fresh zeros a layer; replaced by this
        layer's."""
        if packing is None:
            u = rms_norm(x, self.attn_norm, self.rms_eps)
            pos = self.attn.positions(*x.shape[:2])
            x = x + self.attn.output(self.attn.attend(
                *self.attn.project(u, pos), pos, valid_lens))
            u = rms_norm(x, self.ffn_norm, self.rms_eps)
            return x + (self.mlp(u) if self.dense else self.moe(u, live))
        S, C = packing.inv.shape
        pos = self.attn.positions(S, C)
        ((H, D), dt), *small = self.attn.projected()
        R = self.attn.kv_lora_rank

        # the two wide arrays (a token's queries, 128 x 576, and its
        # attend's results, 128 x 512) are carried as rows of features:
        # with heads an axis of their own the compiler lays all N rows
        # out for one consumer and copies them for the next
        def before(m, x, pos):
            q, *rest = m.before(x, pos)
            return (q.reshape(q.shape[:2] + (H * D,)), *rest)

        def after(m, x, out):
            return m.after(x, out.reshape(out.shape[:2] + (H, R)))

        def stage(name, fn, xs, like, tail=0):
            scratch[name] = map_live_blocks(self, fn, xs, like, n_blocks,
                                            tail, scratch.get(name))
            return scratch[name]

        q, entry, qi, ki, w = stage(
            "before", before, (x, pack_live(pos, packing)),
            (((H * D,), dt), *small), C)
        # the cache write keeps [S, C]; the walk takes each row's
        # queries where they lie packed and leaves its results there
        scratch["attend"] = out = self.attn.attend(
            q[0], unpack_live(entry, packing), qi[0],
            unpack_live(ki, packing), w[0], pos, valid_lens,
            jnp.cumsum(valid_lens) - valid_lens, scratch.get("attend"))
        stream = ((self.d_model,), jnp.float32)
        if self.dense:
            return stage("dense", after, (x, out[None]), (stream,))[0]
        k = (self.moe.num_experts_per_tok,)
        x, shared, u, experts, gates = stage(
            "experts", after, (x, out[None]),
            (stream, stream, ((self.d_model,), self.dtype), (k, jnp.int32),
             (k, jnp.float32)))
        # one call over the packed array: the grouped matmul sizes its
        # work from the rows routed here
        return x + (shared + self.moe.held(u[0], experts[0], gates[0],
                                           live[0])[None])


@register_model("deepseek_v32_lm")
class DeepseekV32LM(nn.Module):
    """Decoder-only LM of the DeepSeek-V3.2-Exp architecture. Defaults
    are the published widths; ``num_layers``, ``first_k_dense``,
    ``experts_held`` and ``vocab_size`` are what a configuration cuts."""

    vocab_size: int = 129280
    d_model: int = 7168
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # this chip's share of each expert layer: experts
    # expert_rank * experts_held .. + experts_held - 1 (None: all)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rms_eps: float = 1e-6
    max_len: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    # per-row cache cursors: the only decode mode this model has
    slot_cursor: bool = False
    cache_dtype: str = "model"
    # accepted because the engine hands it to every model it clones; the
    # cursor-bounded walk is this model's only attend
    prefill_kernel: str = "auto"
    kv_tile: int = 512       # positions a step of the cache walk reads
    expert_tile: int = 128   # rows a step of the grouped matmul runs

    # sown into the "counters" collection by every expert layer; the
    # serving tick returns their sums with the tick's tokens
    tick_counters = ("routed_here", "routed_total", "expert_rows_computed",
                     "experts_read")
    # name -> (the name the host keeps it under, times what)
    tick_counter_units = property(expert_counter_units)
    # a decode apply takes ``live_tokens`` (the dropless experts give
    # each token what they would give it alone), and not one compiled
    # count of rows but blocks: handed ``live_tokens = S * C`` the model
    # runs its per-token layers over as many blocks of this many packed
    # rows as hold a token, counted on the device
    packs_live_tokens = True
    live_block_rows = staticmethod(live_block_rows)

    def serving_refusals(self, **options):
        """Raise for each :class:`ServingEngine` option this model does
        not have yet (the engine calls this with what it was given),
        rather than run wrong."""
        lacks = {
            "paged": "a paged (block-pooled) latent cache: serving/"
                     "kvpool.py allocates [blocks, block, Hk, hd] K and V",
            "draft": "speculative decoding: its multi-token-prediction "
                     "module is not built, and a draft's window would "
                     "have to pass the indexer's selection (the verify "
                     "tick that reads a latent cache, draft='mtp', walks "
                     "every position)",
            "mesh": "tensor parallelism: the latent is shared by all "
                    "heads, so heads are not split; replicas take batches",
            "multi_step": "multi-step decode windows: the expert layers' "
                          "counters are returned once a tick",
            "monolithic_prefill": "whole-prompt prefill (prefill_chunk="
                                  "None): the walk holds a chunk's scores, "
                                  "not a prompt's",
        }
        for name, why in lacks.items():
            if options.get(name):
                raise ValueError(
                    f"deepseek_v32_lm cannot be served with {name}: it "
                    f"lacks {why}")

    def kv_positions_fetched(self, starts, valid, chunk: int) -> int:
        """Cache positions one tick's walks read (latent and index key
        walk the same positions), for the engine's count."""
        return mla.fetched_positions(starts, valid,
                                     min(self.kv_tile, self.max_len))

    @nn.compact
    def __call__(self, tokens, train: bool = False, block_tables=None,
                 seq_lens=None, valid_lens=None,
                 live_tokens: Optional[int] = None):
        """``live_tokens`` (``S * C``, with ``valid_lens`` on a decode
        module) is the packed form of a mixed ``[S, C]`` tick: the live
        tokens are packed to the front and every per-token layer runs
        over the ``ceil(live / block)`` blocks of :meth:`live_block_rows`
        rows that hold one, a loop whose trip count the device derives
        from ``valid_lens``; the attend and the cache write keep ``[S,
        C]``. The result is ``[S, 1, vocab]``, each row's last valid
        token's logits."""
        if block_tables is not None or seq_lens is not None:
            raise ValueError("deepseek_v32_lm has no paged cache")
        if self.cache_dtype != "model":
            raise ValueError(
                f"deepseek_v32_lm keeps its latent cache in the model's "
                f"dtype; cache_dtype={self.cache_dtype!r} (an int8 or fp8 "
                f"latent) is not built")
        if self.decode and not self.slot_cursor:
            raise ValueError("deepseek_v32_lm decodes with per-row cursors "
                             "only (slot_cursor=True)")
        if self.decode and self.max_len % min(self.kv_tile, self.max_len):
            raise ValueError(f"max_len={self.max_len} must be a multiple "
                             f"of kv_tile={self.kv_tile} (or shorter)")
        if live_tokens is not None and (
                valid_lens is None or not self.decode
                or live_tokens != tokens.size):
            raise ValueError("live_tokens (the packed mixed tick) is S * C, "
                             "with valid_lens on a decode module")
        B, T = tokens.shape
        held = (self.n_routed_experts if self.experts_held is None
                else self.experts_held)
        attn = dict(
            d_model=self.d_model, num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, index_n_heads=self.index_n_heads,
            index_head_dim=self.index_head_dim,
            index_topk=self.index_topk,
            inv_freq=tuple(float(f) for f in mla.yarn_inv_freq(
                self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
                self.rope_original_len, self.rope_beta_fast,
                self.rope_beta_slow)),
            softmax_scale=mla.yarn_softmax_scale(
                self.qk_nope_head_dim + self.qk_rope_head_dim,
                self.rope_factor),
            rms_eps=self.rms_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, decode=self.decode,
            cache_len=self.max_len if self.decode else 0,
            kv_tile=self.kv_tile)
        moe = dict(
            d_model=self.d_model, n_routed_experts=self.n_routed_experts,
            experts_held=held,
            expert_rank=self.expert_rank,
            num_experts_per_tok=self.num_experts_per_tok,
            n_group=self.n_group, topk_group=self.topk_group,
            routed_scaling_factor=self.routed_scaling_factor,
            width=self.moe_intermediate_size,
            n_shared_experts=self.n_shared_experts, dtype=self.dtype,
            param_dtype=self.param_dtype, expert_tile=self.expert_tile)
        mlp = dict(width=self.intermediate_size, dtype=self.dtype,
                   param_dtype=self.param_dtype)
        packing = n_blocks = None
        scratch = {}
        if live_tokens is not None:
            packing = live_packing(valid_lens, T, live_tokens)
            tokens = tokens.reshape(-1)[packing.idx][None]  # [1, S * C]
            dealt = valid_lens.sum()
            live = (jnp.arange(live_tokens) < dealt)[None]
            n_blocks = -(-dealt // self.live_block_rows(live_tokens))
        else:
            live = (jnp.ones((B, T), bool) if valid_lens is None
                    else jnp.arange(T)[None, :] < valid_lens[:, None])
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     param_dtype=self.param_dtype,
                     name="embed")(tokens).astype(jnp.float32)
        for i in range(self.num_layers):
            dense = i < self.first_k_dense
            x = DecoderLayer(self.d_model, tuple(sorted(attn.items())),
                             tuple(sorted((mlp if dense else moe).items())),
                             dense, self.rms_eps, self.dtype,
                             self.param_dtype, name=f"layers_{i}")(
                                 x, live, valid_lens, packing, n_blocks,
                                 scratch)
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
