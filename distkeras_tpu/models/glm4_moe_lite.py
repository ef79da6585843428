"""GLM-4.7-Flash's language model (``model_type`` ``glm4_moe_lite``) for
the serving path: RMSNorm, a leading dense SwiGLU layer then expert
layers, multi-head latent attention (MLA) with NO learned selection
(every position up to the query's is attended), sigmoid routing over
all experts with a selection-only correction bias, one shared expert,
and the model's own multi-token-prediction (MTP) module behind the last
layer, which the serving engine runs as its drafter (``draft="mtp"``).

The layer equations are ISSUE 41's and ``chipbench/references/
glm4_moe_lite.py`` follows them in plain float32; this module is the
program. What it does differently, with the same mathematics:

- **Absorbed attention** over the latent cache ``deepseek_v32_lm``
  brought (PR 28, PR 40): a position's entry is ``[c_kv | rope(k_r)]``
  in two leaves, ``latent [S, L, 512]`` and ``rope_key [S, 64, L]``
  (positions minor), ``W_ukv``'s key half folded into the query and its
  value half into the output. The walk is
  :func:`distkeras_tpu.ops.mla.dense_latent_attention`: rows that feed
  a token or a verify window walk together, prompt chunks one by one.
- **One form of a tick inside**: a tick's tokens are rows of a ``[1, N,
  ...]`` array, whether they lie ``[S, C]`` (``N = S * C``, row ``s``
  from ``s * C`` on) or packed to the front (``live_tokens = N``, row
  ``s`` from the exclusive cumulative sum of ``valid_lens`` on;
  :func:`~distkeras_tpu.models.blocks.live_packing`). Every per-token
  layer runs over the ``N`` rows; the cache write and the walk are told
  where each row's tokens lie.
- **The module** (:class:`MTPModule`; DeepSeek-V3 report, section 2.2):
  ``u_t = W_eh [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(h_t)]`` with ``h_t``
  the main model's hidden state at ``t`` after its final norm, one
  expert layer of the main model's kind over ``u`` with its own latent
  cache, its own final norm, the main model's head: logits for
  ``x_{t+2}``. The embedding and the head are the main model's leaves.
  :meth:`Glm4MoeLiteLM.__call__` with ``head_at`` returns the hidden
  states beside the logits of a verify window, and
  :meth:`Glm4MoeLiteLM.draft` feeds them to the module once the engine
  knows each position's next token; the module's cache is one more
  latent layer among the model's cache leaves, fed wherever the main
  layers are fed and rewound with them.

Departures from the published model (the reference has the same): rope
pairs channel ``i`` with ``i + half`` (the checkpoint interleaves: a
fixed permutation of ``wq_b``'s and ``wkv_a``'s rope columns at load).

The serving engine reads off the class: ``tick_counters``,
``packs_live_tokens``, ``mtp_depth`` (drafts the module makes a tick;
the engine clones its decode module with ``verify_window = spec_k + 1``)
and :meth:`serving_refusals`.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.blocks import (
    LivePacking, RoutedExperts, SwiGLU, _dot, _normal, expert_counter_units,
    live_packing, rms_norm)
from distkeras_tpu.models.registry import register_model
from distkeras_tpu.ops import mla


class _Rows(NamedTuple):
    """Where the tokens of a ``[S, C]`` tick lie among the ``N`` rows the
    per-token layers run over (traced; see the module docstring)."""

    S: int
    C: int
    N: int
    fed: jnp.ndarray      # [S]: tokens row s feeds
    offsets: jnp.ndarray  # [S]: the first of them among the N rows
    live: jnp.ndarray     # [N]: a token lies on the row
    packing: Optional[LivePacking]  # None: the rows lie [S, C]

    def pack(self, t):
        """``[S, C, ...] -> [N, ...]``."""
        flat = t.reshape((-1,) + t.shape[2:])
        return flat if self.packing is None else flat[self.packing.idx]

    def unpack(self, t):
        """``[N, ...] -> [S, C, ...]``, zeros where no token was dealt."""
        if self.packing is None:
            return t.reshape((self.S, self.C) + t.shape[1:])
        return jnp.take(t, self.packing.inv, axis=0, mode="fill",
                        fill_value=0)

    def at(self, t, cols):
        """``t [N, ...]`` at column ``cols [S]`` or ``[S, K]`` of each
        row's run (clipped into the array; the caller reads only columns
        the row fed)."""
        first = self.offsets if cols.ndim == 1 else self.offsets[:, None]
        return t[jnp.clip(first + cols, 0, self.N - 1)]


def _rows(shape, valid_lens, live_tokens) -> _Rows:
    S, C = shape
    fed = (jnp.full((S,), C, jnp.int32) if valid_lens is None
           else valid_lens)
    if live_tokens is None:
        live = (jnp.arange(C)[None, :] < fed[:, None]).reshape(-1)
        return _Rows(S, C, S * C, fed, jnp.arange(S) * C, live, None)
    return _Rows(S, C, live_tokens, fed, jnp.cumsum(fed) - fed,
                 jnp.arange(live_tokens) < fed.sum(),
                 live_packing(fed, C, live_tokens))


class DenseLatentAttention(nn.Module):
    """MLA attending every held position; see the module docstring."""
    d_model: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    cache_len: int = 0
    kv_tile: int = 512
    verify_window: int = 1

    def setup(self):
        d, H, R = self.d_model, self.num_heads, self.kv_lora_rank
        rope, nope, pd = (self.qk_rope_head_dim, self.qk_nope_head_dim,
                          self.param_dtype)
        ones = nn.initializers.ones
        self.wq_a = self.param("wq_a", _normal(), (d, self.q_lora_rank), pd)
        self.q_norm = self.param("q_norm", ones, (self.q_lora_rank,), pd)
        self.wq_b = self.param("wq_b", _normal(),
                               (self.q_lora_rank, H, nope + rope), pd)
        self.wkv_a = self.param("wkv_a", _normal(), (d, R + rope), pd)
        self.kv_norm = self.param("kv_norm", ones, (R,), pd)
        self.wkv_b = self.param("wkv_b", _normal(),
                                (R, H, nope + self.v_head_dim), pd)
        self.wo = self.param("wo", _normal(2), (H, self.v_head_dim, d), pd)

    @nn.compact
    def cache(self, B: int):
        """The decode cache's leaves for ``B`` rows, as
        ``deepseek_v32_lm`` lays them (whole 128-lane groups minor) less
        the index key."""
        L, dt = self.cache_len, self.dtype
        return (
            self.variable("cache", "latent", jnp.zeros,
                          (B, L, self.kv_lora_rank), dt),
            self.variable("cache", "rope_key", jnp.zeros,
                          (B, self.qk_rope_head_dim, L), dt),
            self.variable("cache", "cache_index",
                          lambda: jnp.zeros((B,), jnp.int32)))

    def starts(self, B: int):
        """``[B]``: each row's cursor (0 without a cache)."""
        return (self.cache(B)[-1].value if self.decode
                else jnp.zeros((B,), jnp.int32))

    def project(self, u, pos):
        """Per token (``u [N, d]`` at ``pos [N]``): the absorbed query
        ``[N, H * (rank + rope)]`` and the latent entry ``[N, rank +
        rope]``."""
        R, nope, dt = self.kv_lora_rank, self.qk_nope_head_dim, self.dtype
        inv_freq = 1.0 / self.rope_theta ** (
            np.arange(0, self.qk_rope_head_dim, 2, dtype=np.float64)
            / self.qk_rope_head_dim)
        inv_freq = inv_freq.astype(np.float32)
        with jax.named_scope("mla_project"):
            cq = rms_norm(_dot(u, self.wq_a, dt), self.q_norm, self.rms_eps)
            q = _dot(cq, self.wq_b, dt).astype(dt)  # [N, H, nope + rope]
            q_rope = mla.rope_half(q[..., nope:], pos, inv_freq)
            # the key half of W_ukv folded into the query
            q_lat = jnp.einsum("nhd,rhd->nhr", q[..., :nope],
                               self.wkv_b[..., :nope].astype(dt),
                               preferred_element_type=jnp.float32)
            q_full = jnp.concatenate([q_lat.astype(dt), q_rope], axis=-1)
            kv = _dot(u, self.wkv_a, dt)
            entry = jnp.concatenate(
                [rms_norm(kv[..., :R], self.kv_norm, self.rms_eps),
                 mla.rope_half(kv[..., R:], pos, inv_freq)],
                axis=-1).astype(dt)
        return q_full.reshape(q_full.shape[0], -1), entry

    def attend(self, q, entry, starts, rows: _Rows):
        """The tick's entries (``entry [S, C, rank + rope]``) written at
        their rows' cursors, then every query (``q [N, H * D]``)
        attended over all its row holds up to itself: ``[N, H * rank]``
        in the compute dtype."""
        S, C = rows.S, rows.C
        R = self.kv_lora_rank
        if self.decode:
            latent, rope_key, cursor = self.cache(S)
            L = self.cache_len
            with jax.named_scope("cache_update"):
                # each row's valid tokens land at its cursor; a chunk's
                # padding is pushed past the cache and dropped
                pos = starts[:, None] + jnp.arange(C)[None]
                at = jnp.where(jnp.arange(C)[None, :] < rows.fed[:, None],
                               pos, L)
                latent.value = latent.value.at[
                    jnp.arange(S)[:, None], at].set(entry[..., :R],
                                                    mode="drop")
                rope_key.value = mla.write_positions_minor(
                    rope_key.value, entry[..., R:], starts, rows.fed)
                cursor.value = starts + rows.fed
            held, rot = latent.value, rope_key.value
            tile = min(self.kv_tile, L)
        else:
            # no cache: the sequence itself, padded to whole tiles
            tile = min(self.kv_tile, C)
            pad = (-C) % tile
            held, rot = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                         for x in (entry[..., :R], entry[..., R:]))
            rot = rot.swapaxes(1, 2)
        q = jnp.pad(q, ((0, C), (0, 0)))  # any row's slice of C fits
        return mla.dense_latent_attention(
            q, held, rot, starts, rows.fed, rows.offsets, C,
            small=self.verify_window, tile=tile,
            scale=(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5)

    def output(self, out):
        """Per token: the attend's ``[N, H * rank]`` through the value
        half of W_ukv (folded into the output) and ``wo``; float32."""
        dt, nope = self.dtype, self.qk_nope_head_dim
        with jax.named_scope("mla_project"):
            o = jnp.einsum(
                "nhr,rhv->nhv",
                out.reshape(out.shape[0], self.num_heads, -1).astype(dt),
                self.wkv_b[..., nope:].astype(dt),
                preferred_element_type=jnp.float32)
            return jax.lax.dot_general(
                o.astype(dt), self.wo.astype(dt),
                (((1, 2), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32)


class DecoderLayer(nn.Module):
    """Pre-norm attention and feed-forward over the ``N`` rows of a
    tick, each added to the float32 residual stream."""
    attn_kw: tuple  # DenseLatentAttention's fields as sorted items
    ffn_kw: tuple   # SwiGLU's, or RoutedExperts' where not dense
    dense: bool
    rms_eps: float
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, rows: _Rows):
        d = x.shape[-1]
        ones = nn.initializers.ones
        attn_norm = self.param("attn_norm", ones, (d,), self.param_dtype)
        ffn_norm = self.param("ffn_norm", ones, (d,), self.param_dtype)
        attn = DenseLatentAttention(**dict(self.attn_kw), name="attn")
        starts = attn.starts(rows.S)
        pos = rows.pack(starts[:, None] + jnp.arange(rows.C)[None])
        q, entry = attn.project(rms_norm(x[0], attn_norm, self.rms_eps), pos)
        x = x + attn.output(attn.attend(q, rows.unpack(entry), starts,
                                        rows))[None]
        u = rms_norm(x, ffn_norm, self.rms_eps)
        if self.dense:
            return x + SwiGLU(**dict(self.ffn_kw), name="mlp")(u)
        return x + RoutedExperts(**dict(self.ffn_kw), name="moe")(
            u, rows.live[None])


class MTPModule(nn.Module):
    """One multi-token-prediction module: the projection of the next
    token's embedding beside the main model's hidden state, one expert
    layer with its own latent cache, its own final norm."""
    layer_kw: tuple  # DecoderLayer's fields as sorted items
    rms_eps: float
    param_dtype: jnp.dtype = jnp.float32
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, emb_next, hidden, rows: _Rows):
        """``emb_next``, ``hidden`` ``[1, N, d]`` float32 -> the module's
        normed hidden state ``[1, N, d]``."""
        d = hidden.shape[-1]
        ones = nn.initializers.ones
        enorm = self.param("enorm", ones, (d,), self.param_dtype)
        hnorm = self.param("hnorm", ones, (d,), self.param_dtype)
        eh_proj = self.param("eh_proj", _normal(), (2 * d, d),
                             self.param_dtype)
        norm = self.param("norm", ones, (d,), self.param_dtype)
        u = _dot(jnp.concatenate(
            [rms_norm(emb_next, enorm, self.rms_eps),
             rms_norm(hidden, hnorm, self.rms_eps)], axis=-1),
            eh_proj, self.dtype)
        x = DecoderLayer(**dict(self.layer_kw), name="layer")(u, rows)
        return rms_norm(x, norm, self.rms_eps)


@register_model("glm4_moe_lite_lm")
class Glm4MoeLiteLM(nn.Module):
    """Decoder-only LM of the GLM-4.7-Flash architecture with its MTP
    module. Defaults are the published widths; ``num_layers`` is what a
    configuration cuts."""

    vocab_size: int = 154880
    d_model: int = 2048
    num_layers: int = 47
    first_k_dense: int = 1
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    rope_theta: float = 1e6
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
    kv_tile: int = 512       # positions a step of the cache walk reads
    expert_tile: int = 128   # rows a step of the grouped matmul runs
    # queries of a row that still walk with the decoding rows: 1, or
    # the verify window (the pending token and its drafts) where the
    # engine drafts with the module
    verify_window: int = 1

    # sown into the "counters" collection by every expert layer, the
    # module's too; the serving tick returns their sums with its tokens
    tick_counters = ("routed_here", "routed_total", "expert_rows_computed",
                     "experts_read")
    # name -> (the name the host keeps it under, times what)
    tick_counter_units = property(expert_counter_units)
    # a decode apply takes ``live_tokens``: the dropless experts give
    # each token what they would give it alone
    packs_live_tokens = True
    # drafts the module makes a tick (``num_nextn_predict_layers``)
    mtp_depth = 1

    def serving_refusals(self, **options):
        """Raise for each :class:`ServingEngine` option this model does
        not have yet (the engine calls this with what it was given),
        rather than run wrong."""
        lacks = {
            "paged": "a paged (block-pooled) latent cache: serving/"
                     "kvpool.py allocates [blocks, block, Hk, hd] K and V",
            "mesh": "tensor parallelism: the latent is shared by all "
                    "heads, so heads are not split; replicas take batches",
            "multi_step": "multi-step decode windows: the expert layers' "
                          "counters are returned once a tick",
            "monolithic_prefill": "whole-prompt prefill (prefill_chunk="
                                  "None): the walk holds a chunk's scores, "
                                  "not a prompt's",
        }
        if options.get("draft") and options.get("draft_kind") != "mtp":
            raise ValueError(
                "glm4_moe_lite_lm cannot be served with draft: it lacks a "
                "verify tick for a drafter other than its own module "
                "(draft='mtp'): an n-gram or second-model window takes "
                "the head at every position of a [S, chunk] tick")
        for name, why in lacks.items():
            if options.get(name):
                raise ValueError(
                    f"glm4_moe_lite_lm cannot be served with {name}: it "
                    f"lacks {why}")
        self._refuse_cache_dtype()

    def _refuse_cache_dtype(self):
        if self.cache_dtype != "model":
            raise ValueError(
                f"glm4_moe_lite_lm keeps its latent cache in the model's "
                f"dtype; cache_dtype={self.cache_dtype!r} (an int8 or fp8 "
                f"latent) is not built")

    def kv_positions_fetched(self, starts, valid, chunk: int) -> int:
        """Cache positions ONE layer's walk of a tick reads, for the
        engine's count."""
        return mla.dense_fetched_positions(
            starts, valid, min(self.kv_tile, self.max_len),
            self.verify_window)

    def _layer_kw(self, dense: bool) -> dict:
        attn = dict(
            d_model=self.d_model, num_heads=self.num_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            rms_eps=self.rms_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, decode=self.decode,
            cache_len=self.max_len if self.decode else 0,
            kv_tile=self.kv_tile, verify_window=self.verify_window)
        if dense:
            ffn = dict(width=self.intermediate_size, dtype=self.dtype,
                       param_dtype=self.param_dtype)
        else:
            ffn = dict(
                n_routed_experts=self.n_routed_experts,
                # the whole bank is on this chip
                experts_held=self.n_routed_experts, expert_rank=0,
                num_experts_per_tok=self.num_experts_per_tok,
                n_group=1, topk_group=1,
                routed_scaling_factor=self.routed_scaling_factor,
                width=self.moe_intermediate_size,
                n_shared_experts=self.n_shared_experts, dtype=self.dtype,
                param_dtype=self.param_dtype, expert_tile=self.expert_tile)
        return dict(attn_kw=tuple(sorted(attn.items())),
                    ffn_kw=tuple(sorted(ffn.items())), dense=dense,
                    rms_eps=self.rms_eps, param_dtype=self.param_dtype)

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              dtype=self.dtype,
                              param_dtype=self.param_dtype)
        self.layers = [
            DecoderLayer(**self._layer_kw(i < self.first_k_dense))
            for i in range(self.num_layers)]
        self.norm = self.param("norm", nn.initializers.ones,
                               (self.d_model,), self.param_dtype)
        # untied head, float32 logits
        self.head = self.param("head", _normal(),
                               (self.d_model, self.vocab_size),
                               self.param_dtype)
        self.mtp = MTPModule(
            tuple(sorted(self._layer_kw(False).items())), self.rms_eps,
            self.param_dtype, self.dtype)

    def _check(self, tokens, valid_lens, live_tokens):
        self._refuse_cache_dtype()
        if self.decode and not self.slot_cursor:
            raise ValueError("glm4_moe_lite_lm decodes with per-row "
                             "cursors only (slot_cursor=True)")
        if self.decode and self.max_len % min(self.kv_tile, self.max_len):
            raise ValueError(f"max_len={self.max_len} must be a multiple "
                             f"of kv_tile={self.kv_tile} (or shorter)")
        if live_tokens is not None and (valid_lens is None
                                        or not self.decode):
            raise ValueError("live_tokens (the packed mixed tick) needs "
                             "valid_lens on a decode module")

    def __call__(self, tokens, train: bool = False, block_tables=None,
                 seq_lens=None, valid_lens=None,
                 live_tokens: Optional[int] = None, head_at=None):
        """Logits of a ``[S, C]`` call: ``[S, C, vocab]``, or, with
        ``live_tokens`` (a static count ``N``: the packed form of a
        mixed tick, as in :meth:`TransformerLM.__call__`), ``[S, 1,
        vocab]`` at each row's last valid token. With ``head_at [S, K]``
        (columns of each row) the result is ``(hidden, logits)``: the
        final normed hidden states ``[1, N, d]`` of the tick's rows, for
        :meth:`draft`, and the logits ``[S, K, vocab]`` at those columns
        alone (a verify window reads the head at its own positions, not
        at a chunk's)."""
        if block_tables is not None or seq_lens is not None:
            raise ValueError("glm4_moe_lite_lm has no paged cache")
        self._check(tokens, valid_lens, live_tokens)
        rows = _rows(tokens.shape, valid_lens, live_tokens)
        x = self.embed(rows.pack(tokens))[None].astype(jnp.float32)
        for layer in self.layers:
            x = layer(x, rows)
        h = rms_norm(x, self.norm, self.rms_eps)
        if self.is_initializing():
            # the module's parameters and its cache leaves exist from
            # init on, whether or not a caller ever drafts
            self.mtp(x, h, rows)
        if head_at is not None:
            return h, _dot(rows.at(h[0], head_at), self.head, self.dtype)
        if live_tokens is None:
            return _dot(h[0], self.head, self.dtype).reshape(
                tokens.shape + (self.vocab_size,))
        last = rows.at(h[0], jnp.maximum(rows.fed - 1, 0))
        return _dot(last, self.head, self.dtype)[:, None]

    def draft(self, hidden, next_tokens, valid_lens=None,
              live_tokens: Optional[int] = None, draft_at=None):
        """The module over the positions a call of :meth:`__call__` just
        fed: ``hidden`` is that call's ``[1, N, d]``, ``next_tokens [S,
        C]`` the token that FOLLOWS each fed one. The module's cache
        takes the same positions at the same cursors. Returns the
        module's logits (for the token after the next) at column
        ``draft_at [S]`` of each row, ``[S, vocab]``; every column,
        ``[S, C, vocab]``, where ``draft_at`` is None (no packing)."""
        self._check(next_tokens, valid_lens, live_tokens)
        rows = _rows(next_tokens.shape, valid_lens, live_tokens)
        with jax.named_scope("mtp_draft"):
            emb = self.embed(rows.pack(next_tokens))[None].astype(
                jnp.float32)
            g = self.mtp(emb, hidden, rows)[0]
            if draft_at is not None:
                return _dot(rows.at(g, draft_at), self.head, self.dtype)
            return _dot(g, self.head, self.dtype).reshape(
                next_tokens.shape + (self.vocab_size,))
