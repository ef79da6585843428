"""Trinity-Mini's language model (family ``afmoe``) for the TRAINING
path: the full-sequence forward that ``LMTrainer`` differentiates, with
``features_only`` and ``remat`` as ``transformer_lm`` has them. There is
no cache path: :meth:`AfmoeLM.serving_refusals` refuses the engine.

The layer equations are ISSUE 44's and ``chipbench/references/afmoe.py``
follows them in plain float32; this module is the program.

- **Block**, float32 residual: ``x + N2(Attn(N1(x)))``, then ``x +
  N4(FFN(N3(x)))``: a norm before and after each sublayer.
- **Attention**, both kinds of layer in one shape: 32 query heads over 4
  KV heads of 128, RMSNorm a head on ``q`` and ``k``, a per-channel
  sigmoid output gate from the layer's input. ``sliding_attention``
  layers rotate ``q`` and ``k`` (half-split pairs, all channels) and
  attend the last ``sliding_window`` positions; ``full_attention``
  layers carry no positions and attend everything before them. Both are
  one launch family, :func:`~distkeras_tpu.ops.pallas_attention.
  pallas_causal_attention` with ``window`` and grouped KV heads (name
  scopes ``window_attend`` / ``full_attend``); shapes the kernel does
  not tile take the plain masked attend.
- **Experts** (:class:`~distkeras_tpu.models.blocks.RoutedExpertsByPart`
  at one group): sigmoid scores over all experts, the ``k`` largest of
  ``score + bias``, gates normalised over the chosen and scaled, this
  chip's share of the experts through the grouped matmul and its
  gradient (``ops/grouped_experts.py``), one shared expert. The held
  experts run a batch row at a time: the launches keep a row's pairs in
  scalar memory.
- **The selection bias** takes no gradient (it enters the choice of
  experts only) and is state a rule updates: after each optimizer step
  :meth:`AfmoeLM.rule_update` moves it by ``load_balance_coeff *
  sign(mean load - load)`` from the step's own counts, which every
  expert layer sows into the ``"counters"`` collection
  (``expert_load``, all experts counted, held or not).

What ``LMTrainer`` / ``make_lm_train_step`` read off the class:
``features_only``, ``remat``, the ``head`` subtree, ``step_counters``
(this model has them), :meth:`rule_update`, :meth:`step_metrics`,
:meth:`training_refusals`.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.blocks import (
    RoutedExpertsByPart, SwiGLU, _dot, _normal, rms_norm)
from distkeras_tpu.models.registry import register_model
from distkeras_tpu.ops import hybrid_attend, pallas_attention
from distkeras_tpu.ops.mla import rope_half
from distkeras_tpu.ops.pallas_attention import pallas_causal_attention


class GatedAttention(nn.Module):
    """One attention layer of either kind: ``window`` 0 is a full layer
    (no positions), else a sliding-window layer (rotary)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attention: str = "auto"

    @nn.compact
    def __call__(self, u):
        B, T, d = u.shape
        H, Hk, hd = self.num_heads, self.num_kv_heads, self.head_dim
        pd, dt = self.param_dtype, self.dtype
        wq = self.param("wq", _normal(), (d, H, hd), pd)
        wk = self.param("wk", _normal(), (d, Hk, hd), pd)
        wv = self.param("wv", _normal(), (d, Hk, hd), pd)
        wg = self.param("wg", _normal(), (d, H, hd), pd)
        wo = self.param("wo", _normal(2), (H, hd, d), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (hd,), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (hd,), pd)
        with jax.named_scope("attn_project"):
            q = rms_norm(_dot(u, wq, dt), q_norm, self.rms_eps)
            k = rms_norm(_dot(u, wk, dt), k_norm, self.rms_eps)
            v = _dot(u, wv, dt).astype(dt)
            gate = jax.nn.sigmoid(_dot(u, wg, dt))
            pos = jnp.broadcast_to(jnp.arange(T), (B, T))
            if self.window:
                inv_freq = (1.0 / self.rope_theta ** (
                    np.arange(0, hd, 2, dtype=np.float64) / hd)).astype(
                        np.float32)
                q, k = rope_half(q, pos, inv_freq), rope_half(k, pos, inv_freq)
            q, k = q.astype(dt), k.astype(dt)
        with jax.named_scope("window_attend" if self.window
                             else "full_attend"):
            block = pallas_attention.choose_block(
                T, hd, itemsize=jnp.dtype(dt).itemsize)
            if self.attention == "pallas" or (
                    self.attention == "auto" and block is not None
                    and jax.default_backend() == "tpu"):
                out = pallas_causal_attention(
                    q, k, v, block or pallas_attention.DEFAULT_BLOCK,
                    self.window or None)
            else:
                out = hybrid_attend.dense_attention(
                    q, k, v, pos, jnp.zeros((B,), jnp.int32),
                    self.window or None)
        with jax.named_scope("attn_project"):
            return jax.lax.dot_general(
                (out.astype(jnp.float32) * gate).astype(dt), wo.astype(dt),
                (((2, 3), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32)


class Head(nn.Module):
    """The untied head without bias: float32 logits. Its subtree
    (``{"kernel": [d, V]}``) is what the fused loss is handed."""
    vocab_size: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _normal(),
                            (x.shape[-1], self.vocab_size), self.param_dtype)
        return _dot(x, kernel, self.dtype)


class DecoderLayer(nn.Module):
    attn: tuple  # GatedAttention's fields as sorted items (hashable)
    ffn: tuple   # SwiGLU's, or RoutedExpertsByPart's where not dense
    dense: bool
    rms_eps: float
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        n1, n2, n3, n4 = (
            self.param(name, nn.initializers.ones, (d,), self.param_dtype)
            for name in ("attn_norm", "attn_post_norm", "ffn_norm",
                         "ffn_post_norm"))
        eps = self.rms_eps
        x = x + rms_norm(GatedAttention(**dict(self.attn), name="attn")(
            rms_norm(x, n1, eps)), n2, eps)
        u = rms_norm(x, n3, eps)
        if self.dense:
            return x + rms_norm(SwiGLU(**dict(self.ffn), name="mlp")(u), n4,
                                eps)
        moe = RoutedExpertsByPart(**dict(self.ffn), d_model=d, name="moe")
        B, T, _ = u.shape
        experts, gates = moe.route(u.reshape(B * T, d))
        # every expert's tokens of this step, held here or not: what the
        # bias rule reads
        load = (experts[..., None] == jnp.arange(moe.n_routed_experts)).sum(
            (0, 1), dtype=jnp.int32)
        self.sow("counters", "expert_load", load, reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros_like(load))
        experts, gates = (a.reshape(B, T, -1) for a in (experts, gates))
        live = jnp.ones((T,), bool)
        y = jnp.stack([moe.held(u[b], experts[b], gates[b], live)
                       for b in range(B)])
        with jax.named_scope("moe_shared"):
            y = y + moe.shared(u)
        return x + rms_norm(y, n4, eps)


@register_model("afmoe_lm")
class AfmoeLM(nn.Module):
    """Decoder-only LM of the ``afmoe`` architecture. Defaults are
    Trinity-Mini's published widths; ``num_layers``,
    ``num_dense_layers``, ``layer_types``, ``experts_held`` /
    ``expert_rank`` and ``vocab_size`` are what a configuration cuts."""

    vocab_size: int = 200192
    d_model: int = 2048
    num_layers: int = 32
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    # "sliding_attention" or "full_attention" a layer; None: the
    # published pattern (three sliding, then one full)
    layer_types: Optional[Tuple[str, ...]] = None
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 128
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    n_shared_experts: int = 1
    # this chip's share of each expert layer: experts
    # expert_rank * experts_held .. + experts_held - 1 (None: all)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    load_balance_coeff: float = 1e-3
    rope_theta: float = 1e4
    rms_eps: float = 1e-5
    # the longest sequence taken (published max_position_embeddings);
    # no table depends on it, a longer sequence is refused
    max_len: int = 131072
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    remat: str = "none"  # 'none' | 'block'
    # the final norm's output [B, T, d] in place of logits: the fused
    # loss applies the head itself (model.copy(features_only=True))
    features_only: bool = False
    # 'auto' (the kernel on a TPU where the sequence tiles), 'pallas'
    # (force; interpret mode off the chip), 'dense' (the plain attend)
    attention: str = "auto"
    expert_tile: int = 128

    # sown into "counters" by every expert layer; the train step hands
    # their sums over a step to rule_update and step_metrics
    step_counters = ("routed_here", "expert_load")

    def __post_init__(self):
        if isinstance(self.layer_types, list):
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        super().__post_init__()

    def layer_windows(self) -> Tuple[int, ...]:
        """Each layer's window: 0 for a full layer."""
        kinds = self.layer_types
        if kinds is None:
            kinds = tuple("full_attention" if i % 4 == 3
                          else "sliding_attention"
                          for i in range(self.num_layers))
        if len(kinds) < self.num_layers or set(kinds) - {
                "sliding_attention", "full_attention"}:
            raise ValueError(
                f"layer_types names {len(kinds)} layers of "
                f"{self.num_layers}, each 'sliding_attention' or "
                f"'full_attention': {kinds}")
        return tuple(self.sliding_window if k == "sliding_attention" else 0
                     for k in kinds[:self.num_layers])

    def serving_refusals(self, **options):
        raise ValueError(
            "afmoe_lm cannot be served: it has no cache path (a ring for "
            "the window layers, K/V leaves for the full ones, a decode "
            "tick); it is the training path's model")

    def training_refusals(self, axes: dict):
        """Raise for each mesh axis ``LMTrainer`` cannot give this
        model: it trains on ``dp`` alone."""
        lacks = {
            "sp": "ring attention over a band and grouped KV heads",
            "tp": "a split of 4 KV heads and of the expert banks",
            "pp": "a stage split of two kinds of layer",
            "ep": "the expert exchange across chips (all_to_all about the "
                  "grouped matmul): a chip computes the experts it holds",
        }
        for name, why in lacks.items():
            if axes.get(name, 1) > 1:
                raise ValueError(
                    f"afmoe_lm cannot be trained with {name}="
                    f"{axes[name]}: it lacks {why}")

    def _bias_and_load(self, params, counters):
        """``(layer name, bias [E], load [E])`` of every expert layer."""
        return [(name, params["params"][name]["moe"][
            "e_score_correction_bias"], layer["expert_load"])
            for name, layer in sorted(counters.items())]

    def rule_update(self, params, counters):
        """The selection bias after an optimizer step, from that step's
        counts: ``b + coeff * sign(mean(load) - load)``. ``params`` with
        the new biases."""
        with jax.named_scope("router_bias_update"):
            layers = dict(params["params"])
            for name, bias, load in self._bias_and_load(params, counters):
                load = load.astype(jnp.float32)
                moe = dict(layers[name]["moe"])
                moe["e_score_correction_bias"] = bias + (
                    self.load_balance_coeff
                    * jnp.sign(jnp.mean(load) - load)).astype(bias.dtype)
                layers[name] = {**layers[name], "moe": moe}
            return {**params, "params": layers}

    def step_metrics(self, params, counters):
        """What a metrics row says of a step's routing: pairs sent to
        held experts (all expert layers); the fullest expert over the
        mean (all experts, the worst layer: the router's health); what
        this chip pays of it, each a mean over the expert layers: the
        fullest HELD expert over the held experts' mean (the longest
        run of rows the grouped matmul walks) and the held experts'
        share of the pairs over an even share; the largest bias."""
        rows = self._bias_and_load(params, counters)
        loads = jnp.stack([load for _, _, load in rows]).astype(jnp.float32)
        n = self.experts_held or self.n_routed_experts
        held = loads[:, self.expert_rank * n:(self.expert_rank + 1) * n]
        held_mean = held.mean(axis=1)
        return {
            "routed_here": sum(layer["moe"]["routed_here"]
                               for layer in counters.values()),
            "expert_load_max_over_mean": jnp.max(
                loads.max(axis=1) / loads.mean(axis=1)),
            "held_load_max_over_mean": jnp.mean(jnp.where(
                held_mean > 0, held.max(axis=1) / held_mean, 1.0)),
            "routed_here_over_even": jnp.mean(
                held.sum(axis=1) / loads.sum(axis=1)
                * (self.n_routed_experts / n)),
            "router_bias_abs_max": jnp.max(jnp.abs(jnp.stack(
                [bias for _, bias, _ in rows]))),
        }

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        if self.remat not in ("none", "block"):
            raise ValueError(
                f"Unknown remat policy '{self.remat}'. Known: none, block")
        if self.attention not in ("auto", "pallas", "dense"):
            raise ValueError(f"Unknown attention '{self.attention}'. "
                             f"Known: auto, pallas, dense")
        if tokens.shape[-1] > self.max_len:
            raise ValueError(f"a sequence of {tokens.shape[-1]} tokens is "
                             f"longer than max_len={self.max_len}")
        windows = self.layer_windows()
        held = (self.n_routed_experts if self.experts_held is None
                else self.experts_held)
        attn = dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            rms_eps=self.rms_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, attention=self.attention)
        moe = dict(
            n_routed_experts=self.n_routed_experts, experts_held=held,
            expert_rank=self.expert_rank,
            num_experts_per_tok=self.num_experts_per_tok, n_group=1,
            topk_group=1, routed_scaling_factor=self.route_scale,
            width=self.moe_intermediate_size,
            n_shared_experts=self.n_shared_experts, dtype=self.dtype,
            param_dtype=self.param_dtype, expert_tile=self.expert_tile)
        mlp = dict(width=self.intermediate_size, dtype=self.dtype,
                   param_dtype=self.param_dtype)
        # mup: the embedding times sqrt(d_model), float32 from the table
        x = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                     param_dtype=self.param_dtype, name="embed")(
                         tokens) * np.sqrt(self.d_model).astype(np.float32)
        Layer = nn.remat(DecoderLayer) if self.remat == "block" \
            else DecoderLayer
        for i, window in enumerate(windows):
            dense = i < self.num_dense_layers
            x = Layer(
                tuple(sorted(dict(attn, window=window).items())),
                tuple(sorted((mlp if dense else moe).items())), dense,
                self.rms_eps, self.param_dtype, name=f"layers_{i}")(x)
        norm = self.param("norm", nn.initializers.ones, (self.d_model,),
                          self.param_dtype)
        x = rms_norm(x, norm, self.rms_eps)
        if self.features_only:
            return x.astype(self.dtype)
        return Head(self.vocab_size, self.dtype, self.param_dtype,
                    name="head")(x)
