"""The plain reference of DeepSeek-V3.2-Exp's language model (ISSUE 28's
layer equations), and the seeded weights both sides are given.

Plain ``jax.numpy`` in float32 at matmul precision "highest": no kernel,
no cache, no batching, expanded (not absorbed) multi-head latent
attention, the indexer's selection by ``lax.top_k``, every held expert
run over every token and weighted by its gate. One sequence at a time,
computed in blocks of queries and of heads so that a sequence padded to
16384 fits beside the weights. It imports nothing of the program;
:func:`make_params` lays the weights out under the names the program's
model (``deepseek_v32_lm``) reads them by, and that layout is all the two
share.

The equations (``u`` the RMS-normalised input of a sublayer, eps 1e-6, no
bias but the index key's LayerNorm):

- block: ``x = h + Attn(RMSNorm(h))``, ``out = x + FFN(RMSNorm(x))``;
  FFN is SwiGLU in the leading dense layers, else the expert layer;
  final RMSNorm, untied head.
- MLA: ``c_q = RMSNorm(W_dq u)``; ``q = W_uq c_q`` -> heads x (nope |
  rope); ``[c_kv | k_r] = W_dkv u``, ``c_kv = RMSNorm(c_kv)``; rope (YaRN)
  on ``q``'s rope channels and on ``k_r`` (one head shared by all) at the
  token's position; ``[k_nope | v] = W_ukv c_kv``; score ``(q_nope .
  k_nope + q_rope . k_r) * (nope + rope) ** -0.5 * m ** 2``, ``m = 0.1
  ln(factor) + 1``; causal softmax over the selected positions only.
- indexer: ``q_i = W_qi c_q`` -> heads; ``k_i = LayerNorm(W_ki u)`` (one
  head); rope on the first ``rope`` channels of both; ``w = W_w u *
  heads ** -0.5 * dim ** -0.5``; ``I[t, s] = sum_j w[t, j] relu(q_i[t, j]
  . k_i[s])``; ``t`` attends the ``min(t + 1, index_topk)`` positions ``s
  <= t`` with the largest ``I[t, s]`` (all positions that tie with the
  last one chosen are in).
- expert layer: ``s = sigmoid(W_r u)``; selection on ``s + b``: each
  group scores the sum of its two best, the best ``topk_group`` groups
  stay, the best ``num_experts_per_tok`` experts among them are chosen;
  gates are the chosen ``s`` over their sum, times
  ``routed_scaling_factor``; ``y = shared(u) + sum gate_e expert_e(u)``
  **over the chosen experts this chip holds** (``experts_held`` from
  ``expert_rank * experts_held``); the normalisation runs over all
  chosen.

Left out, here and in the program: the indexer's FP8 quantisation and
the Hadamard rotation before it, the multi-token-prediction module; rope
pairs channel ``i`` with ``i + half``.

``precision="int8"`` is the control of "How correct is decided": every
linear layer's operands rounded to int8 (one scale per token and per
output channel), the cached latent and index key rounded per token; the
router's scores stay float32, as the configuration states them.
``precision="dense"`` is the second control: float32 throughout with the
selection switched off (every causal position attended). Both have to
come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
PRECISIONS = ("f32", "int8", "dense")
QUERY_BLOCK = 128  # queries a step of the attention and of the indexer
HEAD_BLOCK = 16    # heads whose keys and values are expanded at once
ROW_BLOCK = 2048   # rows of a layer computed at once, keys of all beside
# W_uq's initial scale. Unit-variance queries and keys under the published
# softmax scale (m ** 2 = 1.87 on top of (nope + rope) ** -0.5) give
# attention logits a spread of 1.87: with random weights the softmax then
# puts a twentieth of its mass on single positions that the indexer
# (random too, so blind to them) keeps or drops by a rounding, and half
# of all greedy tokens differ between bfloat16 and float32. At a half the
# spread is 0.94: attention still reads particular positions (the dense
# control moves nine tokens of ten) and rounding moves a quarter.
QUERY_INIT_SCALE = 0.5

# published values a configuration's "model" may leave out
DEFAULTS = dict(
    vocab_size=129280, d_model=7168, num_layers=61, first_k_dense=3,
    num_heads=128, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, index_n_heads=64,
    index_head_dim=128, index_topk=2048, intermediate_size=18432,
    moe_intermediate_size=2048, n_routed_experts=256, n_shared_experts=1,
    num_experts_per_tok=8, n_group=8, topk_group=4,
    routed_scaling_factor=2.5, experts_held=None, expert_rank=0,
    rope_theta=10000.0, rope_factor=40.0, rope_original_len=4096,
    rope_beta_fast=32.0, rope_beta_slow=1.0)


def sizes(config: dict) -> dict:
    m = dict(DEFAULTS, **{k: v for k, v in config["model"].items()
                          if k in DEFAULTS})
    if m["experts_held"] is None:
        m["experts_held"] = m["n_routed_experts"]
    return m


def key_of(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# -- the weights --------------------------------------------------------------


def _shapes(m: dict) -> dict:
    d, H, R, Q = (m["d_model"], m["num_heads"], m["kv_lora_rank"],
                  m["q_lora_rank"])
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    J, Di, F, held = (m["index_n_heads"], m["index_head_dim"],
                      m["moe_intermediate_size"], m["experts_held"])
    attn = {
        "wq_a": (d, Q), "q_norm": (Q,), "wq_b": (Q, H, nope + rope),
        "wkv_a": (d, R + rope), "kv_norm": (R,), "wkv_b": (R, H, nope + vd),
        "wo": (H, vd, d), "index_wq_b": (Q, J, Di), "index_wk": (d, Di),
        "index_k_norm_scale": (Di,), "index_k_norm_bias": (Di,),
        "index_weights_proj": (d, J)}

    def swiglu(width):
        return {"w_gate": (d, width), "w_up": (d, width),
                "w_down": (width, d)}

    tree = {"embed": {"embedding": (m["vocab_size"], d)}, "norm": (d,),
            "head": (d, m["vocab_size"])}
    for i in range(m["num_layers"]):
        layer = {"attn_norm": (d,), "ffn_norm": (d,), "attn": attn}
        if i < m["first_k_dense"]:
            layer["mlp"] = swiglu(m["intermediate_size"])
        else:
            layer["moe"] = {
                "router": (d, m["n_routed_experts"]),
                "e_score_correction_bias": (m["n_routed_experts"],),
                "w_gate": (held, d, F), "w_up": (held, d, F),
                "w_down": (held, F, d),
                "shared": swiglu(F * m["n_shared_experts"])}
        tree[f"layers_{i}"] = layer
    return tree


# leaves whose leading axes are not fan-in: (leading axes skipped, fan-in axes)
_FAN_IN = {"wo": (0, 2), "w_gate": (-1, 1), "w_up": (-1, 1),
           "w_down": (-1, 1)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_params(model_items, dtype, key):
    m = dict(model_items)
    leaves = []

    def walk(node, path):
        for name, sub in sorted(node.items()):
            if isinstance(sub, dict):
                walk(sub, path + (name,))
            else:
                leaves.append((path + (name,), sub))

    walk(_shapes(m), ())
    out: dict = {}
    for i, (path, shape) in enumerate(leaves):
        noise = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
        leaf = path[-1]
        store = dtype
        if leaf.endswith("norm") or leaf.endswith("norm_scale"):
            value = 1.0 + 0.02 * noise
        elif leaf in ("index_k_norm_bias", "e_score_correction_bias"):
            # small and not zero, so that a dropped one shows
            value = 0.02 * noise
            if leaf == "e_score_correction_bias":
                store = jnp.float32
        elif leaf == "embedding":
            value = noise / math.sqrt(shape[-1])
        else:
            skip, axes = _FAN_IN.get(leaf, (0, 1))
            if skip < 0:  # an expert stack has one more leading axis
                skip = len(shape) - 2
            value = noise / math.sqrt(math.prod(shape[skip:skip + axes]))
            if leaf == "wq_b":
                value = value * QUERY_INIT_SCALE
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[leaf] = value.astype(store)
    return {"params": out}


def make_params(config: dict, seed: int):
    """``{"params": ...}`` on the default device, in one jitted call, from
    the seed, in the dtype ``config["precision"]["parameters"]`` states
    (the router's correction bias float32)."""
    items = tuple(sorted(sizes(config).items()))
    return _make_params(items, config["precision"]["parameters"],
                        key_of(seed))


# -- the mathematics ----------------------------------------------------------


def _f32(x):
    return x.astype(jnp.float32)


def _round(x, axis):
    """``x`` rounded to int8 steps with one scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _linear(x, kernel, precision):
    """``x [T, in] @ kernel [in, ...]``."""
    k = _f32(kernel).reshape(kernel.shape[0], -1)
    if precision == "int8":
        x, k = _round(x, -1), _round(k, 0)
    return (x @ k).reshape(x.shape[:-1] + kernel.shape[1:])


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * _f32(scale)


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * _f32(scale) + _f32(bias)


def yarn_inv_freq(m: dict) -> np.ndarray:
    dim, theta = m["qk_rope_head_dim"], m["rope_theta"]
    freqs = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return dim * math.log(m["rope_original_len"] / (turns * 2 * math.pi)
                              ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(m["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(m["rope_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    # channels that turn fast keep their frequency (ramp 0), slow ones
    # are divided by the factor (ramp 1)
    return (freqs * (1 - ramp) + freqs / m["rope_factor"] * ramp).astype(
        np.float32)


def _rope(x, pos, inv_freq):
    """``x [T, (H,) 2 * half]`` rotated at ``pos [T]``, channel ``i``
    paired with ``i + half``."""
    ang = _f32(pos)[:, None] * jnp.asarray(inv_freq)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rope_first(x, pos, inv_freq, n):
    return jnp.concatenate([_rope(x[..., :n], pos, inv_freq), x[..., n:]],
                           -1)


def _fit(n: int, want: int) -> int:
    """The largest of ``want``, ``want / 2`` ... ``QUERY_BLOCK`` that
    divides ``n``, else ``n`` itself (one block)."""
    size = want
    while size >= QUERY_BLOCK:
        if n % size == 0:
            return size
        size //= 2
    return n


def _blocks(x, want):
    """``[n, ...] -> [n / size, size, ...]``, ``size = _fit(n, want)``."""
    size = _fit(x.shape[0], want)
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


def _keys(m, p, u_all, precision):
    """``(c_kv [T, R], k_r [T, rope], k_i [T, Di])``: what a cache would
    hold of every position."""
    T = u_all.shape[0]
    R, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    inv_freq = yarn_inv_freq(m)
    all_pos = jnp.arange(T)
    kv = _linear(u_all, p["wkv_a"], precision)
    c_kv = _rms_norm(kv[:, :R], p["kv_norm"])
    k_r = _rope(kv[:, R:], all_pos, inv_freq)
    k_i = _rope_first(_layer_norm(
        _linear(u_all, p["index_wk"], precision),
        p["index_k_norm_scale"], p["index_k_norm_bias"]),
        all_pos, inv_freq, rope)
    if precision == "int8":  # the cache's own rounding, per token
        both = _round(jnp.concatenate([c_kv, k_r], -1), -1)
        c_kv, k_r, k_i = both[:, :R], both[:, R:], _round(k_i, -1)
    return c_kv, k_r, k_i


def _attention(m, p, keys, u_q, pos_q, precision):
    """``[Q, d]``: the attention sublayer's output for the queries ``u_q``
    at positions ``pos_q``, over the ``keys`` of all ``T`` positions."""
    c_kv, k_r, k_i = keys
    T = c_kv.shape[0]
    H, R = m["num_heads"], m["kv_lora_rank"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    J, Di = m["index_n_heads"], m["index_head_dim"]
    inv_freq = yarn_inv_freq(m)
    mscale = 0.1 * math.log(m["rope_factor"]) + 1.0
    scale = (nope + rope) ** -0.5 * mscale * mscale
    all_pos = jnp.arange(T)
    # the queries
    c_q = _rms_norm(_linear(u_q, p["wq_a"], precision), p["q_norm"])
    q = _linear(c_q, p["wq_b"], precision)  # [Q, H, nope + rope]
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos_q, inv_freq)], -1)
    q_i = _rope_first(_linear(c_q, p["index_wq_b"], precision), pos_q,
                      inv_freq, rope)
    w = _linear(u_q, p["index_weights_proj"], precision) * (
        J ** -0.5 * Di ** -0.5)
    topk = min(m["index_topk"], T)

    def select(args):
        qb, wb, pb = args
        score = jnp.einsum(
            "qjt,qj->qt", jax.nn.relu(jnp.einsum("qjd,td->qjt", qb, k_i)),
            wb)
        causal = all_pos[None, :] <= pb[:, None]
        if precision == "dense":
            return causal
        score = jnp.where(causal, score, -jnp.inf)
        kth = jax.lax.top_k(score, topk)[0][:, -1]
        return causal & (score >= kth[:, None])

    allowed = jax.lax.map(select, (_blocks(q_i, QUERY_BLOCK),
                                   _blocks(w, QUERY_BLOCK),
                                   _blocks(pos_q, QUERY_BLOCK)))

    def heads(args):
        w_ukv, qh = args  # [R, HB, nope + v], [Q, HB, nope + rope]
        kvh = jnp.einsum("tr,rhn->thn", c_kv,
                         _round(_f32(w_ukv), 0) if precision == "int8"
                         else _f32(w_ukv))
        k_nope, v = kvh[..., :nope], kvh[..., nope:]

        def queries(args):
            qb, ok = args
            s = (jnp.einsum("qhd,thd->hqt", qb[..., :nope], k_nope)
                 + jnp.einsum("qhd,td->hqt", qb[..., nope:], k_r)) * scale
            a = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thv->qhv", a, v)

        out = jax.lax.map(queries, (_blocks(qh, QUERY_BLOCK), allowed))
        return out.reshape((-1,) + out.shape[2:])  # [Q, HB, v]

    hb = min(HEAD_BLOCK, H)
    w_blocks = jnp.moveaxis(
        p["wkv_b"].reshape(R, H // hb, hb, -1), 1, 0)
    q_blocks = jnp.moveaxis(
        q.reshape(q.shape[0], H // hb, hb, -1), 1, 0)
    o = jax.lax.map(heads, (w_blocks, q_blocks))  # [H / hb, Q, hb, v]
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape[0], -1)
    return _linear(o, p["wo"].reshape(-1, p["wo"].shape[-1]), precision)


def _swiglu(p, u, precision):
    h = jax.nn.silu(_linear(u, p["w_gate"], precision)) * _linear(
        u, p["w_up"], precision)
    return _linear(h, p["w_down"], precision)


def route(m, scores, bias):
    """``(experts [T, k], gates [T, k])`` of the group-limited top-k."""
    T, E = scores.shape
    G, k = m["n_group"], m["num_experts_per_tok"]
    biased = scores + bias
    group = jax.lax.top_k(biased.reshape(T, G, E // G), 2)[0].sum(-1)
    kept = jax.lax.top_k(group, m["topk_group"])[1]
    in_kept = (kept[:, :, None] == jnp.arange(G)[None, None, :]).any(1)
    masked = jnp.where(jnp.repeat(in_kept, E // G, axis=1), biased,
                       -jnp.inf)
    experts = jax.lax.top_k(masked, k)[1]
    gates = jnp.take_along_axis(scores, experts, 1)
    return experts, gates / gates.sum(-1, keepdims=True) * m[
        "routed_scaling_factor"]


def _expert_layer(m, p, u, precision):
    scores = jax.nn.sigmoid(u @ _f32(p["router"]))
    experts, gates = route(m, scores, p["e_score_correction_bias"])
    y = _swiglu(p["shared"], u, precision)
    first = m["expert_rank"] * m["experts_held"]
    for e in range(m["experts_held"]):
        gate = jnp.where(experts == first + e, gates, 0.0).sum(-1)
        y = y + gate[:, None] * _swiglu(
            {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}, u,
            precision)
    return y


def _layer(m, p, x, rows, precision):
    """One block over the whole sequence ``x [T, d]``; only the rows
    ``rows`` (positions) are computed and returned, ``ROW_BLOCK`` of
    them at a time."""
    u_all = _rms_norm(x, p["attn_norm"])
    keys = _keys(m, p["attn"], u_all, precision)

    def block(at):
        h = x[at] + _attention(m, p["attn"], keys, u_all[at], at, precision)
        u = _rms_norm(h, p["ffn_norm"])
        if "mlp" in p:
            return h + _swiglu(p["mlp"], u, precision)
        return h + _expert_layer(m, p["moe"], u, precision)

    out = jax.lax.map(block, _blocks(rows, ROW_BLOCK))
    return out.reshape((-1,) + out.shape[2:])


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer_all(model_items, p, x, rows, precision):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(model_items), p, x, rows, precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _top(model_items, p, x, rows, precision):
    """The last block at the rows asked for, the final norm and the
    head."""
    with jax.default_matmul_precision("highest"):
        m = dict(model_items)
        last = f"layers_{m['num_layers'] - 1}"
        h = _layer(m, p[last], x, rows, precision)
        return _linear(_rms_norm(h, p["norm"]), p["head"], precision)


def forward_logits(config: dict, variables, tokens, at,
                   precision: str = "f32", pad_to: int | None = None):
    """``[len(at), V]`` float32 logits of the one sequence ``tokens`` at
    the positions ``at``, under ``variables``, in ``precision``, the
    sequence padded to ``pad_to`` so that few lengths compile."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    m = sizes(config)
    items = tuple(sorted(m.items()))
    p = variables["params"]
    tokens = np.asarray(tokens, np.int32)
    T = max(pad_to or len(tokens), len(tokens))
    if T > QUERY_BLOCK:
        T = -(-T // QUERY_BLOCK) * QUERY_BLOCK
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    x = _f32(p["embed"]["embedding"][jnp.asarray(padded)])
    every = jnp.arange(T)
    for i in range(m["num_layers"] - 1):
        x = _layer_all(items, p[f"layers_{i}"], x, every, precision)
    at = np.asarray(at, np.int64)
    # few widths of the last block compile: powers of two from 16
    n = max(16, 1 << (len(at) - 1).bit_length())
    rows = np.zeros((n,), np.int32)
    rows[:len(at)] = at
    top = {k: p[k] for k in ("norm", "head",
                             f"layers_{m['num_layers'] - 1}")}
    return np.asarray(_top(items, top, x, jnp.asarray(rows), precision)
                      )[:len(at)]
