"""The plain reference of GLM-4.7-Flash's language model (``model_type``
``glm4_moe_lite``; ISSUE 41's layer equations) with its
multi-token-prediction module, and the seeded weights both sides are
given.

Plain ``jax.numpy`` in float32 at matmul precision "highest": no kernel,
no cache, no batching, no packing, expanded (not absorbed) multi-head
latent attention over every causal position, every held expert run over
every token and weighted by its gate. One sequence at a time, computed
in blocks of queries, of heads and of rows so that a sequence padded to
8192 fits beside the weights. It imports nothing of the program;
:func:`make_params` lays the weights out under the names the program's
model (``glm4_moe_lite_lm``) reads them by, and that layout is all the
two share.

The equations (``u`` the RMS-normalised input of a sublayer, eps 1e-5,
no bias):

- block: ``x = h + Attn(RMSNorm(h))``, ``out = x + FFN(RMSNorm(x))``;
  FFN is SwiGLU in the leading dense layer, else the expert layer; final
  RMSNorm, untied head.
- MLA: ``c_q = RMSNorm(W_qa u)``; ``q = W_qb c_q`` -> heads x (nope |
  rope); ``[c_kv | k_r] = W_kva u``, ``c_kv = RMSNorm(c_kv)``; rope (base
  ``rope_theta``, no scaling) on ``q``'s rope channels and on ``k_r``
  (one head shared by all) at the token's position; ``[k_nope | v] =
  W_kvb c_kv``; score ``(q_nope . k_nope + q_rope . k_r) * (nope + rope)
  ** -0.5``; causal softmax over every position up to the query's.
- expert layer: ``s = sigmoid(W_r u)``; the ``num_experts_per_tok``
  largest of ``s + b`` are chosen; gates are the chosen ``s`` over their
  sum, times ``routed_scaling_factor``; ``y = shared(u) + sum gate_e
  expert_e(u)`` over the chosen experts this chip holds.
- MTP module (DeepSeek-V3 report, section 2.2): with ``h_t`` the main
  model's hidden state at ``t`` AFTER its final norm and ``x_{t+1}`` the
  next token, ``u_t = W_eh [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(h_t)]``,
  one expert layer of the main model's kind over ``u`` (causal among the
  ``u``, position ``t``), the module's own final RMSNorm, the main
  model's head: logits for ``x_{t+2}``. ``Emb`` and the head are the
  main model's leaves.

Rope pairs channel ``i`` with ``i + half``, here and in the program.

``precision="int8"`` is the control of "How correct is decided": every
linear layer's operands rounded to int8 (one scale per token and per
output channel), the latent entry rounded per token; the router's scores
stay float32. ``precision="no_shared"`` is the second control: float32
throughout with the shared expert left out of every expert layer. Both
have to come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
PRECISIONS = ("f32", "int8", "no_shared")
QUERY_BLOCK = 128  # queries a step of the attention
HEAD_BLOCK = 10    # heads whose keys and values are expanded at once
ROW_BLOCK = 2048   # rows of a layer computed at once, keys of all beside
HEAD_ROWS = 512    # positions whose logits are computed at once

# published values a configuration's "model" may leave out
DEFAULTS = dict(
    vocab_size=154880, d_model=2048, num_layers=47, first_k_dense=1,
    num_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, intermediate_size=10240,
    moe_intermediate_size=1536, n_routed_experts=64, n_shared_experts=1,
    num_experts_per_tok=4, routed_scaling_factor=1.8, rope_theta=1e6)


def sizes(config: dict) -> dict:
    return dict(DEFAULTS, **{k: v for k, v in config["model"].items()
                             if k in DEFAULTS})


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
    F, held = m["moe_intermediate_size"], m["n_routed_experts"]
    attn = {
        "wq_a": (d, Q), "q_norm": (Q,), "wq_b": (Q, H, nope + rope),
        "wkv_a": (d, R + rope), "kv_norm": (R,), "wkv_b": (R, H, nope + vd),
        "wo": (H, vd, d)}

    def swiglu(width):
        return {"w_gate": (d, width), "w_up": (d, width),
                "w_down": (width, d)}

    def layer(dense):
        out = {"attn_norm": (d,), "ffn_norm": (d,), "attn": attn}
        if dense:
            out["mlp"] = swiglu(m["intermediate_size"])
        else:
            out["moe"] = {
                "router": (d, m["n_routed_experts"]),
                "e_score_correction_bias": (m["n_routed_experts"],),
                "w_gate": (held, d, F), "w_up": (held, d, F),
                "w_down": (held, F, d),
                "shared": swiglu(F * m["n_shared_experts"])}
        return out

    tree = {"embed": {"embedding": (m["vocab_size"], d)}, "norm": (d,),
            "head": (d, m["vocab_size"]),
            # the module shares the embedding and the head: no copies
            "mtp": {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                    "norm": (d,), "layer": layer(False)}}
    for i in range(m["num_layers"]):
        tree[f"layers_{i}"] = layer(i < m["first_k_dense"])
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
        if leaf.endswith("norm"):
            value = 1.0 + 0.02 * noise
        elif leaf == "e_score_correction_bias":
            # small and not zero, so that a dropped one shows
            value, store = 0.02 * noise, jnp.float32
        elif leaf == "embedding":
            value = noise / math.sqrt(shape[-1])
        else:
            skip, axes = _FAN_IN.get(leaf, (0, 1))
            if skip < 0:  # an expert stack has one more leading axis
                skip = len(shape) - 2
            value = noise / math.sqrt(math.prod(shape[skip:skip + axes]))
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


def inv_freq(m: dict) -> np.ndarray:
    dim = m["qk_rope_head_dim"]
    return (1.0 / m["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def _rope(x, pos, freqs):
    """``x [T, (H,) 2 * half]`` rotated at ``pos [T]``, channel ``i``
    paired with ``i + half``."""
    ang = _f32(pos)[:, None] * jnp.asarray(freqs)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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
    """``(c_kv [T, R], k_r [T, rope])``: what a cache would hold of
    every position."""
    R = m["kv_lora_rank"]
    kv = _linear(u_all, p["wkv_a"], precision)
    c_kv = _rms_norm(kv[:, :R], p["kv_norm"])
    k_r = _rope(kv[:, R:], jnp.arange(u_all.shape[0]), inv_freq(m))
    if precision == "int8":  # the cache's own rounding, per token
        both = _round(jnp.concatenate([c_kv, k_r], -1), -1)
        c_kv, k_r = both[:, :R], both[:, R:]
    return c_kv, k_r


def _attention(m, p, keys, u_q, pos_q, precision):
    """``[Q, d]``: the attention sublayer's output for the queries ``u_q``
    at positions ``pos_q``, over the ``keys`` of all ``T`` positions."""
    c_kv, k_r = keys
    T = c_kv.shape[0]
    H, R = m["num_heads"], m["kv_lora_rank"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    scale = (nope + rope) ** -0.5
    all_pos = jnp.arange(T)
    c_q = _rms_norm(_linear(u_q, p["wq_a"], precision), p["q_norm"])
    q = _linear(c_q, p["wq_b"], precision)  # [Q, H, nope + rope]
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos_q, inv_freq(m))], -1)

    def heads(args):
        w_ukv, qh = args  # [R, HB, nope + v], [Q, HB, nope + rope]
        kvh = jnp.einsum("tr,rhn->thn", c_kv,
                         _round(_f32(w_ukv), 0) if precision == "int8"
                         else _f32(w_ukv))
        k_nope, v = kvh[..., :nope], kvh[..., nope:]

        def queries(args):
            qb, pb = args
            s = (jnp.einsum("qhd,thd->hqt", qb[..., :nope], k_nope)
                 + jnp.einsum("qhd,td->hqt", qb[..., nope:], k_r)) * scale
            ok = all_pos[None, :] <= pb[:, None]
            a = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thv->qhv", a, v)

        out = jax.lax.map(queries, (_blocks(qh, QUERY_BLOCK),
                                    _blocks(pos_q, QUERY_BLOCK)))
        return out.reshape((-1,) + out.shape[2:])  # [Q, HB, v]

    hb = math.gcd(HEAD_BLOCK, H)
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
    """``(experts [T, k], gates [T, k])``: the ``k`` largest of ``scores
    + bias``, gated by the scores themselves over their sum."""
    experts = jax.lax.top_k(scores + bias, m["num_experts_per_tok"])[1]
    gates = jnp.take_along_axis(scores, experts, 1)
    return experts, gates / gates.sum(-1, keepdims=True) * m[
        "routed_scaling_factor"]


def _expert_layer(m, p, u, precision):
    scores = jax.nn.sigmoid(u @ _f32(p["router"]))
    experts, gates = route(m, scores, p["e_score_correction_bias"])

    def one(y, args):
        e, w = args  # every expert over every token, by its gate
        gate = jnp.where(experts == e, gates, 0.0).sum(-1)
        return y + gate[:, None] * _swiglu(w, u, precision), None

    y = (jnp.zeros_like(u) if precision == "no_shared"
         else _swiglu(p["shared"], u, precision))
    return jax.lax.scan(one, y, (
        jnp.arange(m["n_routed_experts"]),
        {k: p[k] for k in ("w_gate", "w_up", "w_down")}))[0]


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


@jax.jit
def _final_norm(scale, x):
    return _rms_norm(x, scale)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(head, h, precision):
    with jax.default_matmul_precision("highest"):
        return _linear(h, head, precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _module_input(model_items, p, emb_next, hidden, precision):
    with jax.default_matmul_precision("highest"):
        return _linear(jnp.concatenate(
            [_rms_norm(emb_next, p["enorm"]),
             _rms_norm(hidden, p["hnorm"])], -1), p["eh_proj"], precision)


def _padded(tokens, pad_to):
    tokens = np.asarray(tokens, np.int32)
    T = max(pad_to or len(tokens), len(tokens))
    if T > QUERY_BLOCK:
        T = -(-T // QUERY_BLOCK) * QUERY_BLOCK
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    return padded


def _rows_of(at):
    """``at`` padded to one of few widths: powers of two from 16."""
    at = np.asarray(at, np.int64)
    rows = np.zeros((max(16, 1 << (len(at) - 1).bit_length()),), np.int32)
    rows[:len(at)] = at
    return jnp.asarray(rows)


def _logits(p, h, n, precision):
    """``[n, V]`` (numpy): the head over the first ``n`` rows of ``h``,
    ``HEAD_ROWS`` at a time: 3072 positions of a 154 880-word
    vocabulary are 1.9 GB, which lie on the host."""
    out = [np.asarray(_head(p["head"], h[i:i + HEAD_ROWS], precision))
           for i in range(0, h.shape[0], HEAD_ROWS)]
    return np.concatenate(out)[:n]


def _hidden(m, p, padded, rows, precision):
    """The main model's final normed hidden states ``[len(rows), d]`` at
    the positions ``rows`` of the padded sequence."""
    items = tuple(sorted(m.items()))
    x = _f32(p["embed"]["embedding"][jnp.asarray(padded)])
    every = jnp.arange(len(padded))
    for i in range(m["num_layers"] - 1):
        x = _layer_all(items, p[f"layers_{i}"], x, every, precision)
    h = _layer_all(items, p[f"layers_{m['num_layers'] - 1}"], x, rows,
                   precision)
    return _final_norm(p["norm"], h)


def forward_logits(config: dict, variables, tokens, at,
                   precision: str = "f32", pad_to: int | None = None):
    """``[len(at), V]`` float32 logits of the one sequence ``tokens`` at
    the positions ``at``, under ``variables``, in ``precision``, the
    sequence padded to ``pad_to`` so that few lengths compile."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    m, p = sizes(config), variables["params"]
    h = _hidden(m, p, _padded(tokens, pad_to), _rows_of(at), precision)
    return _logits(p, h, len(at), precision)


def mtp_logits(config: dict, variables, tokens, at, precision: str = "f32",
               pad_to: int | None = None):
    """``[len(at), V]`` float32 logits of the multi-token-prediction
    module at the positions ``at`` of the one sequence ``tokens``: the
    module's row ``t`` reads the main model's hidden state at ``t`` and
    the embedding of ``tokens[t + 1]``, and predicts ``tokens[t + 2]``.
    Every ``t`` in ``at`` is below ``len(tokens) - 1``."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    at = np.asarray(at, np.int64)
    if len(at) and at.max() >= len(tokens) - 1:
        raise ValueError("the module's row t needs tokens[t + 1]")
    m, p = sizes(config), variables["params"]
    items = tuple(sorted(m.items()))
    padded = _padded(tokens, pad_to)
    every = jnp.arange(len(padded))
    hidden = _hidden(m, p, padded, every, precision)
    emb_next = _f32(p["embed"]["embedding"][jnp.asarray(np.roll(padded, -1))])
    u = _module_input(items, p["mtp"], emb_next, hidden, precision)
    g = _layer_all(items, p["mtp"]["layer"], u, _rows_of(at), precision)
    return _logits(p, _final_norm(p["mtp"]["norm"], g), len(at), precision)
