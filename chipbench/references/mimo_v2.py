"""The plain reference of MiMo-V2.5's language model (ISSUE 34's layer
equations), and the seeded weights both sides are given.

Plain ``jax.numpy`` in float32 at matmul precision "highest": no kernel,
no cache, no ring, no batching, every held expert run over every token
and weighted by its gate. One sequence at a time, computed in blocks of
rows, of KV heads and of queries so that a sequence padded to 32768
fits beside the weights. It imports nothing of the program;
:func:`make_params` lays the weights out under the names the program's
model (``mimo_v2_lm``) reads them by, and that layout is all the two
share.

The equations (``u`` the RMS-normalised input of a sublayer, eps 1e-5,
no bias anywhere):

- block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; FFN
  is SwiGLU where ``moe_layer_freq`` is 0 (layer 0), else the expert
  layer; final RMSNorm, untied head.
- attention: layer ``l`` is full (``hybrid_layer_pattern[l]`` 0: ``Hk``
  = ``num_kv_heads``, rope base ``rope_theta``, no sink) or window (1:
  ``swa_num_kv_heads``, ``swa_rope_theta``, a learned sink a head). ``q
  = u Wq [T, H, dk]``, ``k = u Wk [T, Hk, dk]``, ``v = value_scale * u
  Wv [T, Hk, dv]``; rotary on channels ``0 .. r - 1`` of q and k, ``r =
  int(dk * partial_rotary_factor)``, channel ``i < r / 2`` paired with
  ``i + r / 2``, angle ``p * theta ** (-2 i / r)``; ``s[t, j] = q_t .
  k_j / sqrt(dk)`` over ``j <= p_t`` (full) or ``p_t - window < j <=
  p_t`` (window); query head ``h`` reads KV head ``h // (H / Hk)``. Full:
  softmax. Window: ``P = exp(s - m) / (exp(b_h - m) + sum_j exp(s - m))``,
  ``m = max(b_h, max_j s)``: the sink takes mass and gives no value.
  ``o = P v``, then ``Wo``.
- expert layer: ``sigma = sigmoid(u Wr)``; the ``num_experts_per_tok``
  largest of ``sigma + c`` are chosen (no group limit); gates are the
  chosen ``sigma`` over their sum, times ``routed_scaling_factor``; ``y =
  sum gate_e expert_e(u)`` **over the chosen experts this chip holds**
  (``experts_held`` from ``expert_rank * experts_held``); the
  normalisation runs over all chosen. No shared expert.

Left out, here and in the program: the vision and audio towers, the
multi-token-prediction module; ``attention_chunk_size`` is unused (the
window slides).

``precision="int8"`` is the control of "How correct is decided": every
linear layer's operands rounded to int8 (one scale per token and per
output channel), keys and values rounded per token and head; the
router's scores stay float32, as the configuration states them.
``precision="no_window"`` is the second control: float32 throughout,
the window layers attending every causal position (sink kept). Both
have to come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
PRECISIONS = ("f32", "int8", "no_window")
QUERY_BLOCK = 128  # queries a step of the attention
ROW_BLOCK = 2048   # rows of a layer computed at once, keys of all beside

# published values a configuration's "model" may leave out
DEFAULTS = dict(
    vocab_size=152576, d_model=4096, num_layers=48, num_heads=64,
    head_dim=192, v_head_dim=128, num_kv_heads=4, swa_num_kv_heads=8,
    sliding_window=128, partial_rotary_factor=0.334, rope_theta=1e7,
    swa_rope_theta=1e4, attention_value_scale=0.707,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    hybrid_layer_pattern=None, moe_layer_freq=None,
    intermediate_size=16384, moe_intermediate_size=2048,
    n_routed_experts=256, num_experts_per_tok=8,
    routed_scaling_factor=1.0, experts_held=None, expert_rank=0)


def sizes(config: dict) -> dict:
    m = dict(DEFAULTS, **{k: v for k, v in config["model"].items()
                          if k in DEFAULTS})
    n = m["num_layers"]
    if m["experts_held"] is None:
        m["experts_held"] = m["n_routed_experts"]
    if m["hybrid_layer_pattern"] is None:  # the published pattern
        m["hybrid_layer_pattern"] = [int(not (i == 0 or i % 6 == 5))
                                     for i in range(n)]
    if m["moe_layer_freq"] is None:
        m["moe_layer_freq"] = [int(i > 0) for i in range(n)]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        m[key] = tuple(m[key][:n])
    return m


def key_of(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _window(m: dict, i: int) -> bool:
    return bool(m["hybrid_layer_pattern"][i])


def _has_sink(m: dict, i: int) -> bool:
    return m["add_swa_attention_sink_bias" if _window(m, i)
             else "add_full_attention_sink_bias"]


# -- the weights --------------------------------------------------------------


def _shapes(m: dict) -> dict:
    d, H, dk, dv = m["d_model"], m["num_heads"], m["head_dim"], m["v_head_dim"]
    F, held = m["moe_intermediate_size"], m["experts_held"]

    def swiglu(width):
        return {"w_gate": (d, width), "w_up": (d, width),
                "w_down": (width, d)}

    tree = {"embed": {"embedding": (m["vocab_size"], d)}, "norm": (d,),
            "head": (d, m["vocab_size"])}
    for i in range(m["num_layers"]):
        Hk = m["swa_num_kv_heads" if _window(m, i) else "num_kv_heads"]
        attn = {"wq": (d, H, dk), "wk": (d, Hk, dk), "wv": (d, Hk, dv),
                "wo": (H, dv, d)}
        if _has_sink(m, i):
            attn["sink"] = (H,)
        layer = {"attn_norm": (d,), "ffn_norm": (d,), "attn": attn}
        if m["moe_layer_freq"][i]:
            layer["moe"] = {
                "router": (d, m["n_routed_experts"]),
                "e_score_correction_bias": (m["n_routed_experts"],),
                "w_gate": (held, d, F), "w_up": (held, d, F),
                "w_down": (held, F, d)}
        else:
            layer["mlp"] = swiglu(m["intermediate_size"])
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
        if leaf.endswith("norm"):
            value = 1.0 + 0.02 * noise
        elif leaf == "sink":
            # of the scores' own spread (q . k / sqrt(dk) has variance 1
            # under these weights), so that a dropped sink shows
            value, store = noise, jnp.float32
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
    (the sinks and the router's correction bias float32)."""
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


def _rope_first(x, pos, r: int, theta: float):
    """``x [T, H, hd]`` with channels ``0 .. r - 1`` rotated at ``pos
    [T]``, channel ``i`` paired with ``i + r / 2``."""
    half = r // 2
    inv_freq = theta ** (-2.0 * np.arange(half, dtype=np.float64) / r)
    ang = _f32(pos)[:, None, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


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


def _kind(m, window: bool):
    """``(Hk, theta, window)`` of a layer of either kind; window 0: full."""
    if window:
        return m["swa_num_kv_heads"], m["swa_rope_theta"], m["sliding_window"]
    return m["num_kv_heads"], m["rope_theta"], 0


def _keys(m, window, p, u_all, precision):
    """``(k [T, Hk, dk], v [T, Hk, dv])`` of every position."""
    _, theta, _ = _kind(m, window)
    r = int(m["head_dim"] * m["partial_rotary_factor"])
    k = _rope_first(_linear(u_all, p["wk"], precision),
                    jnp.arange(u_all.shape[0]), r, theta)
    v = m["attention_value_scale"] * _linear(u_all, p["wv"], precision)
    if precision == "int8":  # a cache's own rounding, per token and head
        k, v = _round(k, -1), _round(v, -1)
    return k, v


def _attention(m, window, p, keys, u_q, pos_q, precision):
    """``[Q, d]``: the attention sublayer's output for the queries ``u_q``
    at positions ``pos_q``, over the ``keys`` of all ``T`` positions."""
    k_all, v_all = keys
    T = k_all.shape[0]
    H, dk = m["num_heads"], m["head_dim"]
    Hk, theta, window = _kind(m, window)
    if precision == "no_window":
        window = 0
    G = H // Hk
    r = int(dk * m["partial_rotary_factor"])
    q = _rope_first(_linear(u_q, p["wq"], precision), pos_q, r, theta)
    sink = (_f32(p["sink"]).reshape(Hk, G) if "sink" in p
            else jnp.full((Hk, G), -jnp.inf))
    all_pos = jnp.arange(T)

    def head(args):
        qh, kh, vh, bh = args  # [Q, G, dk], [T, dk], [T, dv], [G]

        def queries(args):
            qb, pb = args  # [QB, G, dk], [QB]
            if window:
                # each query reads its own window: positions p - window
                # + 1 .. p, those before the sequence's first masked
                at = pb[:, None] - window + 1 + jnp.arange(window)[None]
                ok = (at >= 0)[None]
                at = jnp.maximum(at, 0)
                s = jnp.einsum("qgd,qwd->gqw", qb, kh[at])
                values = vh[at]  # [QB, window, dv]
            else:
                ok = (all_pos[None, :] <= pb[:, None])[None]
                s = jnp.einsum("qgd,td->gqt", qb, kh)
            s = jnp.where(ok, s / math.sqrt(dk), -jnp.inf)
            mx = jnp.maximum(s.max(-1, keepdims=True), bh[:, None, None])
            e = jnp.exp(s - mx)
            a = e / (e.sum(-1, keepdims=True)
                     + jnp.exp(bh[:, None, None] - mx))
            if window:
                return jnp.einsum("gqw,qwv->qgv", a, values)
            return jnp.einsum("gqt,tv->qgv", a, vh)

        out = jax.lax.map(queries, (_blocks(qh, QUERY_BLOCK),
                                    _blocks(pos_q, QUERY_BLOCK)))
        return out.reshape((-1,) + out.shape[2:])  # [Q, G, dv]

    Q = q.shape[0]
    o = jax.lax.map(head, (
        jnp.moveaxis(q.reshape(Q, Hk, G, dk), 1, 0),
        jnp.moveaxis(k_all, 1, 0), jnp.moveaxis(v_all, 1, 0), sink))
    o = jnp.moveaxis(o, 0, 1).reshape(Q, -1)  # [Q, H * dv]
    return _linear(o, p["wo"].reshape(-1, p["wo"].shape[-1]), precision)


def _swiglu(p, u, precision):
    h = jax.nn.silu(_linear(u, p["w_gate"], precision)) * _linear(
        u, p["w_up"], precision)
    return _linear(h, p["w_down"], precision)


def route(m, scores, bias):
    """``(experts [T, k], gates [T, k])``: the ``k`` largest of ``scores
    + bias``, gated by their own scores over their sum."""
    experts = jax.lax.top_k(scores + bias, m["num_experts_per_tok"])[1]
    gates = jnp.take_along_axis(scores, experts, 1)
    return experts, gates / gates.sum(-1, keepdims=True) * m[
        "routed_scaling_factor"]


def _expert_layer(m, p, u, precision):
    scores = jax.nn.sigmoid(u @ _f32(p["router"]))
    experts, gates = route(m, scores, p["e_score_correction_bias"])
    y = jnp.zeros_like(u)
    first = m["expert_rank"] * m["experts_held"]
    for e in range(m["experts_held"]):
        gate = jnp.where(experts == first + e, gates, 0.0).sum(-1)
        y = y + gate[:, None] * _swiglu(
            {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}, u,
            precision)
    return y


def _layer(m, window, p, x, rows, precision):
    """One block (a window layer where ``window``, else a full one; its
    FFN is what ``p`` holds) over the whole sequence ``x [T, d]``; only
    the rows ``rows`` (positions) are computed and returned,
    ``ROW_BLOCK`` of them at a time."""
    u_all = _rms_norm(x, p["attn_norm"])
    keys = _keys(m, window, p["attn"], u_all, precision)

    def block(at):
        h = x[at] + _attention(m, window, p["attn"], keys, u_all[at], at,
                               precision)
        u = _rms_norm(h, p["ffn_norm"])
        if "mlp" in p:
            return h + _swiglu(p["mlp"], u, precision)
        return h + _expert_layer(m, p["moe"], u, precision)

    out = jax.lax.map(block, _blocks(rows, ROW_BLOCK))
    return out.reshape((-1,) + out.shape[2:])


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _layer_all(model_items, window, p, x, rows, precision):
    """One program a kind of layer (and of FFN, by ``p``'s own
    structure), not one a layer."""
    with jax.default_matmul_precision("highest"):
        return _layer(dict(model_items), window, p, x, rows, precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _top(model_items, p, x, rows, precision):
    """The last block at the rows asked for, the final norm and the
    head."""
    with jax.default_matmul_precision("highest"):
        m = dict(model_items)
        last = m["num_layers"] - 1
        h = _layer(m, _window(m, last), p[f"layers_{last}"], x, rows,
                   precision)
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
        x = _layer_all(items, _window(m, i), p[f"layers_{i}"], x, every,
                       precision)
    at = np.asarray(at, np.int64)
    # few widths of the last block compile: powers of two from 16
    n = max(16, 1 << (len(at) - 1).bit_length())
    rows = np.zeros((n,), np.int32)
    rows[:len(at)] = at
    top = {k: p[k] for k in ("norm", "head",
                             f"layers_{m['num_layers'] - 1}")}
    return np.asarray(_top(items, top, x, jnp.asarray(rows), precision)
                      )[:len(at)]
