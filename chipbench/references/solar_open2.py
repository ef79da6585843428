"""The plain reference of Solar-Open2-250B's language model (ISSUE 36's
layer equations), and the seeded weights both sides are given.

Plain ``jax.numpy`` in float32 at matmul precision "highest": no kernel,
no cache, no chunk form, no batching. A KDA layer is the token-by-token
recurrence under ``lax.scan``; a GQA layer is a masked softmax over
every causal position, a block of queries at a time; every held expert
runs over every token and is weighted by its gate. One sequence at a
time: at 8192 positions the widest intermediate is ``[T, 8192]``
float32, which fits beside the weights. It imports nothing of the
program; :func:`make_params` lays the weights out under the names the
program's model (``solar_open2_lm``) reads them by, and that layout is
all the two share.

The equations (``u`` the RMS-normalised input of a sublayer, eps 1e-5,
no bias anywhere but ``dt_bias``):

- block: ``h = x + Mix(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; final
  RMSNorm, untied head.
- GQA layer (``l in gqa_layers``): ``q = u Wq [T, H, 128]``, ``k = u
  Wk``, ``v = u Wv [T, Hk, 128]``, no rotary; ``s[t, j] = q_t . k_j /
  sqrt(128)`` over ``j <= t``; query head ``h`` reads KV head ``h // (H
  / Hk)``; ``a = softmax(s) v``; output ``(a * sigmoid(u Wg)) Wo``.
- KDA layer, per head of ``kda_num_heads``, ``d = kda_head_dim``:
  ``q~, k~, v~ = u Wq, u Wk, u Wv``, each through its own causal
  depthwise convolution of width 4 (``conv(z)_t = sum_i c_i z_{t - 3 +
  i}``, zeros before position 0) and SiLU; ``q, k`` divided by their
  2-norm a head (``x * rsqrt(sum x^2 + 1e-6)``); ``g_t = -exp(A_log_h)
  * softplus((u Wa1) Wa2 + dt_bias)`` a key channel; ``beta_t = 2 *
  sigmoid(u Wb)`` a head; from ``S = 0``:

      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t / sqrt(d)

  output ``(RMSNorm_d(o_t) * sigmoid((u Wg1) Wg2)) Wo``.
- expert layer: ``sigma = sigmoid(u Wr)``; the ``num_experts_per_tok``
  largest of ``sigma + c`` are chosen; gates are the chosen ``sigma``
  over their sum, times ``routed_scaling_factor``; ``y = shared(u) + sum
  gate_e expert_e(u)`` **over the chosen experts this chip holds**
  (``experts_held`` from ``expert_rank * experts_held``).

``precision="int8"`` is the control of "How correct is decided": every
linear layer's operands rounded to int8 (one scale per token and per
output channel), a GQA layer's keys and values rounded per token and
head; router scores and the recurrent state stay float32.
``precision="no_decay"`` is the second control: float32 throughout with
``alpha = 1`` (``g = 0``), a wrong gate. Both have to come out as not
correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
NORM_EPS = 1e-6  # of q's and k's 2-norm
PRECISIONS = ("f32", "int8", "no_decay")
QUERY_BLOCK = 128  # queries a step of the softmax attention

# published values a configuration's "model" may leave out
DEFAULTS = dict(
    vocab_size=196608, d_model=4096, num_layers=48, num_heads=64,
    head_dim=128, num_kv_heads=8, gqa_layers=None, kda_num_heads=64,
    kda_head_dim=128, short_conv_kernel_size=4, kda_gate_rank=128,
    moe_intermediate_size=1280, n_routed_experts=320,
    num_experts_per_tok=8, n_shared_experts=1, routed_scaling_factor=1.0,
    experts_held=None, expert_rank=0)


def sizes(config: dict) -> dict:
    m = dict(DEFAULTS, **{k: v for k, v in config["model"].items()
                          if k in DEFAULTS})
    if m["experts_held"] is None:
        m["experts_held"] = m["n_routed_experts"]
    if m["gqa_layers"] is None:  # the published pattern: gqa_interval 3
        m["gqa_layers"] = range(0, m["num_layers"], 4)
    m["gqa_layers"] = tuple(i for i in m["gqa_layers"]
                            if i < m["num_layers"])
    return m


def key_of(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# -- the weights --------------------------------------------------------------


def _shapes(m: dict) -> dict:
    d, H, hd, Hk = (m["d_model"], m["num_heads"], m["head_dim"],
                    m["num_kv_heads"])
    Hl, dl, r = m["kda_num_heads"], m["kda_head_dim"], m["kda_gate_rank"]
    W = m["short_conv_kernel_size"]
    F, held = m["moe_intermediate_size"], m["experts_held"]
    gqa = {"wq": (d, H, hd), "wk": (d, Hk, hd), "wv": (d, Hk, hd),
           "wg": (d, H, hd), "wo": (H, hd, d)}
    kda = {"wq": (d, Hl, dl), "wk": (d, Hl, dl), "wv": (d, Hl, dl),
           "conv_q": (W, Hl, dl), "conv_k": (W, Hl, dl),
           "conv_v": (W, Hl, dl), "wa1": (d, r), "wa2": (r, Hl, dl),
           "A_log": (Hl,), "dt_bias": (Hl, dl), "wb": (d, Hl),
           "wg1": (d, r), "wg2": (r, Hl, dl), "out_norm": (dl,),
           "wo": (Hl, dl, d)}
    moe = {"router": (d, m["n_routed_experts"]),
           "e_score_correction_bias": (m["n_routed_experts"],),
           "w_gate": (held, d, F), "w_up": (held, d, F),
           "w_down": (held, F, d)}
    if m["n_shared_experts"]:
        Fs = F * m["n_shared_experts"]
        moe["shared"] = {"w_gate": (d, Fs), "w_up": (d, Fs),
                         "w_down": (Fs, d)}
    tree = {"embed": {"embedding": (m["vocab_size"], d)}, "norm": (d,),
            "head": (d, m["vocab_size"])}
    for i in range(m["num_layers"]):
        mixer = ({"attn": gqa} if i in m["gqa_layers"] else {"kda": kda})
        tree[f"layers_{i}"] = {"attn_norm": (d,), "ffn_norm": (d,),
                               "moe": moe, **mixer}
    return tree


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
        k = jax.random.fold_in(key, i)
        noise = jax.random.normal(k, shape, jnp.float32)
        leaf = path[-1]
        store = dtype
        if leaf.endswith("norm"):
            value = 1.0 + 0.02 * noise
        elif leaf == "A_log":
            # exp(A_log) uniform in [1, 16]: slow and fast heads
            value, store = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0)), jnp.float32
        elif leaf == "dt_bias":
            # the inverse softplus of a log-uniform step in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            value, store = dt + jnp.log(-jnp.expm1(-dt)), jnp.float32
        elif leaf == "e_score_correction_bias":
            # small and not zero, so that a dropped one shows
            value, store = 0.02 * noise, jnp.float32
        elif leaf == "embedding":
            value = noise / math.sqrt(shape[-1])
        elif leaf == "wo":  # [H, hd, d]: fan-in over both leading axes
            value = noise / math.sqrt(shape[0] * shape[1])
        elif leaf in ("w_gate", "w_up", "w_down") and len(shape) == 3:
            value = noise / math.sqrt(shape[1])  # an expert stack
        else:
            value = noise / math.sqrt(shape[0])
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[leaf] = value.astype(store)
    return {"params": out}


def make_params(config: dict, seed: int):
    """``{"params": ...}`` on the default device, in one jitted call, from
    the seed, in the dtype ``config["precision"]["parameters"]`` states
    (``A_log``, ``dt_bias`` and the router's correction bias float32)."""
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


def _gqa(m, p, u, precision):
    """``[T, d]``: the gated softmax attention sublayer, no positional
    signal, over the whole sequence ``u [T, d]``."""
    T = u.shape[0]
    H, Hk, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    G = H // Hk
    q = _linear(u, p["wq"], precision).reshape(T, Hk, G, hd)
    k = _linear(u, p["wk"], precision)
    v = _linear(u, p["wv"], precision)
    if precision == "int8":  # a cache's own rounding, per token and head
        k, v = _round(k, -1), _round(v, -1)
    size = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    pos = jnp.arange(T)

    def queries(args):
        qb, pb = args  # [Q, Hk, G, hd], [Q]
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(hd)
        s = jnp.where((pos[None, :] <= pb[:, None])[None, None], s,
                      -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v)

    a = jax.lax.map(queries, (q.reshape(T // size, size, Hk, G, hd),
                              pos.reshape(T // size, size)))
    gate = jax.nn.sigmoid(_linear(u, p["wg"], precision))
    o = a.reshape(T, H, hd) * gate
    return _linear(o.reshape(T, H * hd), p["wo"].reshape(H * hd, -1),
                   precision)


def _short_conv(z, c):
    """``conv(z)_t = sum_i c_i z_{t - W + 1 + i}`` a channel, zeros
    before position 0; ``z [T, ...]``, ``c [W, ...]``."""
    W, T = c.shape[0], z.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((W - 1,) + z.shape[1:], z.dtype), z], 0)
    return sum(_f32(c[i]) * padded[i:i + T] for i in range(W))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + NORM_EPS)


def _kda(m, p, u, precision):
    """``[T, d]``: the gated delta-rule sublayer, token by token."""
    dl = m["kda_head_dim"]

    def branch(w, c):
        return jax.nn.silu(_short_conv(_linear(u, p[w], precision), p[c]))

    q, k = _unit(branch("wq", "conv_q")), _unit(branch("wk", "conv_k"))
    v = branch("wv", "conv_v")
    a = _linear(_linear(u, p["wa1"], precision), p["wa2"], precision)
    g = -jnp.exp(_f32(p["A_log"]))[None, :, None] * jax.nn.softplus(
        a + _f32(p["dt_bias"]))
    if precision == "no_decay":
        g = jnp.zeros_like(g)
    beta = 2.0 * jax.nn.sigmoid(_linear(u, p["wb"], precision))

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [H, d] each; b_t [H]
        S = jnp.exp(g_t)[:, :, None] * S
        kS = jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - kS)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S) / math.sqrt(dl)

    S0 = jnp.zeros((m["kda_num_heads"], dl, dl), jnp.float32)
    _, o = jax.lax.scan(step, S0, (q, k, v, g, beta))  # [T, H, d]
    gate = jax.nn.sigmoid(
        _linear(_linear(u, p["wg1"], precision), p["wg2"], precision))
    o = _rms_norm(o, p["out_norm"]) * gate
    return _linear(o.reshape(o.shape[0], -1),
                   p["wo"].reshape(-1, p["wo"].shape[-1]), precision)


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
    y = (_swiglu(p["shared"], u, precision) if "shared" in p
         else jnp.zeros_like(u))
    first = m["expert_rank"] * m["experts_held"]
    for e in range(m["experts_held"]):
        gate = jnp.where(experts == first + e, gates, 0.0).sum(-1)
        y = y + gate[:, None] * _swiglu(
            {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}, u,
            precision)
    return y


def _layer(m, p, x, precision):
    """One block over the whole sequence ``x [T, d]``; the mixer is what
    ``p`` holds."""
    u = _rms_norm(x, p["attn_norm"])
    if "attn" in p:
        h = x + _gqa(m, p["attn"], u, precision)
    else:
        h = x + _kda(m, p["kda"], u, precision)
    return h + _expert_layer(m, p["moe"], _rms_norm(h, p["ffn_norm"]),
                             precision)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_all(model_items, p, x, precision):
    """One program a kind of layer (by ``p``'s own structure), not one a
    layer."""
    with jax.default_matmul_precision("highest"):
        return _layer(dict(model_items), p, x, precision)


@functools.partial(jax.jit, static_argnums=(3,))
def _top(p, x, rows, precision):
    """The final norm and the head at the rows asked for."""
    with jax.default_matmul_precision("highest"):
        return _linear(_rms_norm(x[rows], p["norm"]), p["head"], precision)


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
    for i in range(m["num_layers"]):
        x = _layer_all(items, p[f"layers_{i}"], x, precision)
    at = np.asarray(at, np.int64)
    # few widths of the head compile: powers of two from 16
    n = max(16, 1 << (len(at) - 1).bit_length())
    rows = np.zeros((n,), np.int32)
    rows[:len(at)] = at
    top = {k: p[k] for k in ("norm", "head")}
    return np.asarray(_top(top, x, jnp.asarray(rows), precision))[:len(at)]
