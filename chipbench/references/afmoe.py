"""The plain reference of Trinity-Mini's language model (family
``afmoe``; ISSUE 44's layer equations) as a TRAINING job holds it, and
the seeded weights both sides are given.

Plain ``jax.numpy`` in float32 at matmul precision "highest": no kernel,
no grouped matmul, every held expert run over every token and weighted
by its gate, attention a block of queries at a time over all keys under
a mask, one row at a time through ``jax.grad`` with each layer
recomputed in the backward pass, adam and the router's bias rule by
hand. It imports nothing of the program; :func:`make_params` lays the
weights out under the names the program's model (``afmoe_lm``) reads
them by, and that layout is all the two share.

The equations (eps 1e-5, no bias in any linear layer):

- embedding times ``sqrt(d_model)``; block, float32 residual: ``x <- x +
  N2(Attn(N1(x)))``, ``x <- x + N4(FFN(N3(x)))``, four RMSNorms a layer;
  final RMSNorm; untied head.
- attention, both kinds: ``q = RMSNorm_hd(u Wq)`` a head (H), ``k =
  RMSNorm_hd(u Wk)`` a head (Hk), ``v = u Wv``, ``g = sigmoid(u Wg)``
  (H * hd); query head ``h`` reads KV head ``h // (H / Hk)``; scores
  over ``sqrt(hd)``; ``o = (softmax(...) v * g) Wo``.
  ``sliding_attention``: q, k rotated at their positions (base
  ``rope_theta``, all channels, channel ``i`` paired with ``i + hd /
  2``), position ``t`` attends ``max(0, t - window + 1) .. t``.
  ``full_attention``: no rotation, ``t`` attends ``0 .. t``.
- expert layer: ``s = sigmoid(x Wr)`` (float32); the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen; ``w =
  route_scale * s_chosen / sum(s_chosen)``; ``y = sum_e w_e SwiGLU_e(x) +
  SwiGLU_shared(x)`` **over the chosen experts this chip holds**
  (``experts_held`` from ``expert_rank * experts_held``). The first
  ``num_dense_layers`` layers are one SwiGLU ``intermediate_size`` wide.
- the bias ``b`` takes no gradient. After each optimizer step, with
  ``c_e`` the tokens of that step's batch that chose expert ``e`` (all
  experts counted): ``b_e <- b_e + load_balance_coeff * sign(mean(c) -
  c_e)``. No auxiliary loss.
- loss: mean next-token cross-entropy over the batch's ``B * (T - 1)``
  targets.

``precision="int8"`` is the control of "How correct is decided": every
linear layer's operands rounded to int8 (one scale per token and per
output channel, straight-through gradients), keys and values rounded
per token and head; the router's scores stay float32, as the
configuration states them. ``precision="no_window"`` is the second
control: float32 throughout, the window layers attending the whole
causal wedge, which only a sequence longer than the window shows. Both
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
QUERY_BLOCK = 512  # queries a step of the attention

# published values a configuration's "model" may leave out
DEFAULTS = dict(
    vocab_size=200192, d_model=2048, num_layers=32, num_dense_layers=2,
    num_heads=32, num_kv_heads=4, head_dim=128, sliding_window=2048,
    layer_types=None, intermediate_size=6144, moe_intermediate_size=1024,
    n_routed_experts=128, num_experts_per_tok=8, route_scale=2.826,
    n_shared_experts=1, experts_held=None, expert_rank=0,
    load_balance_coeff=1e-3, rope_theta=1e4)


def sizes(config: dict) -> dict:
    m = dict(DEFAULTS, **{k: v for k, v in config["model"].items()
                          if k in DEFAULTS})
    n = m["num_layers"]
    if m["experts_held"] is None:
        m["experts_held"] = m["n_routed_experts"]
    if m["layer_types"] is None:  # the published pattern
        m["layer_types"] = ["full_attention" if i % 4 == 3
                            else "sliding_attention" for i in range(n)]
    m["layer_types"] = tuple(m["layer_types"][:n])
    return m


def key_of(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# -- the weights --------------------------------------------------------------


def _shapes(m: dict) -> dict:
    d, H, Hk, hd = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                    m["head_dim"])
    F, held = m["moe_intermediate_size"], m["experts_held"]

    def swiglu(width):
        return {"w_gate": (d, width), "w_up": (d, width),
                "w_down": (width, d)}

    tree = {"embed": {"embedding": (m["vocab_size"], d)}, "norm": (d,),
            "head": {"kernel": (d, m["vocab_size"])}}
    for i in range(m["num_layers"]):
        layer = {
            "attn_norm": (d,), "attn_post_norm": (d,), "ffn_norm": (d,),
            "ffn_post_norm": (d,),
            "attn": {"wq": (d, H, hd), "wk": (d, Hk, hd), "wv": (d, Hk, hd),
                     "wg": (d, H, hd), "wo": (H, hd, d), "q_norm": (hd,),
                     "k_norm": (hd,)}}
        if i < m["num_dense_layers"]:
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
        elif leaf == "wo":
            value = noise / math.sqrt(shape[0] * shape[1])
        else:  # fan-in is the axis before the last of a matrix; an
            # expert stack has one more leading axis, wq/wk/wv/wg a
            # trailing head axis
            fan_in = shape[1] if path[-2] == "moe" and len(shape) == 3 \
                else shape[0]
            value = noise / math.sqrt(fan_in)
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[leaf] = value.astype(store)
    return {"params": out}


def make_params(config: dict, seed: int):
    """``{"params": ...}`` on the default device, in one jitted call, from
    the seed, in the dtype ``config["precision"]["parameters"]`` states
    (the router's selection bias float32)."""
    m = sizes(config)
    items = tuple(sorted(m.items()))
    return _make_params(items, config["precision"]["parameters"],
                        key_of(seed))


# -- the mathematics ----------------------------------------------------------


def _round_ste(x, axis):
    """``x`` rounded to int8 steps with one scale along ``axis``; the
    gradient passes straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, kernel, precision):
    """``x [T, in] @ kernel [in, ...]``."""
    k = kernel.reshape(kernel.shape[0], -1)
    if precision == "int8":
        x, k = _round_ste(x, -1), _round_ste(k, 0)
    return (x @ k).reshape(x.shape[:-1] + kernel.shape[1:])


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, theta: float):
    """``x [T, H, hd]`` rotated at positions ``0 .. T - 1``, channel
    ``i`` paired with ``i + hd / 2``."""
    T, _, hd = x.shape
    half = hd // 2
    inv_freq = theta ** (-2.0 * np.arange(half, dtype=np.float64) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m, window: int, p, u, precision):
    """``[T, d]``: the attention sublayer of one row."""
    T = u.shape[0]
    H, Hk, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    G = H // Hk
    q = _rms_norm(_linear(u, p["wq"], precision), p["q_norm"])
    k = _rms_norm(_linear(u, p["wk"], precision), p["k_norm"])
    v = _linear(u, p["wv"], precision)
    gate = jax.nn.sigmoid(_linear(u, p["wg"], precision))
    if window:
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    if precision == "int8":  # per token and head, as a cache would round
        k, v = _round_ste(k, -1), _round_ste(v, -1)
    if precision == "no_window":
        window = 0
    size = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def queries(args):
        qb, pb = args  # [Q, Hk, G, hd], [Q]
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(hd)
        ok = key_pos[None, :] <= pb[:, None]
        if window:
            ok &= key_pos[None, :] > pb[:, None] - window
        a = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", a, v)

    o = jax.lax.map(queries, (
        q.reshape(T // size, size, Hk, G, hd),
        key_pos.reshape(T // size, size)))
    o = o.reshape(T, H, hd) * gate
    return _linear(o.reshape(T, H * hd), p["wo"].reshape(H * hd, -1),
                   precision)


def _swiglu(p, u, precision):
    h = jax.nn.silu(_linear(u, p["w_gate"], precision)) * _linear(
        u, p["w_up"], precision)
    return _linear(h, p["w_down"], precision)


def _route(m, p, u):
    """``(gate [T, E], load [E])``: each token's gate for every expert
    (zero where not chosen), and how many tokens chose each."""
    s = jax.nn.sigmoid(u @ p["router"])  # float32 in every precision
    _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"],
                              m["num_experts_per_tok"])
    picked = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(True)
    w = jnp.where(picked, s, 0.0)
    return (m["route_scale"] * w / w.sum(-1, keepdims=True),
            picked.sum(0).astype(jnp.float32))


def _experts(m, p, u, precision):
    """``(y [T, d], load [E])``: the held experts' part, every held
    expert over every token, and the shared expert."""
    gate, load = _route(m, p, u)
    first = m["expert_rank"] * m["experts_held"]
    y = _swiglu(p["shared"], u, precision)
    for e in range(m["experts_held"]):
        one = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        y = y + gate[:, first + e, None] * _swiglu(one, u, precision)
    return y, load


def _layer(m, i: int, p, x, precision):
    window = (m["sliding_window"]
              if m["layer_types"][i] == "sliding_attention" else 0)
    x = x + _rms_norm(_attention(m, window, p["attn"], _rms_norm(
        x, p["attn_norm"]), precision), p["attn_post_norm"])
    u = _rms_norm(x, p["ffn_norm"])
    if "mlp" in p:
        y, load = _swiglu(p["mlp"], u, precision), None
    else:
        y, load = _experts(m, p["moe"], u, precision)
    return x + _rms_norm(y, p["ffn_post_norm"]), load


def _row_loss(m, params, toks, precision):
    """``(summed next-token loss of one row, loads [expert layers, E])``;
    the last position has no target."""
    x = params["embed"]["embedding"][toks] * math.sqrt(m["d_model"])
    loads = []
    for i in range(m["num_layers"]):
        x, load = jax.checkpoint(
            functools.partial(_layer, m, i, precision=precision))(
                params[f"layers_{i}"], x)
        if load is not None:
            loads.append(load)
    h = _rms_norm(x[:-1], params["norm"])
    logits = _linear(h, params["head"]["kernel"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    loss = jnp.sum(lse - jnp.take_along_axis(
        logits, toks[1:, None], axis=-1)[:, 0])
    return loss, jnp.stack(loads)


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


@functools.partial(jax.jit, static_argnums=(0, 3))
@_highest
def _row_fwd(model_items, params, toks, precision):
    return _row_loss(dict(model_items), params, toks, precision)[0]


@functools.partial(jax.jit, static_argnums=(0, 3))
@_highest
def _row_grad(model_items, params, toks, precision):
    m = dict(model_items)
    (loss, loads), grads = jax.value_and_grad(
        lambda p: _row_loss(m, p, toks, precision), has_aux=True)(params)
    return loss, loads, grads


def _f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def row_losses(config, variables, batch, precision="f32"):
    """Each row's mean next-token loss, forward only."""
    items = tuple(sorted(sizes(config).items()))
    params = _f32(variables["params"])
    return [float(_row_fwd(items, params, jnp.asarray(row), precision))
            / (len(row) - 1) for row in np.asarray(batch, np.int32)]


def linear_warmup(schedule: dict, step: int) -> float:
    """The learning rate of optimizer step ``step`` (from 0): linear from
    ``init`` to ``peak`` over ``warmup_steps``, then ``peak``."""
    frac = min(step / schedule["warmup_steps"], 1.0)
    return schedule["init"] + (schedule["peak"] - schedule["init"]) * frac


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_leaf(p, m, v, g, lr, t):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    return p - lr * m_hat / (jnp.sqrt(v_hat) + eps), m, v


def bias_rule(bias, load, coeff: float):
    """The selection bias after a step whose batch sent ``load [E]``
    tokens to each expert."""
    return bias + coeff * jnp.sign(jnp.mean(load) - load)


def train_losses(config, variables, batches, precision="f32",
                 return_params: bool = False):
    """The losses of the first ``len(batches)`` adam steps (b1 0.9, b2
    0.999, eps 1e-8, the learning rate of ``config["trainer"]``'s
    schedule; the router's bias rule after each), each taken before its
    update as a trainer reports it, and the norm of every leaf's first
    gradient. ``variables`` is consumed. ``return_params`` adds the
    parameters after the last update made (``len(batches) - 1``)."""
    m = sizes(config)
    items = tuple(sorted(m.items()))
    params = _f32(variables["params"])
    schedule = config["trainer"]["schedule"]
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    moe_layers = [f"layers_{i}" for i in range(m["num_layers"])
                  if i >= m["num_dense_layers"]]
    losses, first_grad_norms = [], None
    for step, batch in enumerate(batches):
        batch = np.asarray(batch, np.int32)
        total, loads, grads = 0.0, 0.0, None
        for row in batch:
            loss, load, g = _row_grad(items, params, jnp.asarray(row),
                                      precision)
            total += float(loss)
            loads = loads + load
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        count = batch.shape[0] * (batch.shape[1] - 1)
        grads = jax.tree.map(lambda g: g / count, grads)
        losses.append(total / count)
        if first_grad_norms is None:
            first_grad_norms = jax.tree.map(
                lambda g: float(jnp.linalg.norm(g)), grads)
        if step + 1 == len(batches):
            break
        lr = jnp.float32(linear_warmup(schedule, step))
        t = jnp.float32(step + 1)
        flat_p, tree = jax.tree.flatten(params)
        out = [_adam_leaf(p_, m_, v_, g_, lr, t) for p_, m_, v_, g_ in zip(
            flat_p, jax.tree.leaves(mom), jax.tree.leaves(var),
            jax.tree.leaves(grads))]
        params, mom, var = (jax.tree.unflatten(tree, [o[i] for o in out])
                            for i in range(3))
        for k, name in enumerate(moe_layers):
            moe = params[name]["moe"]
            moe["e_score_correction_bias"] = bias_rule(
                moe["e_score_correction_bias"], loads[k],
                m["load_balance_coeff"])
    if return_params:
        return losses, first_grad_norms, {"params": params}
    return losses, first_grad_norms
