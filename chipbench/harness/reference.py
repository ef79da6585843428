"""The plain reference of the configurations' block (GPT-2's: pre-LN,
biased MHA, biased GELU MLP of ratio 4, additive sinusoidal positions,
untied head), and the seeded weights both sides are given.

Plain ``jax.numpy`` in float32 at matmul precision "highest": no kernel,
no cache, no batching, one sequence at a time through one jitted layer
function, so that it fits beside nothing and compiles in seconds. It
imports nothing of the program; :func:`make_params` lays the weights out
under the names the program's model reads them by, and that layout is
all the two share.

``precision="int8"`` is the control of "How correct is decided": the
same mathematics with every linear layer's operands rounded to int8 (one
scale per token and per output channel, straight-through gradients) and
keys and values rounded per token and head, the step below the bfloat16
the configurations state. It has to come out as not correct.

This is the reference of every configuration that names no other
(``spec.reference``, whose docstring is the protocol): each function a
runner calls takes the configuration first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6  # flax LayerNorm's default, which the program's block uses
PRECISIONS = ("f32", "int8")


def key_of(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (the driver's exceed 2**31)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _shapes(m: dict) -> dict:
    d, H, V = m["d_model"], m["num_heads"], m["vocab_size"]
    hd, ff = d // H, 4 * d
    ln = {"scale": (d,), "bias": (d,)}
    block = {
        "LayerNorm_0": ln, "LayerNorm_1": ln,
        "CausalSelfAttention_0": {
            "qkv": {"kernel": (d, 3, H, hd), "bias": (3, H, hd)},
            "out": {"kernel": (H, hd, d), "bias": (d,)}},
        "mlp_up": {"kernel": (d, ff), "bias": (ff,)},
        "mlp_down": {"kernel": (ff, d), "bias": (d,)},
    }
    tree = {"embed": {"embedding": (V, d)}, "ln_f": ln,
            "head": {"kernel": (d, V), "bias": (V,)}}
    for i in range(m["num_layers"]):
        tree[f"Block_{i}"] = block
    return tree


_FAN_IN_AXES = {"qkv": 1, "out": 2, "mlp_up": 1, "mlp_down": 1, "head": 1}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make_params(model_items, dtype, key):
    m = dict(model_items)
    paths, shapes = [], []

    def walk(node, path):
        for name, sub in sorted(node.items()):
            if isinstance(sub, dict):
                walk(sub, path + (name,))
            else:
                paths.append(path + (name,))
                shapes.append(sub)

    walk(_shapes(m), ())
    out: dict = {}
    for i, (path, shape) in enumerate(zip(paths, shapes)):
        noise = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
        leaf, owner = path[-1], path[-2]
        if leaf == "kernel":
            fan_in = math.prod(shape[:_FAN_IN_AXES[owner]])
            value = noise / math.sqrt(fan_in)
        elif leaf == "embedding":
            value = noise / math.sqrt(shape[-1])
        elif leaf == "scale":
            value = 1.0 + 0.02 * noise
        else:  # every bias: small and not zero, so that a dropped one shows
            value = 0.02 * noise
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[leaf] = value.astype(dtype)
    return {"params": out}


def make_params(config: dict, seed: int):
    """``{"params": ...}`` on the default device, in one jitted call, from
    the seed, in the dtype ``config["precision"]["parameters"]`` states.
    Of ``config["model"]`` it reads ``vocab_size``, ``d_model``,
    ``num_heads`` and ``num_layers``."""
    items = tuple(sorted((k, config["model"][k]) for k in
                         ("vocab_size", "d_model", "num_heads", "num_layers")))
    return _make_params(items, config["precision"]["parameters"],
                        key_of(seed))


@functools.lru_cache(maxsize=8)
def _positions(length: int, dim: int):
    """The table on the device, once per shape."""
    return jnp.asarray(sinusoidal_positions(length, dim))


def sinusoidal_positions(length: int, dim: int) -> np.ndarray:
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    out = np.zeros((length, dim), np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


# -- the mathematics ----------------------------------------------------------


def _round_ste(x, axis):
    """``x`` rounded to int8 steps with one scale along ``axis``; the
    gradient passes straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, kernel, bias, precision):
    """``x [T, in] @ kernel [in, out] + bias``."""
    if precision == "int8":
        x, kernel = _round_ste(x, -1), _round_ste(kernel, 0)
    return x @ kernel + bias


def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _block(p, x, precision):
    T, d = x.shape
    att = p["CausalSelfAttention_0"]
    _, _, H, hd = att["qkv"]["kernel"].shape
    h = _layer_norm(x, p["LayerNorm_0"])
    qkv = _linear(h, att["qkv"]["kernel"].reshape(d, 3 * H * hd),
                  att["qkv"]["bias"].reshape(-1), precision)
    q, k, v = jnp.moveaxis(qkv.reshape(T, 3, H, hd), 1, 0)
    if precision == "int8":  # the cache's own rounding: per token and head
        k, v = _round_ste(k, -1), _round_ste(v, -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", w, v).reshape(T, H * hd)
    x = x + _linear(a, att["out"]["kernel"].reshape(H * hd, d),
                    att["out"]["bias"], precision)
    h = _layer_norm(x, p["LayerNorm_1"])
    h = _linear(h, p["mlp_up"]["kernel"], p["mlp_up"]["bias"], precision)
    h = jax.nn.gelu(h, approximate=True)
    return x + _linear(h, p["mlp_down"]["kernel"], p["mlp_down"]["bias"],
                       precision)


def _embed(p_embed, tokens, positions):
    return p_embed["embedding"][tokens] + positions


def _logits(p_top, x, precision):
    h = _layer_norm(x, p_top["ln_f"])
    return _linear(h, p_top["head"]["kernel"], p_top["head"]["bias"],
                   precision)


def _loss_sum(p_top, x, targets, precision):
    """Summed next-token cross-entropy of one row; its last position has
    no target and is left out."""
    logits = _logits(p_top, x[:-1], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0])


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return wrapped


block_fwd = jax.jit(_highest(_block), static_argnums=(2,))
embed_fwd = jax.jit(_embed)
take_rows = jax.jit(lambda x, rows: x[rows])
logits_fwd = jax.jit(_highest(_logits), static_argnums=(2,))
loss_fwd = jax.jit(_highest(_loss_sum), static_argnums=(3,))


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def block_vjp(p, x, g_out, precision):
    _, pull = jax.vjp(lambda p_, x_: _block(p_, x_, precision), p, x)
    return pull(g_out)  # (g_p, g_x)


@functools.partial(jax.jit, static_argnums=(3,))
@_highest
def loss_vjp(p_top, x, targets, precision):
    loss, pull = jax.vjp(
        lambda p_, x_: _loss_sum(p_, x_, targets, precision), p_top, x)
    return (loss,) + pull(jnp.ones((), jnp.float32))  # loss, g_top, g_x


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_grad(g_embedding, tokens, g_x):
    return g_embedding.at[tokens].add(g_x)


def _top(params):
    return {"ln_f": params["ln_f"], "head": params["head"]}


def _num_layers(params) -> int:
    return sum(1 for k in params if k.startswith("Block_"))


def _layer_inputs(params, toks, precision):
    """One row's input to every layer, and the last layer's output."""
    d = params["embed"]["embedding"].shape[1]
    xs = [embed_fwd(params["embed"], toks, _positions(len(toks), d))]
    for i in range(_num_layers(params)):
        xs.append(block_fwd(params[f"Block_{i}"], xs[-1], precision))
    return xs


def forward_logits(config, variables, tokens, at, precision="f32",
                   pad_to=None):
    """Logits ``[len(at), V]`` of one sequence at the positions ``at``,
    through every layer. ``pad_to`` pads the sequence (causal: padding
    after the end changes nothing before it), and the positions are
    padded to a power of two, so that few shapes compile."""
    params = variables["params"]
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    T = max(pad_to or n, n)
    padded = np.zeros((T,), np.int32)
    padded[:n] = tokens
    x = _layer_inputs(params, jnp.asarray(padded), precision)[-1]
    at = np.asarray(at)
    rows = np.zeros((max(64, 1 << (len(at) - 1).bit_length()),), np.int32)
    rows[:len(at)] = at
    return logits_fwd(_top(params), take_rows(x, rows), precision)[:len(at)]


def row_losses(config, variables, batch, precision="f32"):
    """Each row's mean next-token loss, forward only."""
    params = variables["params"]
    out = []
    for row in np.asarray(batch, np.int32):
        toks = jnp.asarray(row)
        x = _layer_inputs(params, toks, precision)[-1]
        out.append(float(loss_fwd(_top(params), x, toks[1:], precision))
                   / (len(row) - 1))
    return out


def loss_and_grads(variables, batch, precision="f32"):
    """Mean next-token loss of ``batch [B, T]`` and its gradient, one row
    at a time, layer by layer (the backward pass recomputes each layer
    from its saved input)."""
    params = variables["params"]
    B, T = batch.shape
    L = _num_layers(params)
    grads = jax.tree.map(jnp.zeros_like, params)
    total = 0.0
    for row in np.asarray(batch, np.int32):
        toks = jnp.asarray(row)
        xs = _layer_inputs(params, toks, precision)
        loss, g_top, g_x = loss_vjp(_top(params), xs[-1], toks[1:], precision)
        total += float(loss)
        for name in ("ln_f", "head"):
            grads[name] = jax.tree.map(jnp.add, grads[name], g_top[name])
        for i in reversed(range(L)):
            g_p, g_x = block_vjp(params[f"Block_{i}"], xs[i], g_x, precision)
            grads[f"Block_{i}"] = jax.tree.map(jnp.add, grads[f"Block_{i}"],
                                               g_p)
        grads["embed"]["embedding"] = _embed_grad(
            grads["embed"]["embedding"], toks, g_x)
    count = B * (T - 1)
    grads = jax.tree.map(lambda g: g / count, grads)
    return total / count, {"params": grads}


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


def train_losses(config, variables, batches, precision="f32"):
    """The losses of the first ``len(batches)`` adam steps (b1 0.9, b2
    0.999, eps 1e-8, the learning rate of ``config["trainer"]``'s
    schedule), each taken before its update as a trainer reports it, and
    the norm of every leaf's first gradient. ``variables`` is consumed."""
    params = variables["params"]
    schedule = config["trainer"]["schedule"]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad_norms = [], None
    for step, batch in enumerate(batches):
        loss, grads = loss_and_grads({"params": params}, batch, precision)
        losses.append(loss)
        if first_grad_norms is None:
            first_grad_norms = jax.tree.map(
                lambda g: float(jnp.linalg.norm(g)), grads["params"])
        if step + 1 == len(batches):
            break
        lr = jnp.float32(linear_warmup(schedule, step))
        t = jnp.float32(step + 1)
        flat_p, tree = jax.tree.flatten(params)
        out = [_adam_leaf(p_, m_, v_, g_, lr, t) for p_, m_, v_, g_ in zip(
            flat_p, jax.tree.leaves(m), jax.tree.leaves(v),
            jax.tree.leaves(grads["params"]))]
        params, m, v = (jax.tree.unflatten(tree, [o[i] for o in out])
                        for i in range(3))
    return losses, first_grad_norms
