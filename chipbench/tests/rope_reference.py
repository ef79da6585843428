"""A reference that a configuration names (``"reference":
"chipbench.tests.rope_reference"``), for the rehearsal of the harness's
seams: GPT-2's block with rotary positions on queries and keys (rotate-half)
where the accepted reference adds a sinusoidal table, which is what
``"pos_emb": "rope"`` makes of the program's model. The rotation's base is
in no weight's shape: it is read from the configuration
(``"rope_theta"``). Serving only, float32 only, one sequence in one jitted
call; the weights have the accepted layout (rope has none of its own).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.reference import _layer_norm, make_params  # noqa: F401

PRECISIONS = ("f32",)


def _rope(x, theta):
    """``x [T, H, hd]`` rotated by its position."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(p, x, theta):
    T, d = x.shape
    att = p["CausalSelfAttention_0"]
    _, _, H, hd = att["qkv"]["kernel"].shape
    h = _layer_norm(x, p["LayerNorm_0"])
    qkv = jnp.einsum("td,dchk->tchk", h, att["qkv"]["kernel"]) + \
        att["qkv"]["bias"]
    q, k, v = jnp.moveaxis(qkv, 1, 0)
    q, k = _rope(q, theta), _rope(k, theta)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", w, v)
    x = x + jnp.einsum("qhd,hde->qe", a, att["out"]["kernel"]) + \
        att["out"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"])
    h = jax.nn.gelu(h @ p["mlp_up"]["kernel"] + p["mlp_up"]["bias"],
                    approximate=True)
    return x + h @ p["mlp_down"]["kernel"] + p["mlp_down"]["bias"]


@functools.partial(jax.jit, static_argnums=(3,))
def _forward(params, tokens, rows, theta):
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]
        for i in range(sum(k.startswith("Block_") for k in params)):
            x = _block(params[f"Block_{i}"], x, theta)
        h = _layer_norm(x[rows], params["ln_f"])
        return h @ params["head"]["kernel"] + params["head"]["bias"]


def forward_logits(config, variables, tokens, at, precision="f32",
                   pad_to=None):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not among {PRECISIONS}")
    n = len(tokens)
    padded = np.zeros((max(pad_to or n, n),), np.int32)
    padded[:n] = tokens
    rows = np.zeros((max(64, 1 << (len(at) - 1).bit_length()),), np.int32)
    rows[:len(at)] = at
    return _forward(variables["params"], padded, rows,
                    float(config["rope_theta"]))[:len(at)]
