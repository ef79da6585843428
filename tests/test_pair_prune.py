"""Causal-grid pruning in ops/pallas_pair.py: the wedge-flattened grids
(forward, dq, dkv) must match a dense reference — outputs, lse, and all
three gradients including the lse cotangent — at block counts that
exercise multi-row wedges. (Standalone from test_ring_attention so it
collects on jax builds without the top-level shard_map export.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu.ops.pallas_pair import (
    _tri_cols,
    _tri_rows,
    pallas_pair_attention,
)


def _dense(q, k, v, causal):
    C = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((C, C), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    lse = (m[..., 0] + jnp.log(p.sum(-1))).transpose(0, 2, 1)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd",
        (p / p.sum(-1, keepdims=True)).astype(q.dtype), v,
    )
    return o, lse


def test_tri_maps_enumerate_the_wedge():
    for n in (1, 2, 5):
        ii, jj = _tri_rows(n)
        assert len(ii) == n * (n + 1) // 2
        assert np.all(jj <= ii)
        # row-major: each new i starts at j == 0 (the init condition)
        starts = np.flatnonzero(jj == 0)
        assert np.array_equal(ii[starts], np.arange(n))
        ic, jc = _tri_cols(n)
        assert len(ic) == len(ii)
        assert np.all(ic >= jc)
        # column-major: each new j starts at i == j (the init condition)
        assert np.array_equal(ic[np.flatnonzero(ic == jc)], np.arange(n))


@pytest.mark.parametrize("C,block", [(64, 32), (96, 32)])
def test_pruned_causal_forward_and_grads_match_dense(C, block):
    rng = np.random.default_rng(0)
    B, H, hd = 2, 2, 128
    q = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    o, lse = pallas_pair_attention(q, k, v, True, block)
    ro, rlse = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                               rtol=1e-5, atol=1e-5)

    # grads through o AND lse (the ring feeds both into its merge)
    def loss(fn):
        def f(q, k, v):
            o, l = fn(q, k, v)
            return jnp.sum(o * 0.01) + jnp.sum(l * 0.02)
        return f

    g = jax.grad(loss(lambda q, k, v: pallas_pair_attention(
        q, k, v, True, block)), argnums=(0, 1, 2))(q, k, v)
    rg = jax.grad(loss(lambda q, k, v: _dense(q, k, v, True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, rg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_noncausal_rectangle_unchanged(
):
    """The non-causal (full-rectangle) path keeps its grid; quick parity
    guard that the kernel refactor didn't disturb it."""
    rng = np.random.default_rng(1)
    B, C, H, hd = 2, 64, 2, 128
    q = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
    o, lse = pallas_pair_attention(q, k, v, False, 32)
    ro, rlse = _dense(q, k, v, False)
    # 1e-4, the tolerance this file's other f32 comparison of
    # differently-ordered sums (the grads above) uses: the kernel adds
    # the two 32-key blocks' softmax partials in sequence, the dense
    # reference sums all 64 keys at once, and on the installed CPU
    # backend 2 of the 32,768 outputs differ by 1.4e-5
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                               rtol=1e-4, atol=1e-4)
