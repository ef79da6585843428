"""On-chip bench of the zigzag ring's per-device inner attend (VERDICT
r4 next #2: the sp path's per-device compute efficiency was never
measured on real silicon — the 2.03x zigzag win was CPU-mesh only).

Measures ``ops.ring_attention._attend`` — the blocked pure-JAX flash
that processes one unmasked chunk pair per call — at flagship sp shapes
(value+grad through the same jax.checkpoint the ring applies), and
reports effective TFLOP/s against (a) the 197 TF/s spec peak and (b)
the Pallas causal-skip kernel's measured effective rate at flagship
shapes (~131 TF/s from the r5 per-op profile), which is the candidate
replacement's known efficiency.

Usage: python benchmarks/ring_inner_bench.py [--C 512] [--B 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--C", type=int, default=512,
                    help="chunk length (T_local/2; flagship sp=8 over "
                         "T=8192 gives C=512)")
    ap.add_argument("--B", type=int, default=8)
    ap.add_argument("--H", type=int, default=8)
    ap.add_argument("--hd", type=int, default=256)
    ap.add_argument("--W", type=int, default=8, help="pairs per dispatch")
    args = ap.parse_args()
    B, C, H, hd, W = args.B, args.C, args.H, args.hd, args.W

    from distkeras_tpu.ops.ring_attention import (
        DEFAULT_KV_BLOCK,
        _attend,
    )

    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(B, C, H, hd)) * 0.1, jnp.bfloat16
    )
    q, k, v = mk(), mk(), mk()
    bk = min(DEFAULT_KV_BLOCK, C)

    def pair_loss(q, k, v):
        o0 = jnp.zeros((B, C, H, hd), jnp.float32)
        m0 = jnp.full((B, H, C), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, C), jnp.float32)
        o, m, l = _attend((o0, m0, l0), q, k, v, causal=False, bk=bk)
        denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return jnp.sum((o / denom) * 1e-3)

    ck = jax.checkpoint(pair_loss)  # as the ring applies it

    def one(carry, _):
        c, q, k, v = carry
        l, grads = jax.value_and_grad(ck, argnums=(0, 1, 2))(q, k, v)
        # feed loss AND a grad through the carry: grads left unconsumed
        # get dead-code-eliminated and the "value+grad" bench times the
        # forward only (r5 review — verified via fusion counts)
        q = q + (l * 1e-6).astype(q.dtype) + (grads[0] * 1e-6).astype(q.dtype)
        return (c + l, q, k, v), None

    @jax.jit
    def step(q, k, v):
        (c, _, _, _), _ = jax.lax.scan(
            one, (jnp.zeros((), jnp.float32), q, k, v), None, length=W
        )
        return c

    def measure(fn):
        float(np.asarray(fn(q, k, v)))  # compile + completion
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(np.asarray(fn(q, k, v)))
            best = min(best, time.perf_counter() - t0)
        return best

    best = measure(step)

    # the r5 replacement: same pair folded through the fused Pallas
    # kernel + the exact stats merge (what the zigzag ring now runs)
    from distkeras_tpu.ops.pallas_pair import (
        pair_supports,
        pallas_pair_attention,
    )
    from distkeras_tpu.ops.ring_attention import _merge_pair

    pb = pair_supports(C, C, hd, itemsize=2)

    def pair_loss_pl(q, k, v):
        o0 = jnp.zeros((B, C, H, hd), jnp.float32)
        m0 = jnp.full((B, H, C), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, C), jnp.float32)
        o_p, lse = pallas_pair_attention(q, k, v, False, pb)
        o, m, l = _merge_pair((o0, m0, l0), o_p, lse)
        denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return jnp.sum((o / denom) * 1e-3)

    ck_pl = jax.checkpoint(pair_loss_pl)

    def one_pl(carry, _):
        c, q, k, v = carry
        l, grads = jax.value_and_grad(ck_pl, argnums=(0, 1, 2))(q, k, v)
        # same grad-consumption guard as the blocked arm
        q = q + (l * 1e-6).astype(q.dtype) + (grads[0] * 1e-6).astype(q.dtype)
        return (c + l, q, k, v), None

    @jax.jit
    def step_pl(q, k, v):
        (c, _, _, _), _ = jax.lax.scan(
            one_pl, (jnp.zeros((), jnp.float32), q, k, v), None, length=W
        )
        return c

    best_pl = measure(step_pl) if pb else None

    # executed FLOPs per pair, fwd + checkpointed bwd: fwd 2 matmuls of
    # 2*B*H*C*C*hd; bwd recomputes fwd (2) then runs 4 grad matmuls -> 8
    # matmul-equivalents total
    flops = 8 * 2 * B * H * C * C * hd * W
    out = {
        "shape": f"B{B}/C{C}/H{H}/hd{hd}-bk{bk}",
        "blocked_ms_per_pair_vgrad": round(best * 1e3 / W, 3),
        "blocked_effective_tflops": round(flops / best / 1e12, 1),
        "pct_of_spec_peak": round(100 * flops / best / 197e12, 1),
    }
    if best_pl is not None:
        out.update({
            "pallas_pair_ms_per_pair_vgrad": round(best_pl * 1e3 / W, 3),
            "pallas_pair_effective_tflops": round(
                flops / best_pl / 1e12, 1),
            "speedup": round(best / best_pl, 2),
        })
    print(json.dumps(out))


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
