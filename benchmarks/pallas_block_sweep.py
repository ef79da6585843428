"""Pallas causal-attention block-size sweep at flagship shapes (VERDICT
r4 next #7: DEFAULT_BLOCK=512 was never swept).

Times value+grad of the causal-skip kernel at block in {128, 256, 512,
1024} (plus the blocked pure-JAX kernel as the floor) for the flagship
attention shape, as a W-deep scan per dispatch ended by a scalar
fetch, so the clock stops after the device has finished.

Usage: python benchmarks/pallas_block_sweep.py [--T 2048] [--B 8]
Prints one line per block and a JSON summary.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def bench_fn(step, q, k, v, W=8, calls=3):
    out = step(q, k, v)
    float(np.asarray(out))  # compile + completion
    best = float("inf")
    for _ in range(calls):
        t0 = time.perf_counter()
        float(np.asarray(step(q, k, v)))
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--T", type=int, default=2048)
    ap.add_argument("--B", type=int, default=8)
    ap.add_argument("--H", type=int, default=8)
    ap.add_argument("--hd", type=int, default=256)
    ap.add_argument("--W", type=int, default=8)
    args = ap.parse_args()
    B, T, H, hd, W = args.B, args.T, args.H, args.hd, args.W

    from distkeras_tpu.ops.pallas_attention import (
        pallas_causal_attention,
        supports,
    )
    from distkeras_tpu.ops.flash_attention import blocked_causal_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, hd)) * 0.1, jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, T, H, hd)) * 0.1, jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, T, H, hd)) * 0.1, jnp.bfloat16)

    def make_step(attn):
        # the carry feeds THROUGH q each iteration (tiny data-dependent
        # perturbation), so the attention+grad can't be hoisted out of
        # the scan as loop-invariant and every iteration really runs
        # (r5 review: a closure version here had zero dependence on the
        # scan carry and measured hoisted code)
        def one(carry, _):
            c, q, k, v = carry

            def loss(q, k, v):
                return jnp.sum(attn(q, k, v).astype(jnp.float32) * 1e-3)

            l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            # feed loss AND a grad through the carry: an unconsumed (or
            # 0-multiplied) grads tree gets dead-code-eliminated and the
            # "value+grad" bench times the forward only (r5 review)
            q = (q + (l * 1e-6).astype(q.dtype)
                 + (grads[0] * 1e-6).astype(q.dtype))
            return (c + l, q, k, v), None

        @jax.jit
        def step(q, k, v):
            (c, _, _, _), _ = jax.lax.scan(
                one, (jnp.zeros((), jnp.float32), q, k, v), None, length=W
            )
            return c

        return step

    results = {}
    t_blocked = bench_fn(make_step(
        lambda q, k, v: blocked_causal_attention(q, k, v, causal=True)
    ), q, k, v, W)
    results["blocked"] = t_blocked
    print(f"blocked kernel: {t_blocked*1e3/W:.2f} ms/step")

    for block in (128, 256, 512, 1024):
        if not supports(T, hd, block, itemsize=2):
            print(f"block={block}: unsupported at T={T}")
            continue
        try:
            t = bench_fn(make_step(
                functools.partial(pallas_causal_attention, block=block)
            ), q, k, v, W)
        except Exception as e:  # VMEM overflow etc.: report, keep sweeping
            print(f"block={block}: FAILED {type(e).__name__}: "
                  f"{str(e)[:120]}")
            continue
        results[f"pallas{block}"] = t
        print(f"block={block}: {t*1e3/W:.2f} ms/step  "
              f"({t_blocked/t:.2f}x vs blocked)")

    best = min((v, k) for k, v in results.items())
    print(json.dumps({
        "shape": f"B{B}/T{T}/H{H}/hd{hd}",
        "ms_per_step": {k: round(v * 1e3 / W, 3)
                        for k, v in results.items()},
        "best": best[1],
    }))


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
