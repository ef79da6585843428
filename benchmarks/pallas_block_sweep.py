"""Pallas causal-attention block-size sweep at one attention shape.

Times value+grad of the training kernel at block in {128, 256, 512,
1024} (plus the blocked pure-JAX kernel as the floor, where the shape
has equal heads and no window) as a W-deep scan per dispatch ended by a
scalar fetch, so the clock stops after the device has finished. Beside
each block it prints the kernel's tile census
(``pallas_attention.tile_census``): the interior, edge and empty tiles
a query head in each of the three launches.

Usage: python benchmarks/pallas_block_sweep.py [--shape NAME]
           [--T 2048] [--B 8] [--H 8] [--Hk 8] [--hd 256] [--window N]
``--shape`` is one of the benchmark's training cells' attends
(``train-seq2k``; ``train-moe-seq8k-window``, ``train-moe-seq8k-full``:
head 128, 32 query heads over 4 KV heads, a window of 2048 or none) or
``flagship`` (B8/T2048/H8/hd256, the default, what ``BLOCK_CANDIDATES``
was first swept at). Prints one line per block and a JSON summary.
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


# B, T, H, Hk, hd, window
SHAPES = {
    "flagship": (8, 2048, 8, 8, 256, None),
    "train-seq2k": (4, 2048, 16, 16, 128, None),
    "train-moe-seq8k-window": (2, 8192, 32, 4, 128, 2048),
    "train-moe-seq8k-full": (2, 8192, 32, 4, 128, None),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="flagship")
    for name in ("B", "T", "H", "Hk", "hd", "window"):
        ap.add_argument(f"--{name}", type=int, default=None)
    ap.add_argument("--W", type=int, default=8)
    args = ap.parse_args()
    B, T, H, Hk, hd, window = (
        given if (given := getattr(args, name)) is not None else preset
        for name, preset in zip(("B", "T", "H", "Hk", "hd", "window"),
                                SHAPES[args.shape]))
    W = args.W

    from distkeras_tpu.ops.pallas_attention import (
        pallas_causal_attention,
        supports,
        tile_census,
    )
    from distkeras_tpu.ops.flash_attention import blocked_causal_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, h, hd)) * 0.1,
                           jnp.bfloat16) for h in (H, Hk, Hk))

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

    results, census = {}, {}
    t_blocked = None
    if H == Hk and window is None:  # the floor has neither band nor groups
        t_blocked = bench_fn(make_step(
            lambda q, k, v: blocked_causal_attention(q, k, v, causal=True)
        ), q, k, v, W)
        results["blocked"] = t_blocked
        print(f"blocked kernel: {t_blocked*1e3/W:.2f} ms/step")

    for block in (128, 256, 512, 1024):
        if not supports(T, hd, block, itemsize=2):
            print(f"block={block}: unsupported at T={T}")
            continue
        census[block] = tile_census(T, min(block, T), window, H // Hk)
        print(f"block={block}: tiles a head {census[block]}")
        try:
            t = bench_fn(make_step(
                functools.partial(pallas_causal_attention, block=block,
                                  window=window)
            ), q, k, v, W)
        except Exception as e:  # VMEM overflow etc.: report, keep sweeping
            print(f"block={block}: FAILED {type(e).__name__}: "
                  f"{str(e)[:120]}")
            continue
        results[f"pallas{block}"] = t
        print(f"block={block}: {t*1e3/W:.2f} ms/step" + (
            f"  ({t_blocked/t:.2f}x vs blocked)" if t_blocked else ""))

    best = min((v, k) for k, v in results.items())
    print(json.dumps({
        "shape": f"B{B}/T{T}/H{H}/Hk{Hk}/hd{hd}/window{window}",
        "ms_per_step": {k: round(v * 1e3 / W, 3)
                        for k, v in results.items()},
        "tile_census": census,
        "best": best[1],
    }))


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
