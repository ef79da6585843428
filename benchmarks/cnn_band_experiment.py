"""Controlled experiment on the CIFAR-CNN headline band (VERDICT r4
next #3): is the run-to-run spread transport/dispatch jitter or
chip-state variance?

Design: N interleaved repetitions of the SAME 100-step workload measured
two ways — as 10 dispatches of a 10-step window (the r4 bench's
granularity) and as 1 dispatch of a 100-step window. Transport jitter is
per-dispatch, so it shrinks ~10x with the long window; chip/clock-state
variance scales with compute time and would show equally in both.
Interleaving A/B within each repetition controls for slow drift.

Prints per-rep samples/sec for both arms and a JSON summary with
mean/std/CV per arm plus the verdict the data supports.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def main(reps: int = 6, batch: int = 2048):
    import optax

    from distkeras_tpu.models import get_model
    from distkeras_tpu.utils.losses import get_loss
    from distkeras_tpu.workers import make_window_step

    rng = np.random.default_rng(0)

    def data(W):
        x = jnp.asarray(
            rng.normal(size=(W, batch, 32, 32, 3)), jnp.bfloat16
        )
        y = jnp.asarray(
            np.eye(10, dtype=np.float32)[
                rng.integers(0, 10, size=(W, batch))
            ]
        )
        return x, y

    model = get_model("cifar_cnn")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32, 32, 3), jnp.float32))
    optimizer = optax.sgd(0.05, momentum=0.9)
    opt_state = optimizer.init(params)
    step = make_window_step(
        model.apply, get_loss("categorical_crossentropy"), optimizer,
        donate=True,
    )

    x10, y10 = data(10)
    x100, y100 = data(100)

    def run(xs, ys, dispatches):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(dispatches):
            params, opt_state, ms = step(params, opt_state, xs, ys)
        final = float(np.asarray(ms["loss"])[-1])
        dt = time.perf_counter() - t0
        assert np.isfinite(final)
        return dispatches * xs.shape[0] * batch / dt

    # compile + warm both shapes
    run(x10, y10, 1)
    run(x100, y100, 1)

    short, long_ = [], []
    for r in range(reps):
        s = run(x10, y10, 10)    # 100 steps, 10 dispatches
        l = run(x100, y100, 1)   # 100 steps, 1 dispatch
        short.append(s)
        long_.append(l)
        print(f"rep {r}: 10-step windows {s:,.0f}  "
              f"100-step window {l:,.0f} samples/sec", flush=True)

    def stats(a):
        a = np.asarray(a)
        return {"mean": round(float(a.mean()), 1),
                "std": round(float(a.std()), 1),
                "cv_pct": round(100 * float(a.std() / a.mean()), 2),
                "min": round(float(a.min()), 1),
                "max": round(float(a.max()), 1)}

    s_st, l_st = stats(short), stats(long_)
    # transport jitter is per-dispatch: if it drives the band, the
    # 1-dispatch arm's CV collapses relative to the 10-dispatch arm's
    verdict = (
        "transport/dispatch jitter (long-window CV much smaller)"
        if l_st["cv_pct"] < 0.5 * s_st["cv_pct"]
        else "chip-state variance (CV survives the long window)"
        if l_st["cv_pct"] > 0.8 * s_st["cv_pct"]
        else "mixed (both contribute)"
    )
    print(json.dumps({
        "short_10step": s_st, "long_100step": l_st, "reps": reps,
        "verdict": verdict,
    }))


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
