"""Per-op profile of the flagship TransformerLM training step (VERDICT r4
next #1: "nobody knows where the missing 0.28 goes").

Captures a ``jax.profiler`` device trace of the exact ``bench.py``
flagship window (5-step scan, donated, fused CE) on the real chip, then
converts the XPlane with ``tensorboard_plugin_profile`` into an op-level
self-time table and prints the top-N ops plus a category rollup
(matmul / attention-kernel / elementwise+fusion / optimizer / copy /
infeed ...). The rollup is the "where every point of the gap goes" table
BASELINE.md records.

Usage:  python benchmarks/flagship_profile.py [--top 25] [--unfused]
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_window(fused: bool = True, D=2048, H=8, L=8, V=8192, B=8, T=2048):
    """The bench.py flagship window, verbatim semantics."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.models import get_model
    from distkeras_tpu.ops.fused_ce import lm_head_loss

    W = 5
    model = get_model("transformer_lm", vocab_size=V, d_model=D,
                      num_heads=H, num_layers=L, max_len=T,
                      attention="standard")
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, V, size=(W, B, T)), jnp.int32
    )
    optimizer = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    feat_model = model.copy(features_only=True)

    if fused:
        def loss_fn(p, tok):
            feats = feat_model.apply(p, tok)
            targets = jnp.concatenate(
                [tok[:, 1:], jnp.zeros_like(tok[:, :1])], axis=1
            )
            mask = jnp.ones(tok.shape, jnp.float32).at[:, -1].set(0.0)
            s, n = lm_head_loss(feats, p["params"]["head"], targets, mask)
            return s / n
    else:
        def loss_fn(p, tok):
            logits = model.apply(p, tok)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tok[:, 1:]
            ).mean()

    def one(carry, tok):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p, tok)
        updates, s = optimizer.update(grads, s, p)
        return (optax.apply_updates(p, updates), s), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def window(p, s, toks):
        (p, s), losses = jax.lax.scan(one, (p, s), toks)
        return p, s, losses

    params = model.init(jax.random.PRNGKey(0), toks[0])
    opt_state = optimizer.init(params)
    return window, params, opt_state, toks


# (category, name-substring keys) — checked in order against the HLO op's
# full framework path, so module names win over generic op types
CATEGORIES = (
    ("mlp-matmul", ("mlp_up", "mlp_down")),
    ("attn-proj-matmul", ("/qkv/", "/out/")),
    ("attention-kernel", ("custom-call", "pallas", "flash")),
    ("head+loss", ("fused_linear_softmax_ce", "/head/", "logsumexp",
                   "softmax", "one_hot", "take_along")),
    ("embedding", ("/embed", "gather", "take")),
    ("layernorm", ("layernorm", "/ln", "rsqrt")),
    ("other-matmul", ("dot_general", "dot", "einsum", "convolution")),
    ("copy/layout", ("copy", "transpose-op", "bitcast", "pad", "reshape",
                     "slice", "concatenate", "dynamic-update")),
    ("elementwise/fusion", ("fusion", "add", "multiply", "subtract",
                            "convert", "select", "divide", "reduce",
                            "exp", "tanh", "maximum", "compare", "iota")),
)


def categorize(name: str, expr: str) -> str:
    base = (name + " " + expr).lower()
    for cat, keys in CATEGORIES:
        if any(k in base for k in keys):
            return cat
    return "other"


def matmul_ceiling():
    """The chip's PRACTICAL standalone bf16 matmul rate: two independent
    8192^3 products per scan iteration (ILP available; outputs feed the
    next iteration so nothing hoists or narrows). The spec-sheet
    197 TF/s is a marketing peak — this probe's asymptote was ~122 TF/s
    on an earlier machine (not measured on the current one), and it is
    the BEST of a probe family (r5 measurements): a scalar-probed matmul gets DCE'd to one column
    (reports 65), an f32-materialize+reduce goes HBM-bound (52),
    dependent chains pay a multi-ms serialization cost per step
    (2048^3: 3.6 / 4096^3: 34 / 8192^3: 108 TF/s), independent
    pairs/quads saturate at ~122. The real flagship program's matmuls
    are billed at 142-182 TF/s by the hardware profiler — ABOVE every
    standalone probe — so the step's matmul efficiency is the device's
    practical ceiling, not a scheduling loss this program could recover
    (BASELINE.md gap table)."""
    import jax
    import jax.numpy as jnp

    S = 8192
    a0 = jnp.full((S, S), 0.01, jnp.bfloat16)
    b1 = jnp.full((S, S), 0.01, jnp.bfloat16)
    b2 = jnp.full((S, S), 0.02, jnp.bfloat16)

    @jax.jit
    def run(a, b1, b2):
        def body(a, _):
            return ((a @ b1) * 0.005 + (a @ b2) * 0.005), None

        a, _ = jax.lax.scan(body, a, None, length=20)
        return jnp.sum(a.astype(jnp.float32))

    float(run(a0, b1, b2))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(run(a0, b1, b2))
        best = min(best, time.perf_counter() - t0)
    return 4.0 * S ** 3 * 20 / best


def op_table(xplane_path: str):
    """Op self-time table out of the raw XPlane. TF 2.21's pywrap plugin
    exposes ``xspace_to_tools_data`` directly (the tensorboard_plugin_
    profile wrapper around it is version-broken against this TF); the
    tool returns gviz JSON — cols + rows of per-op stats including
    self-time, model FLOP rate and bound-by classification."""
    from tensorflow.python.profiler.internal import (
        _pywrap_profiler_plugin as pp,
    )

    data, _ = pp.xspace_to_tools_data([xplane_path], "framework_op_stats")
    obj = json.loads(data.decode() if isinstance(data, bytes) else data)
    t = (obj if isinstance(obj, list) else [obj])[0]
    cols = [c["label"] for c in t["cols"]]
    return [
        dict(zip(cols, [c.get("v") for c in r["c"]])) for r in t["rows"]
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--unfused", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="emit the rollup as one JSON line too")
    args = ap.parse_args()

    import jax

    window, params, opt_state, toks = build_window(fused=not args.unfused)
    # warm up / compile
    params, opt_state, losses = window(params, opt_state, toks)
    float(np.asarray(losses)[-1])

    logdir = tempfile.mkdtemp(prefix="flagship_trace_")
    with jax.profiler.trace(logdir):
        for _ in range(2):
            params, opt_state, losses = window(params, opt_state, toks)
        float(np.asarray(losses)[-1])

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        print("no xplane captured (profiler unsupported on this backend?)")
        return 1
    rows = op_table(paths[0])

    ops = []
    for r in rows:
        if r.get("Host/device") != "Device":
            continue
        name = str(r.get("Operation Name", ""))
        typ = str(r.get("Operation Type", ""))
        self_us = float(r.get("Total self-time (us)") or 0.0)
        if not name or self_us <= 0:
            continue
        ops.append({
            "name": name, "type": typ, "self_us": self_us,
            "gflops_s": float(r.get("Model FLOP Rate (GFLOP/s)") or 0.0),
            "bound": str(r.get("Bound by", "")),
        })
    ops.sort(key=lambda o: -o["self_us"])
    total = sum(o["self_us"] for o in ops)

    print(f"# flagship per-op profile "
          f"({'unfused' if args.unfused else 'fused'} CE), "
          f"2 windows = 10 steps")
    print(f"total device self-time: {total/1e3:.2f} ms "
          f"({total/1e4:.2f} ms/step)")
    print(f"{'op (tail of path)':64s} {'type':14s} {'ms/step':>8s} "
          f"{'%':>6s} {'TFLOP/s':>8s} {'bound':>8s}")
    for o in ops[: args.top]:
        tail = o["name"].split("jvp(TransformerLM))/")[-1].split(
            "closed_call/")[-1][-64:]
        print(f"{tail:64s} {o['type'][:14]:14s} {o['self_us']/1e4:8.3f} "
              f"{100*o['self_us']/total:6.2f} {o['gflops_s']/1e3:8.1f} "
              f"{o['bound']:>8s}")

    rollup: dict = {}
    for o in ops:
        cat = categorize(o["name"], o["type"])
        rollup[cat] = rollup.get(cat, 0.0) + o["self_us"]
    print("\n# category rollup (per step)")
    for cat, us in sorted(rollup.items(), key=lambda kv: -kv[1]):
        print(f"{cat:24s} {us/1e4:9.3f} ms  {100*us/total:6.2f}%")

    ceiling = matmul_ceiling()
    print(f"\n# practical standalone-matmul ceiling (bf16 8192^3 "
          f"independent-pair scan): {ceiling/1e12:.1f} TFLOP/s "
          f"= {100*ceiling/197e12:.1f}% of the 197 TF/s spec peak "
          "(in-program matmuls profile HIGHER: 142-182 TF/s)")
    if args.json:
        print(json.dumps({
            "total_ms_per_step": round(total / 1e4, 3),
            "rollup_pct": {k: round(100 * v / total, 2)
                          for k, v in rollup.items()},
            "matmul_ceiling_tflops": round(ceiling / 1e12, 1),
        }))
    return 0


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    raise SystemExit(main())
