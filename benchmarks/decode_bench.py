"""LM decode (serving) throughput: tokens/sec of KV-cache generation on
the flagship TransformerLM — the inference-side counterpart of bench.py's
training numbers. One jitted prefill + scan decode per call; the second
call reuses the compiled closure (the _generate_fn cache), so the steady
state is what's measured.

Incremental decode at these shapes is HBM-bandwidth-bound: every new
token streams the full parameter set plus the KV cache. Grouped-query
attention (``--kv-heads``, VERDICT r4 next #5) shrinks the cache stream
by H/Hk — the lever that MOVES the roofline rather than describing it.
``--sweep`` runs the full B x kv_heads grid.

Prints one JSON line per config:
{"decode_tokens_per_sec": ..., "config": ...}.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def bench(D=2048, H=8, L=8, V=8192, B=8, prompt_len=128, new_tokens=256,
          kv_heads=None, cache_dtype="model"):
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate

    T = prompt_len + new_tokens
    model = get_model("transformer_lm", vocab_size=V, d_model=D,
                      num_heads=H, num_layers=L, max_len=T,
                      num_kv_heads=kv_heads, cache_dtype=cache_dtype)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, V, size=(B, prompt_len)),
        jnp.int32,
    )
    params = model.init(jax.random.PRNGKey(0), prompt)

    out = generate(model, params, prompt, new_tokens)  # compile
    int(np.asarray(out)[0, -1])  # force completion: fetch a value
    calls = 3
    t0 = time.perf_counter()
    for i in range(calls):
        out = generate(model, params, prompt, new_tokens, seed=i)
        last = int(np.asarray(out)[0, -1])
    dt = time.perf_counter() - t0
    assert 0 <= last < V
    result = {
        "decode_tokens_per_sec": round(calls * B * new_tokens / dt, 1),
        "config": f"d{D}/h{H}/L{L}/v{V}/b{B}-prompt{prompt_len}"
                  f"-new{new_tokens}-greedy-bf16"
                  + (f"-gqa{kv_heads}" if kv_heads else "-mha")
                  + (f"-cache:{cache_dtype}"
                     if cache_dtype != "model" else ""),
    }
    print(json.dumps(result), flush=True)
    del params, out
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=None)
    ap.add_argument("--cache-dtype", choices=["model", "int8"],
                    default="model")
    ap.add_argument("--sweep", action="store_true",
                    help="B in {8,16,32} x kv_heads in {None,2} grid")
    args = ap.parse_args()
    if args.sweep:
        for B in (8, 16, 32):
            for kv in (None, 2):
                bench(B=B, kv_heads=kv, cache_dtype=args.cache_dtype)
        return
    bench(B=args.B, kv_heads=args.kv_heads, cache_dtype=args.cache_dtype)


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
