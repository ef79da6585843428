"""Operations and bytes of the serving path's chunked-prefill attention
calls (``ops/splash_prefill.py``), from what each mixed tick was dealt.

A call's work depends on each row's length, so the engine counts it on
the host as it plans the tick and writes the counts on the tick's
``engine.dispatch`` span: ``attended_tokens``, the (query, key) pairs
the dealt tokens require (each query attends its row's cached span and
the chunk up to itself), and ``key_positions``, the K/V positions read,
live rows only. Idle rows and a chunk's padding are computed by the
kernel and required by nobody: they are not counted, so the share
cannot pass 100 %."""

from __future__ import annotations

from chipbench.harness import span_reduce


def tick(attended: int, keys: int, queries: int, d_model: int,
         itemsize: int = 2):
    """(flops, bytes) of one layer's call in one tick: QK^T and PV, 2 x
    head size flops a pair and head each; every K and V position read
    once, the queries read and the output written."""
    flops = 2 * 2 * d_model * attended
    return flops, (2 * keys + 2 * queries) * d_model * itemsize


def least_seconds(cell: dict, run: dict, trace: dict, peaks: dict):
    """The least seconds the chip could take for the kernel's calls in
    the traced window: over the ``engine.dispatch`` spans the profile
    holds whose tick fed a chunk (a tick of plain decoding runs another
    program, without the kernel), one call a layer. ``None`` where the
    spans carry no counts (a program from before PR 25)."""
    profile = span_reduce.profile_of(run)
    dealt = [a for n, _, _, a in (profile["spans"] if profile else [])
             if n == "engine.dispatch" and "attended_tokens" in a
             and a.get("chunk", 1) > 1]
    if not dealt:
        return None
    model = cell["config_spec"]["model"]
    itemsize = {"int8": 1, "bfloat16": 2, "float32": 4}[
        cell["config_spec"]["precision"]["kv_cache"]]
    total = 0.0
    for a in dealt:
        flops, nbytes = tick(a["attended_tokens"], a["key_positions"],
                             a["n_dec"] + a["fed_tokens"],
                             model["d_model"], itemsize)
        total += max(flops / peaks["flops_bf16"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return model["num_layers"] * total
