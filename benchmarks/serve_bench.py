"""Serving throughput: continuous batching vs back-to-back generate().

A Poisson-arrival load generator (seeded, reproducible) offers N requests
with mixed output lengths to two systems serving the same model:

- **engine** — the continuous-batching :class:`ServingEngine`: S pooled
  KV-cache slots, finished slots refilled from the queue the same tick;
- **static** — back-to-back :func:`generate` calls (B=1), the pre-serving
  baseline: each request waits for every request ahead of it to fully
  finish.

Both replay the identical arrival trace; sustained tokens/sec is total
generated tokens over the makespan (first arrival → last completion), so
queueing time counts against each system. TTFT p50/p99 come from the
engine's MetricsWriter percentiles; full TTFT and per-token latency
*distributions* (fixed-bucket histograms) come from a run-isolated
telemetry MetricRegistry and land in the emitted JSON, so the BENCH
trajectory captures tails, not just means.

Sizing note: every engine tick pays a host round trip (~1 ms on CPU)
that the static path's fully-jitted decode scan never does; the default
model is sized so one decode step is compute-dominated — the regime
continuous batching targets on real serving hardware. Shrink the model
far enough and this bench measures Python dispatch, not scheduling.

Prints one JSON line per config (same shape as decode_bench.py):
{"serve_tokens_per_sec": ..., "static_tokens_per_sec": ..., "config": ...}.

``--shared-prefix`` switches to the paged-engine prefix-caching bench:
a trace where 90% of requests open with the same system prompt, served
twice by the block-paged engine — radix prefix cache ON (shared span's
prefill skipped) vs OFF (every prompt fully prefilled) — comparing TTFT.
``--smoke`` is the tiny CI variant: few requests, asserts the prefix-hit
fraction is actually > 0 and the hit counters are visible in the
Prometheus exposition, so bench drift is caught in tier-1.

``--host-tier`` is the tiered-KV-cache bench: a round-robin
shared-prefix trace whose working set is ~3x the device pool's cache
headroom, served with the host-RAM spill tier vs device-only vs an
all-resident pool — prefix_hit_fraction (>=2x device-only asserted in
``--smoke``), bit-identical streams across all three, swap-in traffic,
and p99 ITL against the all-resident reference (restore waits hidden).

``--long-prompt-interference`` is the chunked-prefill bench (Sarathi's
headline scenario): a closed-loop population of short-prompt/long-decode
streams decodes steadily while long prompts keep arriving. Served twice
— chunked mixed ticks (prefill rides the decode tick under the token
budget) vs the legacy monolithic prefill (every long prompt is one
whole-prompt dispatch that stalls every live stream) — comparing the
short streams' p99 inter-token latency at the sustained token rate.
ITLs are exact (client-side per-token timestamps); the engines'
serving_itl_ms histograms land in the JSON for the BENCH trajectory.
The ``--smoke`` variant self-asserts stream parity with solo
``generate()`` and ``chunked p99 ITL < monolithic p99 ITL``.
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def _trace(n_requests, prompt_len, vocab, mean_interarrival_s, seed=0):
    """Poisson arrivals with mixed output lengths (the continuous-batching
    win case: a long request must not hold short ones hostage)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(
        rng.exponential(mean_interarrival_s, size=n_requests)
    )
    lengths = rng.choice([8, 16, 32, 48], size=n_requests)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    return [
        {"at": float(a), "prompt": p, "max_new_tokens": int(m)}
        for a, p, m in zip(arrivals, prompts, lengths)
    ]


def bench(V=1024, D=256, H=4, L=4, slots=8, n_requests=48, prompt_len=16,
          mean_interarrival_s=0.002, dtype="float32", metrics_path=None):
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.utils.metrics import MetricsWriter

    max_new_max = 48
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=prompt_len + max_new_max,
        dtype=jnp.dtype(dtype), attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    trace = _trace(n_requests, prompt_len, V, mean_interarrival_s)

    # -- warm both systems' compile caches (steady state is the claim) ------
    warm_prompt = jnp.asarray(trace[0]["prompt"])[None]
    for m in sorted({r["max_new_tokens"] for r in trace}):
        np.asarray(generate(model, params, warm_prompt, m))
    warm_engine = ServingEngine(model, params, slots=slots)
    warm_engine.submit(trace[0]["prompt"], max_new_tokens=4)
    warm_engine.drain()

    # -- continuous-batching engine -----------------------------------------
    metrics = MetricsWriter(metrics_path)
    # run-isolated registry: the emitted histograms cover exactly this
    # measured run (the warmup engine above used the global default)
    registry = telemetry.MetricRegistry()
    engine = ServingEngine(model, params, slots=slots, metrics=metrics,
                           registry=registry)
    # warmup is done (the throwaway engine above traced every shape this
    # run uses); from here any jit re-trace is a steady-state recompile
    engine.mark_steady()
    stop = threading.Event()
    loop = threading.Thread(target=engine.serve_forever, args=(stop,),
                            daemon=True)
    t0 = time.perf_counter()
    loop.start()
    reqs = []
    for r in trace:
        delay = t0 + r["at"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        reqs.append(
            engine.submit(r["prompt"], max_new_tokens=r["max_new_tokens"])
        )
    tokens_engine = sum(len(r.stream.tokens(timeout=120)) for r in reqs)
    dt_engine = time.perf_counter() - t0
    stop.set()
    loop.join(timeout=10)
    stats = engine.stats()

    # -- static baseline: back-to-back generate() over the same trace -------
    t0 = time.perf_counter()
    tokens_static = 0
    for r in trace:
        delay = t0 + r["at"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        out = generate(model, params, jnp.asarray(r["prompt"])[None],
                       r["max_new_tokens"])
        tokens_static += int(np.asarray(out).shape[1]) - prompt_len
    dt_static = time.perf_counter() - t0

    ttft_hist = registry.histogram("serving_ttft_ms").value
    token_hist = registry.histogram("serving_token_ms").value
    result = {
        "serve_tokens_per_sec": round(tokens_engine / dt_engine, 1),
        "static_tokens_per_sec": round(tokens_static / dt_static, 1),
        "speedup": round(dt_static / dt_engine, 2),
        "ttft_ms": stats["ttft_ms"],
        "ttft_hist": ttft_hist,
        "token_ms_hist": token_hist,
        "mean_occupancy": stats["mean_occupancy"],
        # runtime introspection (PR 5): flight-recorder cost as a
        # fraction of tick wall time, jit re-traces after warmup
        # (nonempty = steady-state recompile bug), memory watermarks
        "flight_overhead_frac": stats["flight"]["overhead_frac"],
        "steady_recompiles": stats["recompiles_since_mark"],
        "memory": stats["memory"],
        "config": f"d{D}/h{H}/L{L}/v{V}-slots{slots}-req{n_requests}"
                  f"-prompt{prompt_len}-poisson{mean_interarrival_s}"
                  f"-mixed8to48-{dtype}",
    }
    print(json.dumps(result), flush=True)
    return result


def _prefix_trace(n_requests, prefix_len, tail_len, vocab,
                  shared_frac=0.9, seed=0):
    """The prefix-caching win case: ``shared_frac`` of requests open
    with one fixed system prompt and differ only in a short tail."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, size=prefix_len).astype(np.int32)
    out = []
    for i in range(n_requests):
        tail = rng.integers(0, vocab, size=tail_len).astype(np.int32)
        if rng.random() < shared_frac or i == 0:
            prompt = np.concatenate([system, tail])
        else:  # cold request: fresh pseudo-prefix, no reuse
            prompt = np.concatenate([
                rng.integers(0, vocab, size=prefix_len).astype(np.int32),
                tail,
            ])
        out.append(prompt)
    return out


def bench_shared_prefix(V=1024, D=256, H=4, L=4, slots=8, n_requests=16,
                        prefix_len=256, tail_len=8, max_new=8,
                        block_size=16, dtype="float32", smoke=False):
    """TTFT with 90% shared system prompts: paged engine with the radix
    prefix cache vs the same paged engine with the cache disabled (full
    prefill per request). Requests run one at a time on an idle engine,
    so TTFT is a clean prefill measurement — the radix hit turns a
    ``prefix+tail``-token prefill into a tail-only one; queueing and
    decode interleaving effects are the original Poisson bench's job."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import ServingEngine
    from distkeras_tpu.telemetry.exposition import render_prometheus

    if smoke:
        V, D, H, L, slots = 64, 32, 2, 2, 2
        n_requests, prefix_len, tail_len, max_new = 8, 32, 4, 4
        block_size = 8
    max_len = prefix_len + tail_len + max_new
    max_len += (-max_len) % block_size  # paged mode: whole blocks
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    trace = _prefix_trace(n_requests, prefix_len, tail_len, V)

    def run(prefix_cache):
        # warm engine: compile full prefill, the suffix-only prefill the
        # hit path uses (two same-prefix requests back to back), and the
        # tick at both occupancies. jit caches are keyed by module
        # config, so the measured engine reuses every trace.
        rng = np.random.default_rng(99)
        sys_prompt = trace[0][:prefix_len]
        warm_eng = ServingEngine(
            model, params, slots=slots, paged=True,
            block_size=block_size, prefix_cache=prefix_cache,
            registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(),
        )
        for _ in range(2):
            tail = rng.integers(0, V, size=tail_len).astype(np.int32)
            warm_eng.submit(np.concatenate([sys_prompt, tail]),
                            max_new_tokens=max_new)
            warm_eng.drain()

        registry = telemetry.MetricRegistry()
        engine = ServingEngine(
            model, params, slots=slots, paged=True,
            block_size=block_size, prefix_cache=prefix_cache,
            registry=registry, tracer=telemetry.Tracer(),
        )
        engine.mark_steady()  # warm_eng traced every shape this run uses
        t0 = time.perf_counter()
        tokens = 0
        for p in trace:
            req = engine.submit(p, max_new_tokens=max_new)
            engine.drain()
            tokens += len(req.stream.tokens(timeout=60))
        dt = time.perf_counter() - t0
        return engine, registry, tokens, dt

    eng_hit, reg_hit, tokens_hit, dt_hit = run(prefix_cache=True)
    eng_cold, _, tokens_cold, dt_cold = run(prefix_cache=False)
    s_hit, s_cold = eng_hit.stats(), eng_cold.stats()
    exposition = render_prometheus(reg_hit)
    result = {
        "prefix_ttft_ms_p50": s_hit["ttft_ms"]["p50"],
        "full_ttft_ms_p50": s_cold["ttft_ms"]["p50"],
        "ttft_speedup": (
            round(s_cold["ttft_ms"]["p50"] / s_hit["ttft_ms"]["p50"], 2)
            if s_hit["ttft_ms"]["p50"] else None
        ),
        "prefix_hit_fraction": s_hit["prefix_hit_fraction"],
        "prefix_hit_tokens": s_hit["prefix_hit_tokens"],
        "block_evictions": reg_hit.counter(
            "serving_block_evictions_total").value,
        "tokens_per_sec": round(tokens_hit / dt_hit, 1),
        "tokens_per_sec_no_cache": round(tokens_cold / dt_cold, 1),
        "flight_overhead_frac": s_hit["flight"]["overhead_frac"],
        "steady_recompiles": s_hit["recompiles_since_mark"],
        "memory": s_hit["memory"],
        "config": f"d{D}/h{H}/L{L}/v{V}-slots{slots}-req{n_requests}"
                  f"-prefix{prefix_len}+{tail_len}-new{max_new}"
                  f"-bs{block_size}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    if smoke:
        # CI drift guards: sharing must actually happen, the hit
        # counters must be scrapeable, and both runs must finish
        assert result["prefix_hit_fraction"] > 0, result
        assert "serving_prefix_hit_tokens_total" in exposition, (
            "prefix-hit counter missing from /metrics exposition"
        )
        assert "serving_blocks_in_use" in exposition
        assert tokens_hit == tokens_cold == n_requests * max_new
        # runtime-introspection guards: warmup traced every shape, so a
        # steady-state jit re-trace is a latency bug; the flight
        # recorder must cost <5% of tick wall time
        assert result["steady_recompiles"] == {}, result
        assert result["flight_overhead_frac"] < 0.05, result
    print(json.dumps(result), flush=True)
    return result


def _tier_trace(n_groups, reps, prefix_len, tail_len, vocab, seed=0):
    """The tiered-cache win case: ``n_groups`` distinct shared system
    prompts visited round-robin, so by the time a prefix is revisited
    the LRU has evicted it from a device pool sized for a fraction of
    the working set — device-only recomputes it, the host tier swaps
    it back in."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, size=prefix_len).astype(np.int32)
                for _ in range(n_groups)]
    out = []
    for _ in range(reps):
        for p in prefixes:
            tail = rng.integers(0, vocab, size=tail_len).astype(np.int32)
            out.append(np.concatenate([p, tail]))
    return out


def bench_host_tier(V=1024, D=256, H=4, L=4, slots=4, n_groups=9,
                    reps=4, prefix_len=256, tail_len=8, max_new=16,
                    block_size=16, restore_budget=4, dtype="float32",
                    smoke=False, checks=True):
    """Tiered KV cache: a shared-prefix working set sized to ~3x the
    device pool's cache headroom, served three ways —

    - **tier**: device pool holding ~1/3 of the prefixes plus a host
      tier holding all of them (eviction demotes, revisits restore);
    - **device**: the same starved device pool, no tier (a revisited
      prefix is simply recomputed — today's behavior);
    - **resident**: a device pool large enough for everything (the
      all-resident latency reference the tier tries to match).

    Identical trace and seeds across all three, so token streams must
    be bit-identical (non-speculative engines) — asserted. Headline:
    prefix_hit_fraction with the tier >= 2x device-only, zero
    steady-state recompiles, and p99 ITL within ~10% of the resident
    run (restore waits hide behind in-flight ticks; a small absolute
    floor absorbs CPU-timer jitter at sub-ms ticks). Swap-in traffic
    (bytes, effective MB/s over the drain) lands in the JSON."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import FIFOScheduler, ServingEngine

    if smoke:
        V, D, H, L, slots = 64, 32, 2, 2, 2
        n_groups, reps, prefix_len, tail_len, max_new = 6, 3, 32, 4, 16
        block_size = 8
    pb = prefix_len // block_size  # blocks per shared prefix
    worst = -(-(prefix_len + tail_len + max_new) // block_size)
    # device cache headroom = 1/3 of the prefix working set; the pool
    # additionally covers every live slot's worst case so admission
    # never deadlocks on its own residents
    cache_blocks = max((n_groups * pb) // 3, pb)
    num_blocks = 1 + slots * worst + cache_blocks
    host_blocks = n_groups * pb + pb
    max_len = prefix_len + tail_len + max_new
    max_len += (-max_len) % block_size
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    trace = _tier_trace(n_groups, reps, prefix_len, tail_len, V)
    warm_n = n_groups  # first round-robin pass = warmup

    def run(tier, pool_blocks):
        registry = telemetry.MetricRegistry()
        engine = ServingEngine(
            model, params, slots=slots, paged=True,
            block_size=block_size, num_blocks=pool_blocks,
            host_blocks=host_blocks if tier else None,
            scheduler=FIFOScheduler(max_queue_depth=len(trace) + 1,
                                    restore_budget=restore_budget),
            registry=registry, tracer=telemetry.Tracer(),
        )
        # warmup: the first pass over every prefix, submitted
        # CONCURRENTLY so the mixed tick traces at the same per-slot
        # sampling configs and occupancies the measured phase runs.
        # Greedy sampling throughout: an idle slot's cfg equals a busy
        # one's, so occupancy permutations can't mint new tick builder
        # keys mid-measurement (sampled-stream tier parity is
        # tests/test_tiered.py's job)
        # (both widths, the decode-only shape, and — on the tier leg —
        # demotion under pressure plus a revisit's restore), all
        # before the steady mark
        warm = [engine.submit(p, max_new_tokens=max_new)
                for p in trace[:warm_n]]
        engine.drain(timeout=600)
        for r in warm:
            r.stream.tokens(timeout=60)
        engine.submit(trace[0], max_new_tokens=max_new)
        engine.drain(timeout=600)
        engine.mark_steady()
        reqs = [engine.submit(p, max_new_tokens=max_new)
                for p in trace[warm_n:]]
        t0 = time.perf_counter()
        engine.drain(timeout=600)
        dt = time.perf_counter() - t0
        streams = [r.stream.tokens(timeout=60) for r in reqs]
        # snapshot stats NOW: recompile accounting is process-global,
        # and the next leg's differently-sized pool compiles fresh
        # modules that must not be charged to this run's steady window
        return engine, engine.stats(), streams, dt

    eng_t, s_t, streams_t, dt_t = run(tier=True, pool_blocks=num_blocks)
    _, s_d, streams_d, dt_d = run(tier=False, pool_blocks=num_blocks)
    resident_blocks = 1 + slots * worst + n_groups * pb + cache_blocks
    _, s_r, streams_r, dt_r = run(tier=False,
                                  pool_blocks=resident_blocks)
    parity = streams_t == streams_d == streams_r
    swap_bytes = eng_t.host.bytes_restored_total
    tokens = sum(len(s) for s in streams_t)
    result = {
        "tier_hit_fraction": s_t["prefix_hit_fraction"],
        "device_hit_fraction": s_d["prefix_hit_fraction"],
        "resident_hit_fraction": s_r["prefix_hit_fraction"],
        "hit_gain": (
            round(s_t["prefix_hit_fraction"]
                  / s_d["prefix_hit_fraction"], 2)
            if s_d["prefix_hit_fraction"] else None
        ),
        "tier_itl_ms_p99": s_t["itl_ms"]["p99"],
        "resident_itl_ms_p99": s_r["itl_ms"]["p99"],
        "device_itl_ms_p99": s_d["itl_ms"]["p99"],
        "tier_tokens_per_sec": round(tokens / dt_t, 1),
        "device_tokens_per_sec": round(tokens / dt_d, 1),
        "resident_tokens_per_sec": round(tokens / dt_r, 1),
        "demotions": s_t["block_demotions"],
        "restores": s_t["block_restores"],
        "restore_wait_ms": s_t["restore_wait_ms"],
        "swap_in_bytes": swap_bytes,
        "swap_out_bytes": eng_t.host.bytes_demoted_total,
        # effective swap-in traffic over the measured drain — a demand
        # rate, not a link-bandwidth probe
        "swap_in_mb_s": round(swap_bytes / dt_t / 1e6, 2),
        "host_blocks_cached": s_t["host_blocks_cached"],
        "host_bytes": s_t["host_bytes"],
        "parity": parity,
        "flight_overhead_frac": s_t["flight"]["overhead_frac"],
        "steady_recompiles": s_t["recompiles_since_mark"],
        "memory": s_t["memory"],
        "config": f"d{D}/h{H}/L{L}/v{V}-slots{slots}"
                  f"-groups{n_groups}x{reps}-prefix{prefix_len}"
                  f"+{tail_len}-new{max_new}-bs{block_size}"
                  f"-dev{num_blocks}-host{host_blocks}"
                  f"-rb{restore_budget}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # the tier's contract, self-asserted for CI: identical streams
        # with the tier on/off/irrelevant, a real >=2x hit-fraction
        # win on the 3x-capacity trace, actual swap traffic, no
        # steady-state re-traces, and restore waits hidden well enough
        # that tail ITL tracks the all-resident run (10% + a 2 ms
        # floor for CPU-timer jitter at sub-ms ticks)
        assert parity, "token streams diverged across tier settings"
        # >=2x device-only, with an absolute floor so a device run
        # that collapsed to ~zero hits can't make the bound vacuous
        assert result["tier_hit_fraction"] >= max(
            2 * result["device_hit_fraction"], 0.5), result
        assert result["demotions"] > 0 and result["restores"] > 0, result
        assert result["swap_in_bytes"] > 0, result
        assert result["steady_recompiles"] == {}, result
        assert result["flight_overhead_frac"] < 0.05, result
        if result["tier_itl_ms_p99"] and result["resident_itl_ms_p99"]:
            assert (result["tier_itl_ms_p99"]
                    <= 1.1 * result["resident_itl_ms_p99"] + 2.5), result
    print(json.dumps(result), flush=True)
    return result


def bench_long_prompt_interference(
        V=1024, D=256, H=4, L=4, slots=4,
        n_short=24, short_prompt=16, short_new=32,
        n_long=6, long_prompt=1024, long_new=4, long_every=4,
        prefill_chunk=64, tick_token_budget=None, think_time=0.0,
        dtype="float32", smoke=False, checks=True):
    """p99 inter-token latency of live decode streams while long prompts
    keep arriving: chunked mixed-tick prefill vs monolithic prefill.

    Load shape: a closed-loop population of ``slots - 1`` short
    requests decodes continuously (each completion immediately submits
    the next, so decode pressure is constant); after every
    ``long_every`` short completions one ``long_prompt``-token request
    is submitted into the remaining slot. Monolithic mode runs each
    long prompt as ONE whole-prompt dispatch between ticks — every
    short stream's next token waits it out (the ITL spike). Chunked
    mode streams it ``prefill_chunk`` tokens per tick under
    ``tick_token_budget``, decodes riding the same dispatch.

    ITL is measured exactly, client-side: a consumer thread per short
    request timestamps each token; gaps after the first token are the
    samples. Throughput is all generated tokens (short + long) over the
    makespan. ``think_time`` inserts a per-completion pause before the
    next closed-loop short is submitted: at 0 the system is saturated
    (every CPU cycle of chunk padding shows up as lost throughput —
    the worst case for chunking); > 0 models paced traffic with idle
    headroom, where both modes serve the same offered load and the ITL
    tail is the discriminator. ``checks=False`` disables the smoke
    self-asserts (for embedding in the flagship bench.py run, where a
    different accelerator's timing profile must not fail the whole
    BENCH line)."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate
    from distkeras_tpu.serving import FIFOScheduler, ServingEngine

    if smoke:
        # sized so the monolithic long-prompt prefill COMPUTE dominates
        # per-dispatch host overhead — measured on a 1-core CPU worker:
        # prefill[1,1024] ≈ 260 ms (attention-quadratic) vs mixed
        # tick[3,32] ≈ 15 ms, an order of magnitude between the stall
        # and its chunked replacement, so the p99 comparison is
        # physics, not jitter. Any smaller a model/prompt and the bench
        # measures Python dispatch, not the stall it guards against.
        # slots=3 keeps TWO shorts decoding in closed loop, so a long
        # fired at one short's completion always has another short
        # mid-stream to feel (or not feel) the stall.
        V, D, H, L, slots = 64, 256, 4, 2, 3
        n_short, short_prompt, short_new = 8, 8, 8
        n_long, long_prompt, long_new, long_every = 3, 1024, 2, 2
        prefill_chunk = 32
    if tick_token_budget is None:
        # one full chunk of prefill alongside every decode, per tick
        tick_token_budget = slots + prefill_chunk
    max_len = long_prompt + max(long_new, short_new)
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(0)
    shorts = [rng.integers(0, V, size=short_prompt).astype(np.int32)
              for _ in range(n_short)]
    # staggered output lengths: equal lengths would let the closed-loop
    # population complete in lockstep, so every long prompt would land
    # BETWEEN streams (TTFT, not ITL) and the stall would be invisible
    # to the metric this bench exists to measure
    short_lens = rng.integers(max(2, short_new // 2), short_new + 1,
                              size=n_short)
    longs = [rng.integers(0, V, size=long_prompt).astype(np.int32)
             for _ in range(n_long)]

    def run(chunked):
        # warm a THROWAWAY engine through every shape the measured run
        # uses (jit caches key on module config, so the measured engine
        # reuses the compiled tick/prefill programs)
        warm = ServingEngine(
            model, params, slots=slots,
            registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
            prefill_chunk=prefill_chunk if chunked else None,
            scheduler=FIFOScheduler(tick_token_budget=tick_token_budget,
                                    registry=telemetry.MetricRegistry(),
                                    tracer=telemetry.Tracer()),
        )
        warm.submit(shorts[0], max_new_tokens=2)
        warm.submit(longs[0], max_new_tokens=2)
        warm.drain()

        registry = telemetry.MetricRegistry()
        engine = ServingEngine(
            model, params, slots=slots, registry=registry,
            tracer=telemetry.Tracer(),
            prefill_chunk=prefill_chunk if chunked else None,
            scheduler=FIFOScheduler(tick_token_budget=tick_token_budget,
                                    registry=telemetry.MetricRegistry(),
                                    tracer=telemetry.Tracer()),
        )
        engine.mark_steady()  # warm engine traced every shape used here
        stop = threading.Event()
        loop = threading.Thread(target=engine.serve_forever, args=(stop,),
                                daemon=True)
        lock = threading.Lock()
        itls, streams = [], {}  # streams: short idx -> emitted tokens
        tokens = [0]
        short_left = list(enumerate(shorts))
        long_left = list(longs)
        short_done, long_done, long_fired = [0], [0], [0]
        threads = []

        def consume_long(req):
            n = len(req.stream.tokens(timeout=120))
            with lock:
                tokens[0] += n
                long_done[0] += 1

        def consume(idx, req):
            stamps, toks = [], []
            for tok in req.stream:
                stamps.append(time.perf_counter())
                toks.append(tok)
            with lock:
                tokens[0] += len(toks)
                streams[idx] = toks
                itls.extend(
                    (b - a) * 1e3 for a, b in zip(stamps, stamps[1:])
                )
                short_done[0] += 1
                # closed loop: a finished short immediately feeds the
                # next one in; every long_every-th completion also
                # launches a long prompt into the spare slot
                nxt = short_left.pop(0) if short_left else None
                fire_long = (long_left
                             and short_done[0] % long_every == 0)
                lng = long_left.pop(0) if fire_long else None
                if lng is not None:
                    long_fired[0] += 1
            if lng is not None:
                rl = engine.submit(lng, max_new_tokens=long_new)
                tl = threading.Thread(target=consume_long, args=(rl,),
                                      daemon=True)
                tl.start()
                with lock:
                    threads.append(tl)
            if nxt is not None:
                if think_time > 0:
                    time.sleep(think_time)
                i, p = nxt
                r = engine.submit(p, max_new_tokens=int(short_lens[i]))
                t = threading.Thread(target=consume, args=(i, r),
                                     daemon=True)
                t.start()
                with lock:
                    threads.append(t)

        t0 = time.perf_counter()
        loop.start()
        with lock:
            seeds = [short_left.pop(0)
                     for _ in range(min(max(slots - 1, 1),
                                        len(short_left)))]
        for i, p in seeds:
            r = engine.submit(p, max_new_tokens=int(short_lens[i]))
            t = threading.Thread(target=consume, args=(i, r), daemon=True)
            t.start()
            with lock:
                threads.append(t)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            with lock:
                if (short_done[0] >= n_short
                        and long_done[0] >= long_fired[0]):
                    break
            time.sleep(0.005)
        dt = time.perf_counter() - t0
        stop.set()
        loop.join(timeout=10)
        while True:
            with lock:
                pend = [t for t in threads if t.is_alive()]
            if not pend:
                break
            pend[0].join(timeout=10)
        with lock:
            vals = sorted(itls)
            total = tokens[0]
        p50 = vals[int(0.50 * (len(vals) - 1))] if vals else None
        p99 = vals[int(0.99 * (len(vals) - 1))] if vals else None
        est = engine.stats()
        return {
            "itl_ms_p50": p50, "itl_ms_p99": p99,
            "itl_ms_max": vals[-1] if vals else None,
            "itl_samples": len(vals),
            "tokens_per_sec": round(total / dt, 1),
            "itl_hist": registry.histogram("serving_itl_ms").value,
            "decode_stalls": registry.counter(
                "serving_decode_stalls_total").value,
            "steady_recompiles": est["recompiles_since_mark"],
            "flight_overhead_frac": est["flight"]["overhead_frac"],
            "memory": est["memory"],
            "streams": streams,
        }

    chunked = run(chunked=True)
    mono = run(chunked=False)
    if smoke and checks:
        # parity guard: every short stream, in BOTH modes, must be
        # token-identical to a solo generate() of the same prompt
        for mode in (chunked, mono):
            assert len(mode["streams"]) == n_short
            for i, toks in mode["streams"].items():
                want = np.asarray(generate(
                    model, params, jnp.asarray(shorts[i])[None],
                    int(short_lens[i])
                ))[0, short_prompt:].tolist()
                assert toks == want, (i, toks, want)
    result = {
        "chunked_itl_ms_p99": chunked["itl_ms_p99"],
        "monolithic_itl_ms_p99": mono["itl_ms_p99"],
        "itl_p99_reduction": (
            round(mono["itl_ms_p99"] / chunked["itl_ms_p99"], 2)
            if chunked["itl_ms_p99"] else None
        ),
        "chunked_itl_ms_p50": chunked["itl_ms_p50"],
        "monolithic_itl_ms_p50": mono["itl_ms_p50"],
        "chunked_itl_ms_max": chunked["itl_ms_max"],
        "monolithic_itl_ms_max": mono["itl_ms_max"],
        "chunked_tokens_per_sec": chunked["tokens_per_sec"],
        "monolithic_tokens_per_sec": mono["tokens_per_sec"],
        "monolithic_decode_stalls": mono["decode_stalls"],
        "chunked_decode_stalls": chunked["decode_stalls"],
        "chunked_steady_recompiles": chunked["steady_recompiles"],
        "monolithic_steady_recompiles": mono["steady_recompiles"],
        "chunked_flight_overhead_frac": chunked["flight_overhead_frac"],
        "monolithic_flight_overhead_frac": mono["flight_overhead_frac"],
        "memory": chunked["memory"],
        "chunked_itl_samples": chunked["itl_samples"],
        "monolithic_itl_samples": mono["itl_samples"],
        "chunked_itl_hist": chunked["itl_hist"],
        "monolithic_itl_hist": mono["itl_hist"],
        "config": f"d{D}/h{H}/L{L}/v{V}-slots{slots}"
                  f"-short{short_prompt}+{short_new}x{n_short}"
                  f"-long{long_prompt}+{long_new}x{n_long}"
                  f"-chunk{prefill_chunk}-budget{tick_token_budget}"
                  + (f"-think{think_time}" if think_time else "")
                  + f"-{dtype}" + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # CI drift guards: the chunked engine must actually remove the
        # monolithic prefill stall from the decode streams, and the
        # monolithic engine must have seen stalls at all (otherwise the
        # scenario stopped exercising interference)
        assert mono["decode_stalls"] > 0, result
        assert chunked["decode_stalls"] == 0, result
        assert chunked["itl_ms_p99"] < mono["itl_ms_p99"], result
        # runtime-introspection guards (PR 5): a steady-state jit
        # re-trace after warmup is a latency bug in either mode, and
        # the always-on flight recorder must stay under 5% of tick time
        assert chunked["steady_recompiles"] == {}, result
        assert mono["steady_recompiles"] == {}, result
        assert chunked["flight_overhead_frac"] < 0.05, result
        assert mono["flight_overhead_frac"] < 0.05, result
    print(json.dumps(result), flush=True)
    return result


def _overfit_cycle(model, params, corpus, train_steps, T=32, B=8,
                   lr=1e-3, seed=0):
    """Overfit ``model`` on a periodic token stream (a few seconds of
    jitted Adam on CPU). This manufactures the speculative bench's
    HIGH-ACCEPTANCE regime honestly: a model that has learned strong
    local structure emits the same repetitive continuations a real LM
    emits on repetitive text (code, templated prose) — exactly the
    workload where a drafter's proposals survive verification. Random
    untrained weights can't exhibit that (greedy streams wander, the
    n-gram drafter's acceptance sits near 0.3), so without this step
    the bench could only measure the LOW-acceptance regime."""
    import optax

    opt = optax.adam(lr)
    ostate = opt.init(params)

    @jax.jit
    def step(params, ostate, xy):
        def loss(p):
            logits = model.apply(p, xy[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, xy[:, 1:]).mean()

        l, g = jax.value_and_grad(loss)(params)
        up, ostate = opt.update(g, ostate)
        return optax.apply_updates(params, up), ostate, l

    key = jax.random.PRNGKey(seed)
    for _ in range(train_steps):
        key, sub = jax.random.split(key)
        starts = np.asarray(
            jax.random.randint(sub, (B,), 0, len(corpus) - T - 1))
        xy = jnp.stack([jnp.asarray(corpus[s:s + T + 1]) for s in starts])
        params, ostate, l = step(params, ostate, xy)
    return params, float(l)


def bench_speculative(V=64, D=512, H=8, L=4, slots=4, n_requests=12,
                      max_new=48, spec_k=4, prefill_chunk=32,
                      tick_token_budget=None, train_steps=150, period=8,
                      draft="ngram", dtype="float32", smoke=False,
                      checks=True):
    """Speculative decoding vs the plain mixed tick at high acceptance:
    decode tokens/sec and client-side ITL p50/p99 on a staggered-length
    trace, same engine config with and without a drafter.

    The flagship is first overfit on a ``period``-token cycle
    (:func:`_overfit_cycle`) so its greedy streams carry the strong
    local structure speculation feeds on; prompts are rotations of the
    cycle, output lengths staggered so completions never line up. Each
    request's tokens are timestamped by its own consumer thread — ITL
    gaps are exact and client-visible (a verify tick releases an
    accepted prefix as a burst: intra-burst gaps collapse toward zero,
    which is the speculation win as a CLIENT sees it). ``draft`` picks
    the drafter: ``"ngram"`` (self-speculative suffix lookup, no second
    model) or ``"model"`` (a ~100x-smaller TransformerLM overfit on the
    same corpus — the classic two-model setup). ``--smoke`` self-asserts
    greedy bit-parity spec-vs-baseline, p50 ITL <= baseline, >= 1.5x
    decode tok/s, populated acceptance telemetry, and zero steady-state
    recompiles."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import FIFOScheduler, ServingEngine
    from distkeras_tpu.telemetry.exposition import render_prometheus

    if smoke:
        V, D, H, L, slots = 64, 256, 4, 2, 3
        n_requests, max_new, train_steps = 6, 32, 80
    if tick_token_budget is None:
        tick_token_budget = slots * (spec_k + 1) + prefill_chunk
    rng = np.random.default_rng(7)
    cycle = rng.integers(0, V, size=period).astype(np.int32)
    corpus = np.tile(cycle, 64)
    max_len = 2 * period + max_new + spec_k + 1
    max_len += (-max_len) % 16
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    t0 = time.perf_counter()
    params, loss = _overfit_cycle(model, params, corpus, train_steps)
    train_s = time.perf_counter() - t0
    draft_kw = {"draft": "ngram"}
    if draft == "model":
        dmodel = get_model(
            "transformer_lm", vocab_size=V, d_model=32, num_heads=2,
            num_layers=1, max_len=max_len, dtype=jnp.dtype(dtype),
            attention="dense",
        )
        dparams = dmodel.init(jax.random.PRNGKey(1),
                              jnp.zeros((1, 4), jnp.int32))
        dparams, _ = _overfit_cycle(dmodel, dparams, corpus,
                                    train_steps, seed=1)
        draft_kw = {"draft": dmodel, "draft_params": dparams}
    lens = rng.integers(max(4, max_new // 2), max_new + 1,
                        size=n_requests)
    prompts = [np.concatenate([cycle, cycle[:int(o)]]).astype(np.int32)
               for o in rng.integers(1, period, size=n_requests)]

    def run(spec):
        def make_engine():
            return ServingEngine(
                model, params, slots=slots,
                registry=telemetry.MetricRegistry(),
                tracer=telemetry.Tracer(), prefill_chunk=prefill_chunk,
                scheduler=FIFOScheduler(
                    tick_token_budget=tick_token_budget,
                    registry=telemetry.MetricRegistry(),
                    tracer=telemetry.Tracer()),
                **({**draft_kw, "spec_k": spec_k} if spec else {}),
            )

        # warm a throwaway engine through every shape (jit caches key
        # on module config, so the measured engine reuses the traces)
        warm = make_engine()
        for p, m in zip(prompts, lens):
            warm.submit(p, max_new_tokens=int(m))
        warm.drain()

        engine = make_engine()
        registry = engine.registry
        engine.mark_steady()

        # pass 1 — throughput: submit everything, drain, read streams
        # afterwards. No consumer threads contend for the GIL, so the
        # number is the engine's sustained decode rate. Best of 3
        # replays: the window is short, and on a shared CPU runner a
        # scheduler hiccup inside it swamps the effect being measured.
        best = 0.0
        for _ in range(3):
            reqs = [engine.submit(p, max_new_tokens=int(m))
                    for p, m in zip(prompts, lens)]
            t0 = time.perf_counter()
            engine.drain()
            dt = time.perf_counter() - t0
            streams = [r.stream.tokens(timeout=300) for r in reqs]
            total = sum(map(len, streams))
            best = max(best, total / dt)

        # pass 2 — client-side ITL: one consumer thread per request
        # timestamps every token as it crosses the stream boundary (a
        # verify tick releases its accepted prefix as a burst — the
        # intra-burst gaps collapsing toward zero IS the speculation
        # win as a client sees it).
        stop = threading.Event()
        loop = threading.Thread(target=engine.serve_forever,
                                args=(stop,), daemon=True)
        lock = threading.Lock()
        itls = []

        def consume(req):
            stamps = [time.perf_counter() for _ in req.stream]
            with lock:
                itls.extend(
                    (b - a) * 1e3 for a, b in zip(stamps, stamps[1:]))

        loop.start()
        threads = []
        for p, m in zip(prompts, lens):
            r = engine.submit(p, max_new_tokens=int(m))
            t = threading.Thread(target=consume, args=(r,),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=300)
        stop.set()
        loop.join(timeout=10)
        with lock:
            vals = sorted(itls)
        stats = engine.stats()
        return {
            "streams": streams,
            "tokens_per_sec": round(best, 1),
            "itl_ms_p50": vals[int(0.50 * (len(vals) - 1))]
            if vals else None,
            "itl_ms_p99": vals[int(0.99 * (len(vals) - 1))]
            if vals else None,
            "acceptance_rate": stats.get("acceptance_rate"),
            "accept_len": registry.histogram("serving_accept_len").value,
            "steady_recompiles": stats["recompiles_since_mark"],
            "flight_overhead_frac": stats["flight"]["overhead_frac"],
            "memory": stats["memory"],
            "exposition": render_prometheus(registry),
        }

    spec = run(True)
    base = run(False)
    result = {
        "spec_tokens_per_sec": spec["tokens_per_sec"],
        "baseline_tokens_per_sec": base["tokens_per_sec"],
        "decode_speedup": (
            round(spec["tokens_per_sec"] / base["tokens_per_sec"], 2)
            if base["tokens_per_sec"] else None
        ),
        "spec_itl_ms_p50": spec["itl_ms_p50"],
        "baseline_itl_ms_p50": base["itl_ms_p50"],
        "spec_itl_ms_p99": spec["itl_ms_p99"],
        "baseline_itl_ms_p99": base["itl_ms_p99"],
        "acceptance_rate": spec["acceptance_rate"],
        "accept_len": spec["accept_len"],
        "parity": spec["streams"] == base["streams"],
        "spec_steady_recompiles": spec["steady_recompiles"],
        "baseline_steady_recompiles": base["steady_recompiles"],
        "flight_overhead_frac": spec["flight_overhead_frac"],
        "memory": spec["memory"],
        "train_s": round(train_s, 1),
        "train_loss": round(loss, 5),
        "config": f"d{D}/h{H}/L{L}/v{V}-slots{slots}"
                  f"-req{n_requests}-new{max_new}-k{spec_k}"
                  f"-draft{draft}-period{period}"
                  f"-chunk{prefill_chunk}-budget{tick_token_budget}"
                  f"-{dtype}" + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # CI drift guards: speculation must not perturb a single greedy
        # token, must actually be faster at high acceptance (the >=1.5x
        # floor is the ISSUE's headline; the measured smoke sits ~2.5x,
        # so this survives CI jitter), must populate the acceptance
        # telemetry, and must never re-trace in steady state
        assert result["parity"], result
        assert result["decode_speedup"] >= 1.5, result
        assert result["spec_itl_ms_p50"] <= result["baseline_itl_ms_p50"], (
            result)
        assert result["acceptance_rate"] and result["acceptance_rate"] > 0.5, (
            result)
        assert "serving_draft_tokens_total" in spec["exposition"]
        assert "serving_accepted_tokens_total" in spec["exposition"]
        assert "serving_accept_len" in spec["exposition"]
        assert result["spec_steady_recompiles"] == {}, result
        assert result["baseline_steady_recompiles"] == {}, result
        assert result["flight_overhead_frac"] < 0.05, result
    for k in ("exposition",):
        spec.pop(k, None)
    print(json.dumps(result), flush=True)
    return result


def _readback_bound(flight) -> bool:
    """True when the measured engine's SYNC loop actually blocks on
    token readback (flight ``device_wait_ms`` p50 exceeding
    ``dispatch_ms`` p50) — i.e., the runtime surfaces device time at
    the readback point, which is exactly where the pipelined loop can
    hide host work. Accelerator runtimes (whole-program d2h sync) look
    like this. The XLA CPU thunk runtime does NOT: it materializes the
    early token thunk immediately and surfaces the remaining compute
    inside the NEXT donating dispatch, so the sync loop is already
    implicitly overlapped there and an explicit pipeline has nothing
    left to win. The bench probes the measured arm itself and the
    result lands in the JSON, so a reader of the speedup sees which
    regime produced the number."""
    wait = flight.percentile("device_wait_ms", 50)
    disp = flight.percentile("dispatch_ms", 50)
    return (wait is not None and disp is not None and wait > disp)


def bench_pipeline(V=1024, D=256, H=4, L=4, slots=8, n_requests=16,
                   prompt_len=16, max_new=48, prefill_chunk=16,
                   dtype="float32", smoke=False, checks=True):
    """Pipelined async engine loop vs the sync reference
    (``ServingEngine(pipeline=True)`` A/B, ISSUE 10): sustained decode
    tokens/sec over a drain of staggered-length mixed greedy/sampled
    requests, slot layout as the headline plus a paged parity leg.
    Both arms get two warm passes (compile + prefix-hit steady state)
    before ``mark_steady``, then best-of-3 measured drains — so the
    recompile assert covers exactly the measured regime.

    The pipelined loop's win is overlap: host planning + token
    streaming of tick N hidden behind device compute of tick N+1. That
    win exists exactly where the sync loop blocks on readback;
    :func:`_readback_bound` probes the measured sync arm's own flight
    decomposition and the result lands in the JSON beside the speedup.
    The smoke asserts parity, zero steady-state recompiles and flight
    overhead; the speedup and the flight-recorder ``device_wait_ms``
    p50 of both arms are recorded, not asserted — a timing on the CPU
    backend under the test workers' load says nothing of the overlap
    (on readback-bound runtimes the pipelined p50 should drop)."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate
    from distkeras_tpu.serving import ServingEngine

    if smoke:
        V, D, H, L, slots = 64, 64, 2, 2, 4
        n_requests, prompt_len, max_new, prefill_chunk = 8, 8, 24, 8
    max_len = prompt_len + max_new
    max_len += (-max_len) % 16  # paged leg: whole blocks
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    lens = rng.integers(max(4, max_new // 2), max_new + 1,
                        size=n_requests)
    temps = [0.0 if i % 2 == 0 else 0.8 for i in range(n_requests)]

    def run(pipeline, paged):
        eng = ServingEngine(
            model, params, slots=slots, pipeline=pipeline,
            paged=paged, block_size=16, prefill_chunk=prefill_chunk,
            registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(),
        )

        def one_pass():
            reqs = [eng.submit(p, max_new_tokens=int(m), temperature=t,
                               seed=i)
                    for i, (p, m, t) in enumerate(zip(prompts, lens,
                                                      temps))]
            t0 = time.perf_counter()
            eng.drain()
            dt = time.perf_counter() - t0
            streams = [r.stream.tokens(timeout=300) for r in reqs]
            return streams, sum(map(len, streams)) / dt

        # pass 1 compiles, pass 2 reaches the paged prefix-hit steady
        # state (suffix prefills + COW) — both before the recompile mark
        one_pass()
        one_pass()
        eng.mark_steady()
        best, streams = 0.0, None
        for _ in range(3):
            streams, tps = one_pass()
            best = max(best, tps)
        st = eng.stats()
        return {
            "streams": streams,
            "tokens_per_sec": round(best, 1),
            "flight": eng.flight,
            "device_wait_ms_p50": eng.flight.percentile(
                "device_wait_ms", 50),
            "overrun_tokens": st["overrun_tokens"],
            "steady_recompiles": st["recompiles_since_mark"],
            "flight_overhead_frac": st["flight"]["overhead_frac"],
            "memory": st["memory"],
        }

    sync = run(False, False)
    pipe = run(True, False)
    sync_paged = run(False, True)
    pipe_paged = run(True, True)
    # greedy rows must also equal solo generate() — ties the A/B to the
    # engine's ground-truth contract, not just to itself
    solo_ok = True
    for i, (p, m, t) in enumerate(zip(prompts, lens, temps)):
        if t != 0.0:
            continue
        want = np.asarray(generate(
            model, params, jnp.asarray(p)[None], int(m)
        ))[0, prompt_len:].tolist()
        solo_ok = solo_ok and pipe["streams"][i] == want
    capable = _readback_bound(sync["flight"])
    result = {
        "pipe_tokens_per_sec": pipe["tokens_per_sec"],
        "sync_tokens_per_sec": sync["tokens_per_sec"],
        "speedup": (
            round(pipe["tokens_per_sec"] / sync["tokens_per_sec"], 3)
            if sync["tokens_per_sec"] else None
        ),
        "paged_pipe_tokens_per_sec": pipe_paged["tokens_per_sec"],
        "paged_sync_tokens_per_sec": sync_paged["tokens_per_sec"],
        "pipe_device_wait_ms_p50": pipe["device_wait_ms_p50"],
        "sync_device_wait_ms_p50": sync["device_wait_ms_p50"],
        "overrun_tokens": pipe["overrun_tokens"],
        "parity": (pipe["streams"] == sync["streams"]
                   and pipe_paged["streams"] == sync_paged["streams"]
                   and sync_paged["streams"] == sync["streams"]
                   and solo_ok),
        "overlap_capable": capable,
        "pipe_steady_recompiles": pipe["steady_recompiles"],
        "sync_steady_recompiles": sync["steady_recompiles"],
        "paged_pipe_steady_recompiles": pipe_paged["steady_recompiles"],
        "flight_overhead_frac": pipe["flight_overhead_frac"],
        "memory": pipe["memory"],
        "config": f"d{D}/h{H}/L{L}/v{V}-slots{slots}-req{n_requests}"
                  f"-prompt{prompt_len}+{max_new}-chunk{prefill_chunk}"
                  f"-{dtype}" + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # the pipeline's contract, self-asserted: bit-identical streams
        # (pipe vs sync vs solo, slot AND paged), zero steady-state
        # re-traces in every measured arm, bounded flight overhead
        # (the speedup and the probe's regime are in the JSON)
        assert result["parity"], result
        assert result["pipe_steady_recompiles"] == {}, result
        assert result["sync_steady_recompiles"] == {}, result
        assert result["paged_pipe_steady_recompiles"] == {}, result
        assert result["flight_overhead_frac"] < 0.05, result
    print(json.dumps(result), flush=True)
    return result


def bench_multistep(V=1024, D=256, H=4, L=4, slots=8, n_requests=16,
                    prompt_len=16, max_new=48, prefill_chunk=16,
                    k_list=(1, 2, 4, 8), dtype="float32", smoke=False,
                    checks=True):
    """Device-resident multi-step decode (``ServingEngine(
    multi_step_k=k)``, ISSUE 19): sustained decode tokens/sec vs the
    window width k over a drain of staggered-length mixed
    greedy/sampled requests — slot layout as the headline sweep plus a
    paged parity leg at the best k. The win is dispatch amortization:
    one host→device dispatch and one readback per k tokens instead of
    per token, so tok/s should rise monotonically-or-flat with k
    wherever per-dispatch overhead is a real cost, with every stream
    bit-identical to the k=1 reference.

    Each arm warms the tick family on a throwaway engine (compile +
    steady state), then measures on a FRESH engine whose histograms
    only ever see steady-state passes — the ITL p99 comparison against
    k=1 is therefore clean of compile spikes, which matters because the
    whole point of per-token ITL attribution is that a k-wide window
    must NOT show up as a k-wide ITL lump."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate
    from distkeras_tpu.serving import ServingEngine

    if smoke:
        V, D, H, L, slots = 64, 64, 2, 2, 4
        n_requests, prompt_len, max_new, prefill_chunk = 8, 8, 24, 8
    max_len = prompt_len + max_new
    max_len += (-max_len) % 16  # paged leg: whole blocks
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    lens = rng.integers(max(4, max_new // 2), max_new + 1,
                        size=n_requests)
    temps = [0.0 if i % 2 == 0 else 0.8 for i in range(n_requests)]

    def run(k, paged):
        def make():
            return ServingEngine(
                model, params, slots=slots, paged=paged,
                block_size=16, prefill_chunk=prefill_chunk,
                multi_step_k=k,
                registry=telemetry.MetricRegistry(),
                tracer=telemetry.Tracer(),
            )

        def one_pass(eng):
            reqs = [eng.submit(p, max_new_tokens=int(m), temperature=t,
                               seed=i)
                    for i, (p, m, t) in enumerate(zip(prompts, lens,
                                                      temps))]
            t0 = time.perf_counter()
            eng.drain()
            dt = time.perf_counter() - t0
            streams = [r.stream.tokens(timeout=300) for r in reqs]
            return streams, sum(map(len, streams)) / dt

        # throwaway warmer: pass 1 compiles the tick family for this
        # (k, layout), pass 2 reaches the paged prefix-hit steady state
        warm = make()
        one_pass(warm)
        one_pass(warm)
        # measured engine: the builders are module-level lru_caches
        # keyed on structurally-equal module clones, so the fresh
        # engine pays no re-trace — its registry sees ONLY steady state
        eng = make()
        streams, tps = one_pass(eng)
        eng.mark_steady()
        best = tps
        for _ in range(3):
            streams, tps = one_pass(eng)
            best = max(best, tps)
        st = eng.stats()
        return {
            "streams": streams,
            "tokens_per_sec": round(best, 1),
            "itl_ms_p99": st["itl_ms"]["p99"],
            "dispatches": st["dispatches"],
            "tokens_per_dispatch_p50": st["tokens_per_dispatch"]["p50"],
            "fallbacks": st["multi_step_fallbacks"],
            "steady_recompiles": st["recompiles_since_mark"],
            "flight_overhead_frac": st["flight"]["overhead_frac"],
            "memory": st["memory"],
        }

    k_list = tuple(sorted(set(int(k) for k in k_list)))
    arms = {k: run(k, paged=False) for k in k_list}
    k1 = arms[min(k_list)]
    best_k = max(arms, key=lambda k: arms[k]["tokens_per_sec"])
    paged_arm = run(best_k, paged=True)

    # parity: every arm (and the paged leg) bit-identical, greedy rows
    # also equal solo generate() — ties the sweep to the engine's
    # ground-truth contract, not just to itself
    parity = all(a["streams"] == k1["streams"] for a in arms.values())
    parity = parity and paged_arm["streams"] == k1["streams"]
    for i, (p, m, t) in enumerate(zip(prompts, lens, temps)):
        if t != 0.0:
            continue
        want = np.asarray(generate(
            model, params, jnp.asarray(p)[None], int(m)
        ))[0, prompt_len:].tolist()
        parity = parity and k1["streams"][i] == want

    recompiles: dict = {}
    for k, a in arms.items():
        recompiles.update(a["steady_recompiles"])
    recompiles.update(paged_arm["steady_recompiles"])

    result = {
        **{f"tok_s_k{k}": a["tokens_per_sec"] for k, a in arms.items()},
        "best_k": best_k,
        "speedup_best": (
            round(arms[best_k]["tokens_per_sec"]
                  / k1["tokens_per_sec"], 3)
            if k1["tokens_per_sec"] else None
        ),
        "paged_tok_s_best": paged_arm["tokens_per_sec"],
        **{f"itl_p99_ms_k{k}": a["itl_ms_p99"]
           for k, a in arms.items()},
        **{f"dispatches_k{k}": a["dispatches"]
           for k, a in arms.items()},
        "tokens_per_dispatch_p50_best":
            arms[best_k]["tokens_per_dispatch_p50"],
        "fallbacks_best": arms[best_k]["fallbacks"],
        "parity": parity,
        "multi_steady_recompiles": recompiles,
        "flight_overhead_frac": arms[best_k]["flight_overhead_frac"],
        "memory": arms[best_k]["memory"],
        "config": f"d{D}/h{H}/L{L}/v{V}-slots{slots}-req{n_requests}"
                  f"-prompt{prompt_len}+{max_new}-chunk{prefill_chunk}"
                  f"-k{','.join(map(str, k_list))}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # the window's contract, self-asserted: bit-identical streams
        # at every k (slot AND paged, sampled AND greedy-vs-solo), zero
        # steady-state re-traces in every measured arm, strictly fewer
        # dispatches at the best k (the amortization is real, not
        # vacuous), tok/s monotonic-or-flat k=1→4 with >=1.3x at the
        # best k, and ITL p99 no worse than k=1 at matched load (the
        # per-token attribution bound, with the host-tier bench's
        # small-absolute slack for sub-ms CPU steps)
        assert result["parity"], result
        assert result["multi_steady_recompiles"] == {}, result
        if max(k_list) > 1:
            kb = result["best_k"]
            assert result[f"dispatches_k{kb}"] < result[
                f"dispatches_k{min(k_list)}"] or kb == min(k_list), result
            assert result["speedup_best"] >= 1.3, result
            if 4 in arms and 1 in arms:
                assert (result["tok_s_k4"]
                        >= result["tok_s_k1"]), result
            p99_1 = result[f"itl_p99_ms_k{min(k_list)}"]
            p99_b = result[f"itl_p99_ms_k{best_k}"]
            if p99_1 and p99_b:
                assert p99_b <= 1.1 * p99_1 + 2.5, result
    print(json.dumps(result), flush=True)
    return result


def bench_multichip(tp_list=(1, 2), V=1024, D=256, H=8, Hk=4, L=4,
                    slots=4, n_requests=16, prompt_len=16, max_new=32,
                    block_size=16, dtype="float32", smoke=False):
    """Tensor-parallel decode: the same paged chunked engine at
    increasing mesh width (``make_mesh({'model': tp})``), measuring
    sustained decode tokens/sec per tp against the single-chip
    (mesh=None) engine. Token streams must be BIT-IDENTICAL to the
    single-chip paged path at every tp, and the measured pass must hit
    every jit cache (``recompiles_since_mark() == {}``).

    On forced host devices (CPU CI) the numbers measure dispatch, not
    silicon — the parity and recompile asserts are the point there;
    real scaling numbers come from running this on a TPU slice, where
    each shard's decode reads 1/tp of the KV cache per tick (the
    bandwidth-bound decode lever). If the process has fewer devices
    than ``max(tp_list)``, re-exec under
    ``--xla_force_host_platform_device_count`` (the dryrun_multichip
    pattern) before calling this."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.parallel.mesh import make_mesh
    from distkeras_tpu.serving import ServingEngine

    if smoke:
        V, D, H, Hk, L, slots = 64, 32, 8, 4, 2, 2
        n_requests, prompt_len, max_new = 6, 8, 8
        block_size = 8
    need = max(tp_list)
    if len(jax.devices()) < need:
        raise RuntimeError(
            f"bench_multichip needs {need} devices, have "
            f"{len(jax.devices())} — run via --multichip (it forces "
            f"host devices when short)"
        )
    max_len = prompt_len + max_new
    max_len += (-max_len) % block_size
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense", num_kv_heads=Hk, pos_emb="rope",
    )
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]

    def run(mesh):
        eng = ServingEngine(
            model, params, slots=slots, paged=True,
            block_size=block_size, registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(), mesh=mesh,
        )

        def one_pass():
            reqs = [eng.submit(p, max_new_tokens=max_new)
                    for p in prompts]
            t0 = time.perf_counter()
            eng.drain()
            dt = time.perf_counter() - t0
            streams = [r.stream.tokens(timeout=120) for r in reqs]
            return streams, sum(map(len, streams)) / dt

        one_pass()  # warm: trace every tick/prefill shape this run uses
        eng.mark_steady()
        streams, tps = one_pass()
        return streams, tps, eng.recompiles_since_mark()

    base_streams, base_tps, _ = run(None)
    result = {
        "baseline_decode_tok_s": round(base_tps, 1),
        "multichip_decode_tok_s": {},
        "parity": True,
        "steady_recompiles": {},
        "n_devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "config": f"d{D}/h{H}kv{Hk}/L{L}/v{V}-slots{slots}"
                  f"-req{n_requests}-prompt{prompt_len}+{max_new}"
                  f"-bs{block_size}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    for tp in tp_list:
        streams, tps, recomp = run(make_mesh({"model": tp}))
        result["multichip_decode_tok_s"][f"tp{tp}"] = round(tps, 1)
        result["parity"] = result["parity"] and (streams == base_streams)
        result["steady_recompiles"].update(recomp)
    if smoke:
        # drift guards: sharding must not perturb a single token, and a
        # steady-state measured pass must never re-trace
        assert result["parity"], result
        assert result["steady_recompiles"] == {}, result
    print(json.dumps(result), flush=True)
    return result


def _respawn_on_virtual_cpu(need: int, args, what: str, timeout=1800):
    """Run this script again with ``args`` in a child pinned to the CPU
    backend with ``need`` virtual host devices (the flag must be set
    before XLA initializes a backend) and return its JSON line — with a
    ``ran_on`` label: these are the "multi-replica" numbers of a process
    that saw fewer real devices than replicas, and they are CPU
    numbers. The child never needs the chip this parent may hold."""
    import subprocess

    env = dict(os.environ)
    flags = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={need}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), *args]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{what} subprocess failed "
            f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}\n"
            f"{proc.stdout[-2000:]}"
        )
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")][-1]
    result = {**json.loads(line),
              "ran_on": f"cpu: {need} virtual host devices, child process"}
    print(json.dumps(result), flush=True)
    return result


def run_multichip(tp_list=(1, 2), smoke=False):
    """bench_multichip with the dryrun_multichip respawn pattern: when
    this process has fewer devices than max(tp_list) (one real chip, or
    a plain CPU host), re-exec the bench in a subprocess with a forced
    virtual CPU mesh — the env must be set before XLA initializes a
    backend. Returns the bench's JSON dict either way."""
    need = max(tp_list)
    if len(jax.devices()) >= need:
        return bench_multichip(tp_list=tp_list, smoke=smoke)

    args = ["--multichip", "--tp-list", ",".join(map(str, tp_list))]
    return _respawn_on_virtual_cpu(
        need, args + ["--smoke"] * smoke, "multichip bench")


def bench_router(V=512, D=256, H=4, L=2, replicas=3, slots=2,
                 n_prefixes=3, prefix_len=1024, tail_len=8, max_new=4,
                 n_requests=48, clients=3, block_size=16,
                 prefill_chunk=64, slack_blocks=5,
                 n_failover=6, failover_new=24, dtype="float32",
                 smoke=False, checks=True):
    """Multi-replica serving fabric: N in-process LMServer replicas
    (each pinned to its own device) behind the prefix-affinity Router,
    vs ONE replica with the identical per-replica config.

    The workload is ``n_prefixes`` distinct system prompts cycled
    round-robin by a closed loop of ``clients`` concurrent clients —
    the many-tenants-few-templates shape prefix caching exists for.
    Each replica's block pool is sized to hold ONE cached prefix
    (plus working blocks), so the fleet's *aggregate* cache capacity is
    the scaling resource: affine routing partitions the prefix working
    set across replicas (every replica serves its own prefix from
    cache), while a single replica with the same per-replica pool
    must evict round-robin and re-prefill almost every prompt. That
    capacity effect is host-parallelism-independent — the ≥2.4×
    aggregate-throughput floor holds even on a single-core runner,
    where replica *compute* cannot overlap; on multi-core hosts (and
    real multi-chip fleets, where each replica owns an accelerator)
    dispatch overlap adds on top.

    Three routed passes + one reference measure the fabric:

    - fleet (affine) vs single replica: aggregate tokens/sec over the
      makespan — the throughput-scaling headline;
    - fleet (random routing): the control arm — same fleet, affinity
      off — whose fleet ``prefix_hit_fraction`` collapses because every
      replica keeps evicting every prefix;
    - a single replica given the fleet's aggregate block budget,
      served through the router: the hit-fraction reference that
      prefix-affine routing must stay within 10% of.

    A failover phase then streams ``n_failover`` longer requests
    through a fresh fleet, kills the replica carrying the most
    in-flight streams, and requires every accepted stream to complete
    bit-identical to solo ``generate()`` (replay-with-skip on the
    survivors) with zero requests reported failed.

    The measured fleet pass also exercises fleet-wide tracing: every
    sampled request must yield ONE complete merged span chain under
    its propagated trace id (router + replica spans, zero lost spans),
    the router's per-request archive round trips must cost <5% of the
    bench window, the ``chrome_trace`` op's Perfetto export must be
    valid trace-event JSON (saved to
    ``/tmp/distkeras-router-chrome-trace.json`` for the CI artifact),
    and the critical-path phase sums must reconcile with the
    client-observed latency.

    ``--smoke`` self-asserts all of the above (≥2.4× scaling, affine
    hit fraction within 10% of the reference, random measurably worse,
    zero lost streams, zero steady-state recompiles in the measured
    fleet pass, plus the tracing contract). Needs ``replicas`` local
    devices — run via :func:`run_router`, which forces virtual host
    devices when the process is short (CPU CI)."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate
    from distkeras_tpu.serving import (
        LMServer, Router, ServingClient, ServingEngine,
    )

    if smoke:
        # the default sizes ARE modest (CPU-runnable in ~2 min); smoke
        # only trims the failover tail
        n_failover, failover_new = 4, 16
    if len(jax.devices()) < replicas:
        raise RuntimeError(
            f"bench_router wants {replicas} devices (one per replica), "
            f"have {len(jax.devices())} — run via --router (it forces "
            f"host devices when short)"
        )
    max_len = prefix_len + tail_len + max(max_new, failover_new)
    max_len += (-max_len) % block_size
    max_blocks = max_len // block_size
    prefix_blocks = prefix_len // block_size
    # per-replica pool: ONE cached prefix + one request's worst case +
    # slack. This is the capacity knob that makes aggregate fleet
    # cache the scaling resource: a replica can hold its own prefix
    # hot, but n_prefixes of them cannot coexist, so the single
    # replica LRU-thrashes (round-robin arrivals are LRU's worst case)
    # while the affine fleet serves every prefix from cache.
    num_blocks = 1 + prefix_blocks + max_blocks + slack_blocks
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(0)
    prefixes = [rng.integers(0, V, size=prefix_len).astype(np.int32)
                for _ in range(n_prefixes)]
    # request i = prefix (i mod P) + a fresh tail: round-robin is LRU's
    # worst case for the capacity-starved single replica and the steady
    # state for the affine fleet
    def make_prompt(i, r):
        tail = r.integers(0, V, size=tail_len).astype(np.int32)
        return np.concatenate([prefixes[i % n_prefixes], tail])

    devices = jax.devices()

    def start_fleet(n, pool_blocks):
        servers = []
        for i in range(n):
            eng = ServingEngine(
                model, params, slots=slots, paged=True,
                block_size=block_size, num_blocks=pool_blocks,
                prefill_chunk=prefill_chunk,
                registry=telemetry.MetricRegistry(),
                # distinct tracer process identities: in-process
                # replicas stand in for replica processes, so merged
                # chains / Chrome exports get one lane per replica
                tracer=telemetry.Tracer(pid=1000 + i),
                device=devices[i % len(devices)],
            )
            servers.append(LMServer(eng).start())
        return servers

    def warm_and_mark(servers):
        # compile every shape each replica will use — one cold prefix,
        # one repeat (the suffix-only hit path), decode — with a
        # THROWAWAY prefix so the bench prefixes start uncached; then
        # declare steady state (any later re-trace is a bug)
        wrng = np.random.default_rng(999)
        for s in servers:
            c = ServingClient("127.0.0.1", s.port)
            pref = wrng.integers(0, V, size=prefix_len).astype(np.int32)
            for _ in range(2):
                tail = wrng.integers(0, V, size=tail_len).astype(np.int32)
                rid = c.generate(np.concatenate([pref, tail]),
                                 max_new_tokens=max_new)
                c.result(rid, timeout=300)
            c.close()
        for s in servers:
            s.engine.mark_steady()

    def run_routed(n_replicas, policy, pool_blocks,
                   verify_traces=False):
        servers = start_fleet(n_replicas, pool_blocks)
        warm_and_mark(servers)
        router = Router(
            [("127.0.0.1", s.port, f"r{i}")
             for i, s in enumerate(servers)],
            policy=policy, block_size=block_size, poll_interval=0.1,
            registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(pid=1),
        ).start()
        client = ServingClient("127.0.0.1", router.port,
                               request_timeout=300.0)
        prng = np.random.default_rng(7)
        prompts = [make_prompt(i, prng) for i in range(n_requests)]
        lock = threading.Lock()
        nxt = [0]
        streams: dict = {}
        traces: dict = {}
        lats: dict = {}

        def worker():
            while True:
                with lock:
                    if nxt[0] >= n_requests:
                        return
                    i = nxt[0]
                    nxt[0] += 1
                t_req = time.perf_counter()
                rid = client.generate(prompts[i], max_new_tokens=max_new)
                toks, reason = client.result(rid, timeout=300)
                lat_ms = (time.perf_counter() - t_req) * 1e3
                with lock:
                    streams[i] = (toks, reason)
                    traces[i] = client.trace_of(rid)
                    lats[i] = lat_ms

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        dt = time.perf_counter() - t0
        router.manager.probe_all()  # fresh counters for the fleet sums
        st = client.stats()
        recomp: dict = {}
        for s in servers:
            recomp.update(s.engine.recompiles_since_mark())
        out = {
            "tokens_per_sec": round(
                sum(len(t) for t, _ in streams.values()) / dt, 1),
            "prefix_hit_fraction": st.get("prefix_hit_fraction"),
            "requests_completed": st.get("requests_completed"),
            "spilled": st["router"]["spilled"],
            "routed": st["router"]["routed"],
            "failed": st["router"]["failed"],
            "steady_recompiles": recomp,
            "streams": streams,
            "prompts": prompts,
        }
        if verify_traces:
            out["trace"] = _verify_traces(client, st, traces, lats, dt)
        client.close()
        router.stop()
        for s in servers:
            s.stop()
        return out

    def _verify_traces(client, st, traces, lats, dt):
        """Fleet-tracing acceptance, measured on the live fleet: every
        sampled request yields ONE complete merged chain under its
        propagated id (zero lost spans), the archive's per-request
        round trips cost <5% of the bench window, the chrome_trace op
        exports valid trace-event JSON (saved for the CI artifact),
        and the critical-path phase sums reconcile with the
        client-observed latency."""
        required = {"router.route", "router.stream", "queued",
                    "prefill", "decode", "finish", "stream"}
        sample = sorted(traces)[:16]
        lost = 0
        for i in sample:
            chain = client.trace_dump(trace=traces[i])
            names = {s["span"] for s in chain}
            ids = {s["trace"] for s in chain}
            if not required <= names or ids != {traces[i]}:
                lost += 1
        # critical path vs the client's own stopwatch, on the slowest
        # sampled request (largest denominator): phase sums must
        # reconcile within 5%, floored at 25 ms of wire/ack overhead a
        # sub-100ms CPU request cannot amortize
        slow = max(sample, key=lambda i: lats[i])
        cp = telemetry.critical_path(
            client.trace_dump(trace=traces[slow]))
        phase_sum = sum(cp["phases"].values()) if cp else None
        cp_ok = (phase_sum is not None
                 and abs(phase_sum - lats[slow])
                 <= max(0.05 * lats[slow], 25.0))
        doc = client.chrome_trace(trace=traces[slow])
        events = doc["traceEvents"]
        invalid = [e for e in events
                   if not all(k in e for k in ("ph", "ts", "pid", "tid"))]
        s_ids = {e["id"] for e in events if e.get("ph") == "s"}
        f_ids = {e["id"] for e in events if e.get("ph") == "f"}
        with open("/tmp/distkeras-router-chrome-trace.json", "w") as fh:
            json.dump(doc, fh)
        arch = st["router"]["trace_archive"]
        return {
            "n_traced": len(sample),
            "lost_spans": lost,
            "archived": arch["archived"],
            "archive_errors": arch["errors"],
            # archive round trips relative to the measured window —
            # the tracing-overhead bound the smoke asserts
            "overhead_frac": round(
                (arch["ms_total"] / 1e3) / max(dt, 1e-9), 4),
            "critical_path": cp,
            "client_ms": round(lats[slow], 1),
            "critical_path_reconciles": cp_ok,
            "chrome_events": len(events),
            "chrome_invalid": len(invalid),
            "chrome_flows_paired": bool(s_ids) and s_ids == f_ids,
        }

    def run_failover():
        servers = start_fleet(replicas, num_blocks)
        warm_and_mark(servers)
        router = Router(
            [("127.0.0.1", s.port, f"r{i}")
             for i, s in enumerate(servers)],
            policy="affine", block_size=block_size, poll_interval=0.05,
            down_after=1, backoff_base=0.05,
            registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(),
        ).start()
        client = ServingClient("127.0.0.1", router.port,
                               request_timeout=300.0)
        frng = np.random.default_rng(11)
        prompts = [frng.integers(0, V, size=16).astype(np.int32)
                   for _ in range(n_failover)]
        rids = [client.generate(p, max_new_tokens=failover_new)
                for p in prompts]
        # kill the replica carrying the most in-flight streams once
        # tokens are moving
        deadline = time.monotonic() + 60
        by = {}
        while time.monotonic() < deadline:
            by = router.stats()["router"]["inflight_by_replica"]
            if by and max(by.values()) >= 2:
                break
            time.sleep(0.01)
        victim = max(by, key=by.get) if by else "r0"
        servers[int(victim[1:])].stop()
        lost = 0
        for p, rid in zip(prompts, rids):
            toks, reason = client.result(rid, timeout=300)
            want = np.asarray(generate(
                model, params, jnp.asarray(p)[None], failover_new
            ))[0, len(p):].tolist()
            if toks != want or reason != "length":
                lost += 1
        st = client.stats()
        out = {
            "streams_lost": lost,
            "killed": victim,
            "inflight_on_victim": by.get(victim, 0),
            "failed_over": st["router"]["failed_over"],
            "failed": st["router"]["failed"],
        }
        client.close()
        router.stop()
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass
        return out

    fleet = run_routed(replicas, "affine", num_blocks,
                       verify_traces=True)
    single = run_routed(1, "affine", num_blocks)
    rand = run_routed(replicas, "random", num_blocks)
    # hit-fraction reference: ONE replica with the fleet's aggregate
    # block budget — what affinity must preserve across the split fleet
    ref = run_routed(1, "affine",
                     1 + replicas * (prefix_blocks + slack_blocks)
                     + slots * max_blocks)
    failover = run_failover()

    # parity spot check: routed streams are solo-generate streams
    parity = True
    for i in list(fleet["streams"])[:4]:
        want = np.asarray(generate(
            model, params, jnp.asarray(fleet["prompts"][i])[None], max_new
        ))[0, len(fleet["prompts"][i]):].tolist()
        got, reason = fleet["streams"][i]
        parity = parity and got == want and reason == "length"

    result = {
        "router_scaling": (
            round(fleet["tokens_per_sec"] / single["tokens_per_sec"], 2)
            if single["tokens_per_sec"] else None
        ),
        "fleet_tokens_per_sec": fleet["tokens_per_sec"],
        "single_tokens_per_sec": single["tokens_per_sec"],
        "fleet_hit_affine": fleet["prefix_hit_fraction"],
        "fleet_hit_random": rand["prefix_hit_fraction"],
        "single_hit_thrash": single["prefix_hit_fraction"],
        "single_hit_reference": ref["prefix_hit_fraction"],
        "parity": parity,
        "spilled": fleet["spilled"],
        "failover_streams_lost": failover["streams_lost"],
        "failover_failed_over": failover["failed_over"],
        "failover_inflight_on_victim": failover["inflight_on_victim"],
        "failover_failed": failover["failed"],
        "fleet_steady_recompiles": fleet["steady_recompiles"],
        "fleet_trace": fleet.get("trace"),
        "n_devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "config": f"d{D}/h{H}/L{L}/v{V}-replicas{replicas}x{slots}slots"
                  f"-prefix{prefix_len}x{n_prefixes}+{tail_len}"
                  f"-new{max_new}-req{n_requests}-clients{clients}"
                  f"-bs{block_size}-blocks{num_blocks}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # the fabric's contract, self-asserted (ISSUE 8 acceptance):
        # capacity scaling, affinity preserving the fleet hit fraction
        # (random routing measurably worse), failover losing nothing,
        # and no steady-state re-traces in the measured fleet pass
        assert result["parity"], result
        assert result["router_scaling"] >= 2.4, result
        assert (result["fleet_hit_affine"]
                >= 0.9 * result["single_hit_reference"]), result
        assert (result["fleet_hit_random"]
                < result["fleet_hit_affine"] - 0.1), result
        assert result["failover_streams_lost"] == 0, result
        assert result["failover_failed"] == 0, result
        assert result["failover_failed_over"] >= 1, result
        assert result["fleet_steady_recompiles"] == {}, result
        # fleet tracing (ISSUE 11 acceptance): one complete merged
        # chain per request (zero lost spans), archive+export overhead
        # under 5% of the bench window (alongside the per-replica
        # flight-overhead bound the engines already self-assert),
        # Perfetto-valid export with paired flow arrows, and
        # critical-path sums reconciling with client latency
        tr = result["fleet_trace"]
        assert tr["lost_spans"] == 0, result
        assert tr["archive_errors"] == 0, result
        assert tr["overhead_frac"] < 0.05, result
        assert tr["chrome_invalid"] == 0, result
        assert tr["chrome_flows_paired"], result
        assert tr["critical_path_reconciles"], result
    for k in ("streams", "prompts"):
        fleet.pop(k, None)
    print(json.dumps(result), flush=True)
    return result


def run_router(smoke=False, replicas=3, checks=True):
    """bench_router with the respawn pattern: when this process has
    fewer devices than replicas (one real chip, or a plain CPU host),
    re-exec in a subprocess with forced virtual host devices so each
    replica engine owns a device (the env must be set before XLA
    initializes). Returns the bench's JSON dict either way."""
    if len(jax.devices()) >= replicas:
        return bench_router(smoke=smoke, replicas=replicas,
                            checks=checks)

    return _respawn_on_virtual_cpu(
        replicas, ["--router", "--replicas", str(replicas)]
        + ["--smoke"] * smoke + ["--no-checks"] * (not checks),
        "router bench")


def bench_fleet_sim(V=256, D=64, H=2, L=2, slots=2,
                    min_replicas=1, max_replicas=3,
                    n_tenants=4, prefix_len=64, tail_len=16,
                    batch_body=256, interactive_new=24, batch_new=8,
                    block_size=16, prefill_chunk=32,
                    tick_token_budget=48,
                    baseline_clients=2, ramp_clients=12,
                    burst_clients=20, batch_clients=4,
                    think_time=0.005,
                    baseline_s=1.5, ramp_s=5.0, burst_s=7.0,
                    kill_after_s=2.0, settle_timeout_s=45.0,
                    itl_slo_ms=500.0, seed=0, dtype="float32",
                    smoke=False, checks=True):
    """Elastic-fleet simulation: the :class:`Autoscaler` control loop
    driven end to end by a deterministic, seeded load model shaped
    like a diurnal million-user trace scaled to CI — a baseline
    trickle, an arrival ramp, a 10x interactive burst with long-prompt
    batch traffic riding along (tenant-skewed prompts throughout), a
    replica kill at the worst moment, then silence.

    The fleet starts at ``min_replicas`` in-process LMServer replicas
    (one per forced host device) behind the Router; ``max_replicas``
    more are pre-built, warmed, and ``mark_steady()``-ed into a spare
    pool — the ``spawn`` actuator hands them to the controller, which
    is exactly how a real fleet holds warm standbys so elasticity
    never pays a compile (and how this bench can assert zero
    steady-state recompiles *through* scale-ups). Load is closed-loop
    per phase — N concurrent clients with seeded think time — so queue
    pressure is machine-speed-independent: the controller's signals,
    not wall-clock token rates, are what the phases shape.

    Interactive traffic rides the default QoS tier; batch clients
    submit ``tier="batch"`` long-prompt requests that the scheduler
    admits only behind the interactive queue and whose prefill chunks
    are preempted first under ``tick_token_budget`` pressure — the
    burst phase is where batch gives so interactive holds.

    ``--smoke`` self-asserts the controller contract end to end:

    - determinism: ``Autoscaler.replay()`` of the recorded signal
      timeline through a fresh DecisionEngine reproduces the live
      decision sequence exactly (same seed → same signals → same
      scaling decisions);
    - convergence without flap: the fleet reaches ``max_replicas``
      on the ramp, returns to ``min_replicas`` after the traffic
      stops, and the action sequence is monotone — zero scale-ups
      after the first scale-down (the hysteresis/cooldown law);
    - QoS isolation: interactive p99 ITL during the burst stays
      within ``itl_slo_ms`` while batch absorbs the degradation
      (batch p99 TTFT above interactive's, batch prefill chunks
      preempted at least once);
    - resilience: a replica killed mid-burst loses zero streams
      (router replay) and the controller replaces it from the spare
      pool (a scale-up after the kill);
    - zero steady-state recompiles across every engine, spares and
      scale-ups included.

    Needs ``max_replicas + 1`` local devices — run via
    :func:`run_fleet_sim`, which forces virtual host devices when the
    process is short (CPU CI)."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import (
        Autoscaler, FIFOScheduler, LMServer, Router, ServingClient,
        ServingEngine,
    )

    n_servers = max_replicas + 1  # kill consumes one for good
    if len(jax.devices()) < n_servers:
        raise RuntimeError(
            f"bench_fleet_sim wants {n_servers} devices (one per "
            f"replica incl. the post-kill spare), have "
            f"{len(jax.devices())} — run via --fleet-sim (it forces "
            f"host devices when short)"
        )
    max_len = prefix_len + batch_body + max(interactive_new, batch_new)
    max_len += (-max_len) % block_size
    max_blocks = max_len // block_size
    num_blocks = (1 + slots * max_blocks
                  + n_tenants * (prefix_len // block_size) + 8)
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))

    # ---- the deterministic trace: tenant-skewed prompts, precomputed
    # from the seed so two runs offer the identical request sequence
    rng = np.random.default_rng(seed)
    tenants = [rng.integers(0, V, size=prefix_len).astype(np.int32)
               for _ in range(n_tenants)]
    skew = np.array([1.0 / (k + 1) for k in range(n_tenants)])
    skew /= skew.sum()  # zipf-ish: tenant 0 dominates

    def make_trace(n, body_len):
        return [np.concatenate([
            tenants[int(rng.choice(n_tenants, p=skew))],
            rng.integers(0, V, size=body_len).astype(np.int32),
        ]) for _ in range(n)]

    trace_i = make_trace(1024, tail_len)
    trace_b = make_trace(128, batch_body)

    # ---- fleet: every server pre-built and warmed so a scale-up is a
    # pool pop, never a compile
    devices = jax.devices()
    servers = {}
    for i in range(n_servers):
        reg = telemetry.MetricRegistry()
        tracer = telemetry.Tracer(pid=1000 + i)
        eng = ServingEngine(
            model, params, slots=slots, paged=True,
            block_size=block_size, num_blocks=num_blocks,
            prefill_chunk=prefill_chunk,
            scheduler=FIFOScheduler(
                tick_token_budget=tick_token_budget,
                registry=reg, tracer=tracer),
            registry=reg, tracer=tracer,
            device=devices[i % len(devices)],
        )
        # per-replica SLO monitor: the controller's burn signals flow
        # through manager.aggregate_alerts() -> these monitors. Bounds
        # are lenient — this sim drives scaling with queue depth; the
        # burn-driven paths are covered by tests/test_controller.py.
        # The anomaly twins ride along with CI-speed calibration
        # (baseline+ramp train the EWMA, the 10x burst deviates): the
        # smoke asserts at least one fires
        slo = telemetry.SloMonitor(
            telemetry.default_serving_rules(
                itl_p99_ms=10_000.0, ttft_p99_ms=120_000.0,
                max_queue_depth=1e9, max_expiry_per_s=1e9)
            + telemetry.default_anomaly_rules(
                z_threshold=3.0, min_samples=8,
                windows=(0.75, 2.0)),
            registry=reg, tracer=tracer, interval_s=0.25)
        servers[f"r{i}"] = LMServer(eng, slo=slo).start()

    wrng = np.random.default_rng(999)
    for s in servers.values():
        c = ServingClient("127.0.0.1", s.port)
        pref = wrng.integers(0, V, size=prefix_len).astype(np.int32)
        tail_a = wrng.integers(0, V, size=tail_len).astype(np.int32)
        # cold prefix, full repeat, a MID-block divergent tail (random
        # tails in the trace birthday-collide on leading tokens, so
        # the copy-on-write block copy is a steady-state shape), and
        # the long batch prompt
        tail_c = tail_a.copy()
        tail_c[tail_len // 2:] = wrng.integers(
            0, V, size=tail_len - tail_len // 2)
        for tail in (tail_a, tail_a, tail_c,
                     wrng.integers(0, V, size=batch_body
                                   ).astype(np.int32)):
            rid = c.generate(np.concatenate([pref, tail]),
                             max_new_tokens=4)
            c.result(rid, timeout=300)
        c.close()
    for s in servers.values():
        s.engine.mark_steady()

    router = Router(
        [("127.0.0.1", servers["r0"].port, "r0")],
        policy="affine", block_size=block_size,
        spill_queue_depth=2, poll_interval=0.05,
        down_after=1, backoff_base=0.05,
        registry=telemetry.MetricRegistry(),
        tracer=telemetry.Tracer(pid=1),
    ).start()

    pool_lock = threading.Lock()
    spares = [f"r{i}" for i in range(1, n_servers)]

    def spawn():
        with pool_lock:
            if not spares:
                raise RuntimeError("spare pool exhausted")
            name = spares.pop(0)
        # a previously retired replica left the fleet drained;
        # re-open admissions before it rejoins routing
        servers[name].engine.end_drain()
        return ("127.0.0.1", servers[name].port, name)

    def retire(name):
        with pool_lock:
            spares.append(name)
            spares.sort()

    auto = Autoscaler(
        router, spawn=spawn, retire=retire,
        interval_s=0.2, drain_timeout_s=60.0,
        registry=telemetry.MetricRegistry(),
        tracer=telemetry.Tracer(pid=2),
        min_replicas=min_replicas, max_replicas=max_replicas,
        queue_high=3.0, queue_low=0.5,
        up_consecutive=2, down_consecutive=8,
        cooldown_s=1.5, rebalance=False,
    )

    # ---- closed-loop load: phase-tagged at submit time
    client = ServingClient("127.0.0.1", router.port,
                           request_timeout=300.0)
    stop_evt = threading.Event()
    phase_box = {"name": "baseline"}
    lock = threading.Lock()
    cursor = {"interactive": 0, "batch": 0}
    samples: list = []
    lost = [0]
    threads: list = []

    def worker(tier, wid):
        prng = np.random.default_rng(seed * 7919 + wid)
        trace = trace_i if tier == "interactive" else trace_b
        new = interactive_new if tier == "interactive" else batch_new
        while not stop_evt.is_set():
            with lock:
                i = cursor[tier]
                cursor[tier] += 1
            prompt = trace[i % len(trace)]
            ph = phase_box["name"]
            t0 = time.perf_counter()
            try:
                rid = client.generate(prompt, max_new_tokens=new,
                                      tier=tier)
                ttft = None
                last = t0
                itls = []
                reason = None
                for kind, val in client.frames(rid, timeout=300):
                    t = time.perf_counter()
                    if kind == "end":
                        reason = val
                        break
                    if ttft is None:
                        ttft = (t - t0) * 1e3
                    else:
                        itls.append((t - last) * 1e3)
                    last = t
            except Exception:
                with lock:
                    lost[0] += 1
                continue
            with lock:
                if reason != "length":
                    lost[0] += 1
                samples.append({"phase": ph, "tier": tier,
                                "ttft_ms": ttft, "itl_ms": itls})
            if tier == "interactive" and think_time:
                stop_evt.wait(float(prng.uniform(0.5, 1.5))
                              * think_time)

    def add_workers(tier, n):
        for _ in range(n):
            t = threading.Thread(target=worker,
                                 args=(tier, len(threads)), daemon=True)
            threads.append(t)
            t.start()

    auto.start()
    add_workers("interactive", baseline_clients)
    time.sleep(baseline_s)
    phase_box["name"] = "ramp"
    add_workers("interactive", ramp_clients - baseline_clients)
    time.sleep(ramp_s)
    phase_box["name"] = "burst"
    add_workers("interactive", burst_clients - ramp_clients)
    add_workers("batch", batch_clients)
    time.sleep(kill_after_s)

    # kill the busiest routable replica mid-burst (name tiebreak keeps
    # the choice reproducible under equal load)
    deadline = time.monotonic() + 30
    routable = []
    while time.monotonic() < deadline:
        routable = [r.name for r in router.manager.routable()]
        if len(routable) >= 2:
            break
        time.sleep(0.05)
    by = router.stats()["router"]["inflight_by_replica"]
    killed = max(routable, key=lambda n: (by.get(n, 0), n))
    # stamp BEFORE stop(): the manager sees the sockets die the moment
    # stop() starts closing them, so the controller's replacement
    # scale-up can fire while stop() is still joining threads
    kill_t = time.monotonic()
    servers[killed].stop()
    time.sleep(max(burst_s - kill_after_s, 0.0))

    phase_box["name"] = "settle"
    stop_evt.set()
    for t in threads:
        t.join(timeout=600)
    deadline = time.monotonic() + settle_timeout_s
    while time.monotonic() < deadline:
        if len(router.manager.routable()) <= min_replicas:
            break
        time.sleep(0.1)
    time.sleep(0.5)  # a few more polls observing the converged fleet
    auto.stop()

    # ---- harvest
    replay_ok = auto.replay() == auto.decisions()
    acts = list(auto.events)
    ups = [e for e in acts if e["action"] == "scale_up"]
    downs = [e for e in acts if e["action"] == "scale_down"]
    osc = 0
    seen_down = False
    for e in acts:
        if e["action"] == "scale_down":
            seen_down = True
        elif e["action"] == "scale_up" and seen_down:
            osc += 1
    recomp: dict = {}
    preempt = {"interactive": 0, "batch": 0}
    for s in servers.values():
        recomp.update(s.engine.recompiles_since_mark())
        try:
            qos = s.engine.stats().get("qos", {})
        except Exception:
            qos = {}
        for t in preempt:
            preempt[t] += int(qos.get(t, {}).get("preempted_chunks", 0))

    # ---- time-series / journal forensics (scraped over the live wire
    # BEFORE teardown — this is the fleet-wide `timeseries`/`events`
    # path the operator tooling uses)
    import io

    from distkeras_tpu.telemetry.report import render_fleet_timeline
    from distkeras_tpu.telemetry.timeseries import write_timeline

    fleet_ts = router.fleet_timeseries()
    fleet_ev = router.fleet_events()
    scale_events = [e for e in fleet_ev["events"]
                    if e.get("actor") == "autoscaler"]
    # the journal must reconcile 1:1 with the controller's own decision
    # log — same actions, same polls, same reasons, in order
    journal_reconciles = (
        [(e["action"], e.get("poll"), e.get("reason"))
         for e in scale_events]
        == [(d["action"], d.get("poll"), d.get("reason"))
            for d in auto.decisions()])
    events_ordered = all(
        a["t"] <= b["t"] for a, b in zip(fleet_ev["events"],
                                         fleet_ev["events"][1:]))
    tl_path = "/tmp/distkeras-fleet-timeline.jsonl"
    write_timeline(tl_path, fleet_ts["points"], fleet_ev["events"],
                   meta=fleet_ts["meta"])
    buf = io.StringIO()
    try:
        render_fleet_timeline(fleet_ts["points"], fleet_ev["events"],
                              meta=fleet_ts["meta"], out=buf)
        rendered = buf.getvalue()
        timeline_renders = (events_ordered and all(
            e["action"] in rendered for e in scale_events))
    except Exception:
        timeline_renders = False
    # anomaly firings: the cumulative slo_alerts_total counter per
    # *_anomaly rule, summed across every replica's registry
    anomaly_fired: dict = {}
    for s in servers.values():
        fam = s.engine.registry.collect().get("slo_alerts_total") or {}
        for se in fam.get("series", []):
            rule = se["labels"].get("rule", "")
            if rule.endswith("_anomaly") and se["value"] > 0:
                anomaly_fired[rule] = (anomaly_fired.get(rule, 0)
                                       + int(se["value"]))
    ts_overhead = max(
        (s.timeseries.meta()["overhead_frac"]
         for s in servers.values() if s.timeseries is not None),
        default=0.0)
    ts_overhead = max(ts_overhead,
                      router.timeseries.meta()["overhead_frac"])
    # the p99 ITL exemplar must name a trace the router actually
    # archived — the registry→trace join is the whole point
    archived = set(router.archive.ids()) if router.archive else set()
    exemplar_ids = []
    for s in servers.values():
        try:
            ex = s.engine.stats()["itl_ms"]["p99_exemplar"]
        except Exception:
            ex = None
        if ex and ex.get("trace_id") is not None:
            exemplar_ids.append(ex["trace_id"])

    def _resolves(tid):
        try:
            return int(tid) in archived
        except (TypeError, ValueError):
            return False

    exemplar_resolved = any(_resolves(t) for t in exemplar_ids)

    def pct(vals, q):
        return (round(float(np.percentile(np.asarray(vals), q)), 1)
                if vals else None)

    burst_i = [s for s in samples
               if s["phase"] == "burst" and s["tier"] == "interactive"]
    burst_b = [s for s in samples
               if s["phase"] == "burst" and s["tier"] == "batch"]
    result = {
        "replay_deterministic": replay_ok,
        "scale_ups": len(ups),
        "scale_downs": len(downs),
        "oscillations": osc,
        "actuation_failures": sum(1 for e in acts if not e.get("ok")),
        "max_routable": max(s["replicas"]
                            for _, s in auto.signal_log),
        "final_routable": auto.signal_log[-1][1]["replicas"],
        "killed": killed,
        "post_kill_scale_up": any(e["t"] >= kill_t for e in ups),
        "lost_streams": lost[0],
        "requests_interactive": sum(
            1 for s in samples if s["tier"] == "interactive"),
        "requests_batch": sum(
            1 for s in samples if s["tier"] == "batch"),
        "burst_itl_p99_interactive_ms": pct(
            [g for s in burst_i for g in s["itl_ms"]], 99),
        "burst_ttft_p99_interactive_ms": pct(
            [s["ttft_ms"] for s in burst_i
             if s["ttft_ms"] is not None], 99),
        "burst_ttft_p99_batch_ms": pct(
            [s["ttft_ms"] for s in burst_b
             if s["ttft_ms"] is not None], 99),
        "itl_slo_ms": itl_slo_ms,
        "batch_preempted_chunks": preempt["batch"],
        "interactive_preempted_chunks": preempt["interactive"],
        "controller_polls": len(auto.signal_log),
        "actions": [{k: e.get(k) for k in
                     ("action", "reason", "replica", "ok")}
                    for e in acts],
        "journal_events": len(fleet_ev["events"]),
        "journal_scale_events": len(scale_events),
        "journal_reconciles": journal_reconciles,
        "anomaly_rules_fired": sorted(anomaly_fired),
        "anomaly_firings": sum(anomaly_fired.values()),
        "timeseries_points": len(fleet_ts["points"]),
        "timeseries_sources": fleet_ts["meta"].get("sources"),
        "timeseries_overhead_frac": round(ts_overhead, 6),
        "timeline_path": tl_path,
        "timeline_renders": timeline_renders,
        "itl_p99_exemplar_resolved": exemplar_resolved,
        "steady_recompiles": recomp,
        "n_devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "config": f"d{D}/h{H}/L{L}/v{V}-fleet{min_replicas}.."
                  f"{max_replicas}x{slots}slots-tenants{n_tenants}"
                  f"-burst{burst_clients}+{batch_clients}batch"
                  f"-budget{tick_token_budget}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # the controller contract, self-asserted (see docstring)
        assert result["replay_deterministic"], result
        assert result["actuation_failures"] == 0, result
        assert result["scale_ups"] >= 2, result
        assert result["max_routable"] == max_replicas, result
        assert result["scale_downs"] >= 1, result
        assert result["final_routable"] == min_replicas, result
        assert result["oscillations"] == 0, result
        assert result["lost_streams"] == 0, result
        assert result["post_kill_scale_up"], result
        assert result["burst_itl_p99_interactive_ms"] is not None, result
        assert (result["burst_itl_p99_interactive_ms"]
                <= itl_slo_ms), result
        assert (result["burst_ttft_p99_batch_ms"]
                > result["burst_ttft_p99_interactive_ms"]), result
        assert result["batch_preempted_chunks"] >= 1, result
        assert result["steady_recompiles"] == {}, result
        # the observability contract: the journal IS the decision log,
        # the burst registers as an anomaly, the timeline renders with
        # every scale action in timestamp order, sampling stays under
        # 1% overhead, and the tail exemplar joins to a real archived
        # trace
        assert result["journal_reconciles"], result
        assert result["journal_scale_events"] == len(acts), result
        assert result["anomaly_firings"] >= 1, result
        assert result["timeline_renders"], result
        assert result["timeseries_points"] >= 1, result
        assert result["timeseries_overhead_frac"] < 0.01, result
        assert result["itl_p99_exemplar_resolved"], result
    client.close()
    router.stop()
    for s in servers.values():
        try:
            s.stop()
        except Exception:
            pass
    print(json.dumps(result), flush=True)
    return result


def run_fleet_sim(smoke=False, checks=True, max_replicas=3):
    """bench_fleet_sim with the respawn pattern: when this process has
    fewer devices than the fleet wants (``max_replicas + 1``), re-exec
    in a subprocess with forced virtual host devices (the env must be
    set before XLA initializes). Returns the bench's JSON dict either
    way."""
    need = max_replicas + 1
    if len(jax.devices()) >= need:
        return bench_fleet_sim(smoke=smoke, checks=checks,
                               max_replicas=max_replicas)

    return _respawn_on_virtual_cpu(
        need, ["--fleet-sim"] + ["--smoke"] * smoke
        + ["--no-checks"] * (not checks), "fleet-sim")


def bench_disagg(V=64, D=256, H=4, L=2, replicas=3, slots=3,
                 n_short=12, short_prompt=8, short_new=8,
                 n_long=3, long_prompt=1024, long_new=2, long_every=2,
                 concurrency=4, block_size=32, prefill_chunk=32,
                 disagg_threshold=512, race_longs=4, race_prompt=256,
                 dtype="float32", smoke=False, checks=True):
    """Prefill/decode disaggregation through the router: the
    long-prompt-interference trace against a specialized fleet
    (1 prefill-role replica + ``replicas - 1`` decode-role replicas,
    KV blocks migrated over export_kv/import_kv) vs the uniform
    baseline (``replicas`` mixed replicas, same total hardware).

    Load shape (the PR-4 interference trace, lifted to the fleet): a
    closed-loop population of ``concurrency`` short requests decodes
    continuously through the router; after every ``long_every`` short
    completions one ``long_prompt``-token request arrives. In the
    uniform fleet the long prompt chunk-prefills THROUGH a decode
    replica's mixed ticks — every tick it rides is fatter, so the live
    streams' ITL inflates, and the prompt itself is metered through
    the shared token budget, so its TTFT stretches. In the
    disaggregated fleet the router runs the prompt on the prefill
    replica (monolithic whole-prompt dispatch — the compute-optimal
    shape, and nothing decodes there to feel the stall), ships the KV
    blocks to a decode replica, and the request decodes off a
    prefix-cache hit: decode replicas only ever see a one-chunk
    suffix.

    Client-side measurement: every token of every stream is
    timestamped — TTFT per request (p99 across shorts AND longs) and
    ITL per short stream (p99 across all gaps). A race phase then
    points the migration path at a prefill replica whose pool barely
    holds one prompt and fires ``race_longs`` concurrent longs:
    whatever mix of migrations and eviction-race fallbacks results,
    every stream must complete bit-identical (seeded replay is the
    fallback, zero lost streams).

    ``--smoke`` self-asserts: p99 TTFT AND p99 ITL both beat the
    uniform baseline, every long was migrated (outcome="ok"), sampled
    short + all long streams bit-identical to solo ``generate()``,
    zero lost/failed streams in the race phase, and zero steady-state
    recompiles in the measured disaggregated fleet. The latency beats
    hold even on a 1-core host (measured 1.6x TTFT / 2.7x ITL on a
    single-core worker): one monolithic dispatch on the dedicated
    prefill replica is simply cheaper than 32 fat mixed ticks
    competing with decode for budget and slots — parallel hardware
    (``parallel_capable`` in the JSON) adds overlap on top. Needs
    ``replicas`` local devices — run via :func:`run_disagg`, which
    forces virtual host devices when the process is short."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate
    from distkeras_tpu.serving import (
        FIFOScheduler, LMServer, Router, ServingClient, ServingEngine,
    )

    if len(jax.devices()) < replicas:
        raise RuntimeError(
            f"bench_disagg wants {replicas} devices (one per replica), "
            f"have {len(jax.devices())} — run via --disagg (it forces "
            f"host devices when short)"
        )
    max_len = long_prompt + max(long_new, short_new) + block_size
    max_len += (-max_len) % block_size
    max_blocks = max_len // block_size
    # every slot's worst case + every long prefix cached + slack
    num_blocks = (1 + slots * max_blocks
                  + (n_long + 1) * (long_prompt // block_size) + 8)
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(0)
    shorts = [rng.integers(0, V, size=short_prompt).astype(np.int32)
              for _ in range(n_short)]
    short_lens = rng.integers(max(2, short_new // 2), short_new + 1,
                              size=n_short)
    longs = [rng.integers(0, V, size=long_prompt).astype(np.int32)
             for _ in range(n_long)]
    devices = jax.devices()

    def start_fleet(roles, pool_blocks=None, chunk_override=None):
        servers = []
        for i, role in enumerate(roles):
            # the prefill replica runs MONOLITHIC whole-prompt prefill
            # (its compute-bound shape: one dispatch, no chunk-metering
            # — nothing decodes there to be stalled); decode/mixed
            # replicas keep the chunked mixed tick
            chunk = (None if role == "prefill"
                     else (chunk_override or prefill_chunk))
            eng = ServingEngine(
                model, params, slots=slots, paged=True,
                block_size=block_size,
                num_blocks=pool_blocks or num_blocks,
                prefill_chunk=chunk, role=role,
                scheduler=FIFOScheduler(
                    tick_token_budget=slots + (chunk or prefill_chunk),
                    registry=telemetry.MetricRegistry(),
                    tracer=telemetry.Tracer()),
                registry=telemetry.MetricRegistry(),
                tracer=telemetry.Tracer(pid=1000 + i),
                device=devices[i % len(devices)],
            )
            servers.append(LMServer(eng).start())
        return servers

    def run_arm(roles, disagg):
        servers = start_fleet(roles)
        router = Router(
            [("127.0.0.1", s.port, f"r{i}")
             for i, s in enumerate(servers)],
            block_size=block_size, poll_interval=0.1,
            disagg_prompt_tokens=(disagg_threshold if disagg else None),
            registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(pid=1),
        ).start()
        deadline = time.monotonic() + 30
        while (len(router.manager.routable()) < len(servers)
               and time.monotonic() < deadline):
            time.sleep(0.02)
        client = ServingClient("127.0.0.1", router.port,
                               request_timeout=600.0)
        # warm every shape each arm uses — throwaway prompts so the
        # bench prefixes start uncached — then declare steady state
        wrng = np.random.default_rng(999)
        wl = wrng.integers(0, V, size=long_prompt).astype(np.int32)
        ws = wrng.integers(0, V, size=short_prompt).astype(np.int32)
        for p, n in ((ws, short_new), (wl, long_new), (wl, long_new)):
            rid = client.generate(p, max_new_tokens=int(n))
            client.result(rid, timeout=600)
        for s in servers:
            s.engine.mark_steady()

        lock = threading.Lock()
        itls, ttfts = [], []
        short_streams, long_streams = {}, {}
        short_left = list(range(n_short))
        long_left = list(range(n_long))
        short_done, long_done, long_fired = [0], [0], [0]
        threads = []

        def consume_long(j):
            t0 = time.perf_counter()
            rid = client.generate(longs[j], max_new_tokens=long_new)
            stamps, toks = [], []
            for tok in client.stream(rid, timeout=600):
                stamps.append(time.perf_counter())
                toks.append(tok)
            with lock:
                if stamps:
                    ttfts.append((stamps[0] - t0) * 1e3)
                long_streams[j] = toks
                long_done[0] += 1

        def consume_short(i):
            t0 = time.perf_counter()
            rid = client.generate(shorts[i],
                                  max_new_tokens=int(short_lens[i]))
            stamps, toks = [], []
            for tok in client.stream(rid, timeout=600):
                stamps.append(time.perf_counter())
                toks.append(tok)
            with lock:
                if stamps:
                    ttfts.append((stamps[0] - t0) * 1e3)
                itls.extend((b - a) * 1e3
                            for a, b in zip(stamps, stamps[1:]))
                short_streams[i] = toks
                short_done[0] += 1
                nxt = short_left.pop(0) if short_left else None
                fire = (long_left
                        and short_done[0] % long_every == 0)
                lng = long_left.pop(0) if fire else None
                if lng is not None:
                    long_fired[0] += 1
            if lng is not None:
                tl = threading.Thread(target=consume_long, args=(lng,),
                                      daemon=True)
                tl.start()
                with lock:
                    threads.append(tl)
            if nxt is not None:
                t = threading.Thread(target=consume_short, args=(nxt,),
                                     daemon=True)
                t.start()
                with lock:
                    threads.append(t)

        t0 = time.perf_counter()
        with lock:
            seeds = [short_left.pop(0)
                     for _ in range(min(concurrency, len(short_left)))]
        for i in seeds:
            t = threading.Thread(target=consume_short, args=(i,),
                                 daemon=True)
            t.start()
            with lock:
                threads.append(t)
        deadline = time.monotonic() + 900
        while time.monotonic() < deadline:
            with lock:
                if (short_done[0] >= n_short
                        and long_done[0] >= long_fired[0]
                        and not long_left):
                    break
                # shorts exhausted with longs never reached by the
                # completion cadence: fire the stragglers directly
                lng = (long_left.pop(0)
                       if long_left and short_done[0] >= n_short
                       else None)
                if lng is not None:
                    long_fired[0] += 1
            if lng is not None:
                tl = threading.Thread(target=consume_long, args=(lng,),
                                      daemon=True)
                tl.start()
                with lock:
                    threads.append(tl)
            time.sleep(0.005)
        dt = time.perf_counter() - t0
        st = client.stats()
        recomp: dict = {}
        for s in servers:
            recomp.update(s.engine.recompiles_since_mark())
        vals = sorted(itls)
        tt = sorted(ttfts)

        def p99(v):
            return v[int(0.99 * (len(v) - 1))] if v else None

        out = {
            "itl_ms_p50": (vals[int(0.50 * (len(vals) - 1))]
                           if vals else None),
            "itl_ms_p99": p99(vals), "itl_samples": len(vals),
            "ttft_ms_p99": p99(tt), "ttft_ms_max": tt[-1] if tt else None,
            "tokens_per_sec": round(
                (sum(len(t) for t in short_streams.values())
                 + sum(len(t) for t in long_streams.values())) / dt, 1),
            "kv_migrations_ok": 0.0,
            "kv_migration_ms": st["router"].get("kv_migration_ms"),
            "failed": st["router"]["failed"],
            "steady_recompiles": recomp,
            "short_streams": short_streams,
            "long_streams": long_streams,
        }
        mig = router.metrics().get("serving_kv_migrations_total", {})
        for s_ in mig.get("series", []):
            if s_.get("labels", {}).get("outcome") == "ok":
                out["kv_migrations_ok"] = s_.get("value", 0.0)
        client.close()
        router.stop()
        for s in servers:
            s.stop()
        return out

    def run_race():
        """Migration vs eviction: a prefill replica whose pool barely
        holds one prompt, several concurrent longs — every stream must
        complete bit-identical whatever mix of migrations and
        fallbacks results."""
        rr = np.random.default_rng(11)
        prompts = [rr.integers(0, V, size=race_prompt).astype(np.int32)
                   for _ in range(race_longs)]
        tiny = 1 + (race_prompt + long_new) // block_size + 4
        servers = start_fleet(["prefill"] + ["decode"] * (replicas - 1),
                              pool_blocks=None)
        # shrink only the prefill replica's pool: stop it, restart tiny
        servers[0].stop()
        servers[0] = start_fleet(["prefill"], pool_blocks=tiny)[0]
        router = Router(
            [("127.0.0.1", s.port, f"r{i}")
             for i, s in enumerate(servers)],
            block_size=block_size, poll_interval=0.1,
            disagg_prompt_tokens=min(disagg_threshold, race_prompt),
            registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(pid=2),
        ).start()
        deadline = time.monotonic() + 30
        while (len(router.manager.routable()) < len(servers)
               and time.monotonic() < deadline):
            time.sleep(0.02)
        client = ServingClient("127.0.0.1", router.port,
                               request_timeout=600.0)
        results = {}
        lock = threading.Lock()

        def one(i):
            rid = client.generate(prompts[i], max_new_tokens=long_new)
            with lock:
                results[i] = client.result(rid, timeout=600)

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(race_longs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        lost = 0
        for i, (toks, reason) in results.items():
            want = np.asarray(generate(
                model, params, jnp.asarray(prompts[i])[None], long_new
            ))[0, race_prompt:].tolist()
            if toks != want or reason != "length":
                lost += 1
        st = client.stats()
        mig_total = st["router"]["kv_migrations"]
        out = {
            "race_streams": len(results),
            "race_streams_lost": lost + (race_longs - len(results)),
            "race_failed": st["router"]["failed"],
            "race_migrations": mig_total,
        }
        client.close()
        router.stop()
        for s in servers:
            s.stop()
        return out

    disagg = run_arm(["prefill"] + ["decode"] * (replicas - 1),
                     disagg=True)
    base = run_arm(["mixed"] * replicas, disagg=False)
    race = run_race()

    # parity: every long stream and a sample of short streams must be
    # solo-generate streams, in BOTH arms
    parity = True
    for arm in (disagg, base):
        for j, toks in arm["long_streams"].items():
            want = np.asarray(generate(
                model, params, jnp.asarray(longs[j])[None], long_new
            ))[0, long_prompt:].tolist()
            parity = parity and toks == want
        for i in list(arm["short_streams"])[:4]:
            want = np.asarray(generate(
                model, params, jnp.asarray(shorts[i])[None],
                int(short_lens[i])
            ))[0, short_prompt:].tolist()
            parity = parity and arm["short_streams"][i] == want

    result = {
        "disagg_ttft_ms_p99": disagg["ttft_ms_p99"],
        "baseline_ttft_ms_p99": base["ttft_ms_p99"],
        "ttft_p99_reduction": (
            round(base["ttft_ms_p99"] / disagg["ttft_ms_p99"], 2)
            if disagg["ttft_ms_p99"] else None),
        "disagg_itl_ms_p99": disagg["itl_ms_p99"],
        "baseline_itl_ms_p99": base["itl_ms_p99"],
        "itl_p99_reduction": (
            round(base["itl_ms_p99"] / disagg["itl_ms_p99"], 2)
            if disagg["itl_ms_p99"] else None),
        "disagg_itl_ms_p50": disagg["itl_ms_p50"],
        "baseline_itl_ms_p50": base["itl_ms_p50"],
        "disagg_tokens_per_sec": disagg["tokens_per_sec"],
        "baseline_tokens_per_sec": base["tokens_per_sec"],
        "kv_migrations_ok": disagg["kv_migrations_ok"],
        "kv_migration_ms": disagg["kv_migration_ms"],
        "parity": parity,
        "failed": disagg["failed"] + base["failed"],
        "race_streams_lost": race["race_streams_lost"],
        "race_failed": race["race_failed"],
        "race_migrations": race["race_migrations"],
        "disagg_steady_recompiles": disagg["steady_recompiles"],
        "itl_samples": disagg["itl_samples"],
        # the latency contract needs real parallelism between the
        # prefill replica and the decode replicas — a 1-core host
        # serializes their compute and can only check correctness
        "parallel_capable": (os.cpu_count() or 1) >= 2,
        "n_devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "config": f"d{D}/h{H}/L{L}/v{V}-replicas{replicas}x{slots}slots"
                  f"-short{short_prompt}+{short_new}x{n_short}"
                  f"-long{long_prompt}+{long_new}x{n_long}"
                  f"-chunk{prefill_chunk}-bs{block_size}"
                  f"-thresh{disagg_threshold}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # the disaggregation contract, self-asserted (ISSUE 14
        # acceptance): migrated streams bit-identical, every long
        # actually migrated, BOTH tail latencies beat the uniform
        # fleet, the eviction race loses nothing, and the measured
        # disagg fleet never re-traced in steady state
        assert result["parity"], result
        assert result["kv_migrations_ok"] >= n_long, result
        # the latency headline holds even on a 1-core host (measured
        # 1.6x TTFT / 2.7x ITL there): one monolithic dispatch on the
        # dedicated prefill replica beats 32 fat mixed ticks competing
        # with decode for budget and slots, before parallel hardware
        # adds overlap on top
        assert (result["disagg_ttft_ms_p99"]
                < result["baseline_ttft_ms_p99"]), result
        assert (result["disagg_itl_ms_p99"]
                < result["baseline_itl_ms_p99"]), result
        assert result["failed"] == 0, result
        assert result["race_streams_lost"] == 0, result
        assert result["race_failed"] == 0, result
        assert result["disagg_steady_recompiles"] == {}, result
    for arm in (disagg, base):
        arm.pop("short_streams", None)
        arm.pop("long_streams", None)
    print(json.dumps(result), flush=True)
    return result


def run_disagg(smoke=False, replicas=3, checks=True):
    """bench_disagg with the respawn pattern of :func:`run_router`:
    forces virtual host devices when the process has fewer than
    ``replicas`` so each replica engine owns one."""
    if len(jax.devices()) >= replicas:
        return bench_disagg(smoke=smoke, replicas=replicas,
                            checks=checks)

    return _respawn_on_virtual_cpu(
        replicas, ["--disagg", "--replicas", str(replicas)]
        + ["--smoke"] * smoke + ["--no-checks"] * (not checks),
        "disagg bench", timeout=2400)


def bench_live_update(V=256, D=128, H=4, L=2, replicas=3, slots=2,
                      prompt_len=16, max_new=32, n_requests=18,
                      clients=3, block_size=16, n_updates=3,
                      dtype="float32", smoke=False, checks=True):
    """Zero-downtime live weight updates at the fleet level.

    Three in-process LMServer replicas behind the Router serve a
    closed loop of seeded greedy streams while the router performs
    rolling weight updates (drain → chunked push → undrain, one
    replica at a time) mid-flight. Three phases:

    - **baseline**: the workload with no pushes — client-side exact
      per-stream ITLs (every token timestamped at the client);
    - **live-update**: the identical workload while ``n_updates``
      fleet-wide rolling updates land mid-flight (alternating between
      two same-shape weight sets; one rides the wire ``push_weights``
      op, the rest the admin API). Every stream must complete with
      its full token budget (zero dropped/corrupted), post-update
      streams must be bit-identical to solo ``generate()`` on the
      final weights, ITL p99 must stay within 10% of baseline (+ a
      2.5 ms CPU-jitter floor), and the measured pass must stay at
      zero steady-state recompiles — a weight swap changes traced
      *values*, never compiled shapes;
    - **rollback**: the SLO-burn auto-rollback, end to end with a real
      quality canary. Each replica runs an :class:`SloMonitor` with
      one burn-rate rule — the *rate of length-finishes* on canary
      traffic that, under good weights, deterministically samples its
      eos early (greedy; ``eos_id`` is read off solo ``generate()``).
      An injected **bad checkpoint** (structurally valid, garbage
      values — validation rightly accepts it) makes canaries run to
      their full budget, the rule burns in every window, and the
      router's armed guard re-pushes the previous version:
      ``router_weight_rollbacks_total`` increments, canaries return
      to eos-finishing, and zero streams are lost throughout.

    ``--smoke`` self-asserts all of the above. Needs ``replicas``
    devices — run via :func:`run_live_update` (forces virtual host
    devices when short)."""
    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import generate
    from distkeras_tpu.serving import (
        LMServer, Router, ServingClient, ServingEngine,
    )
    from distkeras_tpu.telemetry.slo import SloMonitor, SloRule

    if len(jax.devices()) < replicas:
        raise RuntimeError(
            f"bench_live_update wants {replicas} devices, have "
            f"{len(jax.devices())} — run via --live-update (it forces "
            f"host devices when short)"
        )
    max_len = prompt_len + max_new + 16
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=max_len, dtype=jnp.dtype(dtype),
        attention="dense",
    )
    dummy = jnp.zeros((1, 4), jnp.int32)
    good_a = model.init(jax.random.PRNGKey(0), dummy)
    good_b = model.init(jax.random.PRNGKey(1), dummy)
    # the "bad checkpoint": same tree, same shapes, garbage values —
    # validation accepts it (as it should), only quality burns
    bad = model.init(jax.random.PRNGKey(666), dummy)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, V, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]

    devices = jax.devices()
    servers = []
    for i in range(replicas):
        reg = telemetry.MetricRegistry()
        eng = ServingEngine(
            model, good_a, slots=slots, paged=True,
            block_size=block_size, registry=reg,
            tracer=telemetry.Tracer(pid=1000 + i),
            device=devices[i % len(devices)],
        )
        # the quality canary: under good weights the canary stream
        # greedily samples its eos well inside the budget, so ANY
        # sustained rate of length-finishes is a burned objective
        slo = SloMonitor(
            [SloRule("canary_length_rate", "serving_requests_total",
                     "rate", 0.02, labels=(("reason", "length"),),
                     windows=(1.5, 3.0), burn_threshold=0.5)],
            registry=reg, tracer=eng.tracer, interval_s=0.25,
        )
        servers.append(LMServer(eng, slo=slo).start())
    router = Router(
        [("127.0.0.1", s.port, f"r{i}")
         for i, s in enumerate(servers)],
        block_size=block_size, poll_interval=0.1,
        registry=telemetry.MetricRegistry(),
        tracer=telemetry.Tracer(pid=1),
    ).start()
    client = ServingClient("127.0.0.1", router.port,
                           request_timeout=600.0)

    def refs(params):
        return {
            i: np.asarray(generate(
                model, params, jnp.asarray(p)[None], max_new
            ))[0, prompt_len:].tolist()
            for i, p in enumerate(prompts[:4])
        }

    def run_phase(tag):
        """Closed loop of `clients` workers over the prompt list;
        returns per-stream (tokens, reason) + exact client-side
        ITLs."""
        lock = threading.Lock()
        nxt = [0]
        streams: dict = {}
        itls: list = []

        def worker():
            while True:
                with lock:
                    if nxt[0] >= n_requests:
                        return
                    i = nxt[0]
                    nxt[0] += 1
                rid = client.generate(prompts[i],
                                      max_new_tokens=max_new)
                toks = []
                reason = None
                last_t = None
                gaps = []
                for kind, val in client.frames(rid, timeout=600):
                    now = time.perf_counter()
                    if kind == "end":
                        reason = val
                        break
                    toks.append(val)
                    if last_t is not None:
                        gaps.append((now - last_t) * 1e3)
                    last_t = now
                with lock:
                    streams[i] = (toks, reason)
                    itls.extend(gaps)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        dt = time.perf_counter() - t0
        arr = np.asarray(sorted(itls)) if itls else np.asarray([0.0])
        return {
            "tag": tag, "streams": streams, "makespan_s": dt,
            "itl_p50": float(arr[int(0.50 * (len(arr) - 1))]),
            "itl_p99": float(arr[int(0.99 * (len(arr) - 1))]),
            "tokens": int(sum(len(t) for t, _ in streams.values())),
        }

    # warmup: compile every shape (cold + repeat prompt, decode), and
    # one same-values push so nothing about the swap path is cold;
    # then declare steady state — later re-traces are a bug
    for _ in range(2):
        rid = client.generate(prompts[0], max_new_tokens=4)
        client.result(rid, timeout=600)
    router.rolling_update(good_a, retry_timeout_s=120.0)
    for s in servers:
        s.engine.mark_steady()

    base = run_phase("baseline")

    # live-update phase: the same workload with mid-flight rolling
    # updates — one through the wire op, the rest via the admin API
    push_err: list = []

    def pusher():
        try:
            pc = ServingClient("127.0.0.1", router.port,
                               request_timeout=600.0)
            sets = [good_b, good_a]
            for u in range(n_updates):
                time.sleep(0.3)
                params = sets[u % 2]
                if u == 0:
                    pc.push_weights(params, chunk_bytes=256 << 10,
                                    timeout=600.0)
                else:
                    router.rolling_update(params,
                                          retry_timeout_s=120.0)
            pc.close()
        except Exception as e:  # surfaced in the JSON, fails smoke
            push_err.append(f"{type(e).__name__}: {e}")

    pt = threading.Thread(target=pusher, daemon=True)
    pt.start()
    live = run_phase("live")
    pt.join(timeout=600)

    final_params = [good_b, good_a][(n_updates - 1) % 2]
    # post-update parity: fresh streams on the converged fleet are
    # bit-identical to solo generate() on the final weights
    want = refs(final_params)
    post_parity = True
    for i in want:
        rid = client.generate(prompts[i], max_new_tokens=max_new)
        toks, reason = client.result(rid, timeout=600)
        post_parity = post_parity and toks == want[i] \
            and reason == "length"
    # every mid-flight stream completed with its full budget
    complete = all(
        reason == "length" and len(toks) == max_new
        for toks, reason in live["streams"].values()
    )
    recomp: dict = {}
    for s in servers:
        recomp.update(s.engine.recompiles_since_mark())
    fleet_stats = client.stats()
    swaps_total = fleet_stats.get("weight_swaps")

    # -- rollback phase: bad checkpoint → SLO burn → auto-rollback ----
    canary_prompt = rng.integers(0, V, size=prompt_len).astype(np.int32)
    canary_ref = np.asarray(generate(
        model, final_params, jnp.asarray(canary_prompt)[None], max_new
    ))[0, prompt_len:].tolist()
    eos_id = int(canary_ref[3])  # the good weights emit this 4th
    canary_stop = threading.Event()
    canary_out: list = []

    def canary_loop():
        while not canary_stop.is_set():
            try:
                rid = client.generate(canary_prompt,
                                      max_new_tokens=max_new,
                                      eos_id=eos_id)
                toks, reason = client.result(rid, timeout=600)
                canary_out.append((time.monotonic(), reason,
                                   len(toks)))
            except Exception:
                canary_out.append((time.monotonic(), "error", 0))
            time.sleep(0.1)

    # let the live/parity phases' legitimate length-finishes decay out
    # of every burn window before arming the guard — the rollback must
    # be attributable to the canary regression, not stale rates
    time.sleep(3.5)
    ct = threading.Thread(target=canary_loop, daemon=True)
    ct.start()
    time.sleep(1.0)  # a little good-weights canary history
    # the bad push, guard armed on the fleet's per-replica monitors
    t_bad = time.monotonic()
    router.rolling_update(bad, guard_window_s=60.0,
                          retry_timeout_s=120.0)
    rollback_fired = False
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        w = router.stats()["router"]["weights"]
        if w["rollbacks"] >= 1:
            rollback_fired = True
            break
        time.sleep(0.2)
    t_rb = time.monotonic()
    time.sleep(2.0)  # post-rollback canaries
    canary_stop.set()
    ct.join(timeout=30)
    # canaries after the rollback finish on eos again (the previous
    # weights are back); none errored/disconnected at any point
    post_rb = [r for t, r, _ in canary_out if t > t_rb + 0.5]
    canary_recovered = bool(post_rb) and all(r == "eos"
                                             for r in post_rb)
    canary_lost = sum(1 for _, r, _ in canary_out
                      if r not in ("eos", "length"))
    wfinal = router.stats()["router"]["weights"]

    result = {
        "base_itl_ms_p50": round(base["itl_p50"], 3),
        "base_itl_ms_p99": round(base["itl_p99"], 3),
        "live_itl_ms_p50": round(live["itl_p50"], 3),
        "live_itl_ms_p99": round(live["itl_p99"], 3),
        "itl_p99_ratio": (
            round(live["itl_p99"] / base["itl_p99"], 3)
            if base["itl_p99"] else None
        ),
        "updates_applied": n_updates + 1,  # + the warmup push
        "fleet_weight_swaps": swaps_total,
        "streams_complete": complete,
        "post_update_parity": post_parity,
        "push_errors": push_err,
        "steady_recompiles": recomp,
        "rollback_fired": rollback_fired,
        "rollback_s": (round(t_rb - t_bad, 2) if rollback_fired
                       else None),
        "rollbacks_total": wfinal["rollbacks"],
        "canary_recovered": canary_recovered,
        "canary_streams_lost": canary_lost,
        "canary_runs": len(canary_out),
        "n_devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "config": f"d{D}/h{H}/L{L}/v{V}-replicas{replicas}x{slots}"
                  f"slots-new{max_new}-req{n_requests}-clients"
                  f"{clients}-updates{n_updates}-{dtype}"
                  + ("-smoke" if smoke else ""),
    }
    if smoke and checks:
        # the live-update contract (ISSUE 15 acceptance): mid-flight
        # fleet pushes with zero dropped/corrupted streams, post-swap
        # bit-parity, ITL p99 during swaps within 10% of the no-push
        # baseline (+ CPU-jitter floor), zero steady-state recompiles,
        # and the injected bad checkpoint triggering auto-rollback
        # with zero lost streams
        assert result["push_errors"] == [], result
        assert result["streams_complete"], result
        assert result["post_update_parity"], result
        assert result["steady_recompiles"] == {}, result
        assert (result["live_itl_ms_p99"]
                <= 1.10 * result["base_itl_ms_p99"] + 2.5), result
        assert result["rollback_fired"], result
        assert result["rollbacks_total"] >= 1, result
        assert result["canary_recovered"], result
        assert result["canary_streams_lost"] == 0, result
    client.close()
    router.stop()
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass
    print(json.dumps(result), flush=True)
    return result


def run_live_update(smoke=False, replicas=3, checks=True):
    """bench_live_update with the respawn pattern of
    :func:`run_router`: forces virtual host devices when the process
    has fewer than ``replicas`` so each replica engine owns one."""
    if len(jax.devices()) >= replicas:
        return bench_live_update(smoke=smoke, replicas=replicas,
                                 checks=checks)

    return _respawn_on_virtual_cpu(
        replicas, ["--live-update", "--replicas", str(replicas)]
        + ["--smoke"] * smoke + ["--no-checks"] * (not checks),
        "live-update bench", timeout=2400)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--interarrival", type=float, default=0.002,
                    help="mean Poisson inter-arrival (seconds)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--metrics", default=None,
                    help="JSONL path for the engine's MetricsWriter")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="paged-engine prefix-caching TTFT bench "
                         "(90%% shared system prompts)")
    ap.add_argument("--long-prompt-interference", action="store_true",
                    help="chunked-prefill ITL bench: short decode "
                         "streams vs a stream of long prompts, chunked "
                         "mixed ticks vs monolithic prefill")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny self-asserting CI variant of "
                         "--shared-prefix (default) or "
                         "--long-prompt-interference")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="shared system-prompt length (default 256)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--long-prompt", type=int, default=None,
                    help="interference bench: long-prompt length "
                         "(default 1024)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="interference bench: chunk size C (default 64)")
    ap.add_argument("--tick-token-budget", type=int, default=None,
                    help="interference bench: per-tick token budget "
                         "(default slots + chunk)")
    ap.add_argument("--think-time", type=float, default=0.0,
                    help="interference bench: pause (s) before each "
                         "closed-loop short refill — 0 saturates, > 0 "
                         "models paced traffic with idle headroom")
    ap.add_argument("--host-tier", action="store_true",
                    help="tiered KV cache bench: shared-prefix trace "
                         "sized to 3x the device pool's cache headroom, "
                         "host-RAM spill tier vs device-only vs "
                         "all-resident — prefix_hit_fraction >=2x "
                         "device-only, bit-identical streams, swap "
                         "bandwidth in the JSON")
    ap.add_argument("--restore-budget", type=int, default=4,
                    help="host-tier bench: blocks restored per tick "
                         "(FIFOScheduler restore_budget, default 4)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative-decoding bench: draft-assisted "
                         "verify ticks vs the plain mixed tick at high "
                         "acceptance (flagship overfit on a periodic "
                         "stream), decode tok/s + client-side ITL")
    ap.add_argument("--draft", default="ngram",
                    choices=["ngram", "model"],
                    help="speculative bench drafter: self-speculative "
                         "n-gram lookup (default) or a small overfit "
                         "draft TransformerLM")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative bench: draft tokens proposed per "
                         "row per tick (default 4)")
    ap.add_argument("--multi-step", action="store_true",
                    help="device-resident multi-step decode sweep: "
                         "tok/s and ITL p99 vs window width k, with "
                         "bit-parity, zero-recompile, and "
                         "dispatch-amortization self-asserts under "
                         "--smoke (ISSUE 19)")
    ap.add_argument("--multi-step-k", default="1,2,4,8",
                    help="comma list of window widths for --multi-step "
                         "(each arm serves the identical workload at "
                         "ServingEngine(multi_step_k=k))")
    ap.add_argument("--pipeline", action="store_true",
                    help="pipelined async engine loop A/B: "
                         "ServingEngine(pipeline=True) vs the sync "
                         "reference — decode tok/s, device_wait_ms "
                         "p50, bit-parity across slot+paged")
    ap.add_argument("--multichip", action="store_true",
                    help="tensor-parallel decode bench: the paged "
                         "engine under shard_map at each tp in "
                         "--tp-list vs single-chip, bit-identical "
                         "streams asserted; forces virtual host "
                         "devices when the process is short")
    ap.add_argument("--tp-list", default="1,2",
                    help="comma-separated tensor-parallel degrees for "
                         "--multichip (default 1,2)")
    ap.add_argument("--router", action="store_true",
                    help="multi-replica fabric bench: N in-process "
                         "LMServer replicas behind the prefix-affinity "
                         "Router vs one replica — closed-loop "
                         "throughput scaling, affine-vs-random fleet "
                         "prefix_hit_fraction, kill-one-replica "
                         "failover; forces virtual host devices when "
                         "the process is short")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode disaggregation bench: the "
                         "long-prompt-interference trace through a "
                         "1-prefill + (replicas-1)-decode fleet with "
                         "KV-block migration vs the uniform mixed "
                         "baseline — p99 TTFT + p99 ITL, migrated "
                         "parity, eviction-race zero-lost; forces "
                         "virtual host devices when the process is "
                         "short")
    ap.add_argument("--live-update", action="store_true",
                    help="zero-downtime live weight update bench: "
                         "mid-flight fleet rolling updates (drain → "
                         "chunked push → undrain) with zero dropped/"
                         "corrupted streams, ITL p99 within 10%% of "
                         "the no-push baseline, and an injected bad "
                         "checkpoint triggering SLO-burn auto-"
                         "rollback; forces virtual host devices when "
                         "the process is short")
    ap.add_argument("--replicas", type=int, default=3,
                    help="replica count for --router/--disagg/"
                         "--live-update (default 3)")
    ap.add_argument("--fleet-sim", action="store_true",
                    help="elastic-fleet simulation: the Autoscaler "
                         "control loop under a seeded diurnal load "
                         "model (baseline/ramp/10x burst with QoS "
                         "batch tier/replica kill/settle), asserting "
                         "deterministic replay, flap-free "
                         "convergence, interactive SLO held while "
                         "batch gives, and zero lost streams; forces "
                         "virtual host devices when the process is "
                         "short")
    ap.add_argument("--no-checks", action="store_true",
                    help="disable the --smoke self-asserts (used by "
                         "the flagship bench.py fold, where a fabric "
                         "regression must land as a worse number, not "
                         "a dead BENCH line)")
    args = ap.parse_args()
    if args.multi_step:
        kw = dict(slots=args.slots, dtype=args.dtype, smoke=args.smoke,
                  k_list=tuple(int(x) for x
                               in args.multi_step_k.split(",")),
                  checks=not args.no_checks)
        if args.prefill_chunk is not None:
            kw["prefill_chunk"] = args.prefill_chunk
        bench_multistep(**kw)
        return
    if args.pipeline:
        kw = dict(slots=args.slots, dtype=args.dtype, smoke=args.smoke,
                  checks=not args.no_checks)
        if args.prefill_chunk is not None:
            kw["prefill_chunk"] = args.prefill_chunk
        bench_pipeline(**kw)
        return
    if args.fleet_sim:
        kw = dict(smoke=args.smoke, checks=not args.no_checks)
        if len(jax.devices()) >= 4:
            bench_fleet_sim(**kw)
        else:
            run_fleet_sim(**kw)
        return
    if args.live_update:
        kw = dict(smoke=args.smoke, replicas=args.replicas,
                  checks=not args.no_checks)
        if len(jax.devices()) >= args.replicas:
            bench_live_update(**kw)
        else:
            run_live_update(**kw)
        return
    if args.disagg:
        kw = dict(smoke=args.smoke, replicas=args.replicas,
                  checks=not args.no_checks)
        if len(jax.devices()) >= args.replicas:
            bench_disagg(**kw)
        else:
            run_disagg(**kw)
        return
    if args.router:
        kw = dict(smoke=args.smoke, replicas=args.replicas,
                  checks=not args.no_checks)
        if len(jax.devices()) >= args.replicas:
            bench_router(**kw)
        else:
            run_router(**kw)
        return
    if args.multichip:
        tp_list = tuple(int(t) for t in args.tp_list.split(","))
        if len(jax.devices()) >= max(tp_list):
            bench_multichip(tp_list=tp_list, smoke=args.smoke)
        else:
            run_multichip(tp_list=tp_list, smoke=args.smoke)
        return
    if args.host_tier:
        kw = dict(slots=args.slots, block_size=args.block_size,
                  restore_budget=args.restore_budget, dtype=args.dtype,
                  smoke=args.smoke, checks=not args.no_checks)
        if args.prefix_len is not None:
            kw["prefix_len"] = args.prefix_len
        bench_host_tier(**kw)
        return
    if args.speculative:
        kw = dict(draft=args.draft, spec_k=args.spec_k,
                  dtype=args.dtype, smoke=args.smoke)
        if args.prefill_chunk is not None:
            kw["prefill_chunk"] = args.prefill_chunk
        if args.tick_token_budget is not None:
            kw["tick_token_budget"] = args.tick_token_budget
        bench_speculative(**kw)
        return
    if args.long_prompt_interference:
        kw = dict(slots=args.slots, dtype=args.dtype, smoke=args.smoke,
                  tick_token_budget=args.tick_token_budget,
                  think_time=args.think_time)
        if args.long_prompt is not None:
            kw["long_prompt"] = args.long_prompt
        if args.prefill_chunk is not None:
            kw["prefill_chunk"] = args.prefill_chunk
        bench_long_prompt_interference(**kw)
        return
    if args.shared_prefix or args.smoke:
        kw = dict(slots=args.slots, block_size=args.block_size,
                  dtype=args.dtype, smoke=args.smoke)
        # only forward explicit values — the function's defaults are the
        # tuned shared-prefix config, not the Poisson bench's
        if args.prefix_len is not None:
            kw["prefix_len"] = args.prefix_len
        if args.requests != ap.get_default("requests"):
            kw["n_requests"] = args.requests
        bench_shared_prefix(**kw)
        return
    bench(slots=args.slots, n_requests=args.requests,
          mean_interarrival_s=args.interarrival, dtype=args.dtype,
          metrics_path=args.metrics)


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
