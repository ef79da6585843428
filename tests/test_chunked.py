"""Chunked prefill fused into the decode tick: mixed-tick parity with
solo generate() across slot/paged × cache dtype × MHA/GQA × chunk
sizes, token-budget edge cases (budget < chunk, block-boundary
straddling, indivisible prompts, prefill starvation under decode
saturation, eos-during-prefill-tick refill), the deprecated
max_prefills_per_tick shim, ITL/stall telemetry, and the serve_bench
--long-prompt-interference --smoke drift guard."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import generate
from distkeras_tpu.serving import (
    DEFAULT_PREFILL_CHUNK,
    FIFOScheduler,
    ServingEngine,
)

KW = dict(vocab_size=64, d_model=32, num_heads=2, num_layers=2,
          max_len=48, dtype=jnp.float32, attention="dense")


def _model_and_params(seed=0, **over):
    kw = dict(KW)
    kw.update(over)
    model = get_model("transformer_lm", **kw)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _solo(model, params, prompt, **cfg):
    out = generate(
        model, params, jnp.asarray(prompt)[None], cfg["max_new_tokens"],
        temperature=cfg.get("temperature", 0.0),
        seed=cfg.get("seed", 0), eos_id=cfg.get("eos_id"),
        top_k=cfg.get("top_k"), top_p=cfg.get("top_p"),
    )
    toks = np.asarray(out)[0, len(prompt):].tolist()
    eos = cfg.get("eos_id")
    if eos is not None and eos in toks:
        toks = toks[: toks.index(eos) + 1]
    return toks


def _engine(model, params, paged=False, **kw):
    kw.setdefault("registry", telemetry.MetricRegistry())
    kw.setdefault("tracer", telemetry.Tracer())
    if paged:
        kw.setdefault("block_size", 8)
    return ServingEngine(model, params, paged=paged, **kw)


# -- parity matrix -----------------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 3, 64])
@pytest.mark.parametrize("mode", ["slot", "paged"])
@pytest.mark.parametrize("cache_dtype", ["model", "int8"])
def test_chunked_parity_matrix(mode, cache_dtype, chunk):
    """Streams served through the chunked mixed tick are token-identical
    to solo generate() for chunk sizes below, straddling, and beyond the
    prompt length — slot and paged layouts, both cache dtypes, GQA +
    rope, greedy and sampled decoding, and (paged) prefix hit / miss /
    mid-block COW while neighbours are mid-decode."""
    over = dict(pos_emb="rope", d_model=64, cache_dtype=cache_dtype,
                num_heads=4, num_kv_heads=2)
    model, params = _model_and_params(**over)
    rng = np.random.default_rng(0)
    system = rng.integers(0, 64, size=16).astype(np.int32)  # 2 blocks
    prompts = [
        np.concatenate([system, rng.integers(0, 64, size=5)]).astype(
            np.int32),                        # miss (first), then inserts
        np.concatenate([system, rng.integers(0, 64, size=6)]).astype(
            np.int32),                        # full-block hit (paged)
        rng.integers(0, 64, size=7).astype(np.int32),   # unrelated miss
        np.concatenate([system[:12], rng.integers(0, 64, size=6)]).astype(
            np.int32),                        # COW: diverges mid-block 2
    ]
    cfgs = [
        dict(max_new_tokens=6),
        dict(max_new_tokens=9),
        dict(max_new_tokens=4, temperature=1.0, seed=7),
        dict(max_new_tokens=7, temperature=0.8, seed=3, top_k=8),
    ]
    eng = _engine(model, params, paged=(mode == "paged"), slots=2,
                  prefill_chunk=chunk)
    reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
    eng.drain()
    for p, c, r in zip(prompts, cfgs, reqs):
        assert r.stream.tokens(timeout=10) == _solo(model, params, p, **c)
        assert r.stream.finish_reason == "length"
    if mode == "paged":
        # sharing still happens under chunked admission (suffix-only
        # chunks after the radix hit)
        assert eng.stats()["prefix_hit_tokens"] > 0
        assert np.all(eng.pool.ref == 0)
    # chunked engines never run a monolithic prefill dispatch
    assert eng.stats()["decode_stalls"] == 0


def test_chunked_parity_with_eos_mid_stream():
    model, params = _model_and_params()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, size=6).astype(np.int32)
               for _ in range(3)]
    probe = _solo(model, params, prompts[0], max_new_tokens=8)
    eos = probe[2]
    cfgs = [
        dict(max_new_tokens=8, eos_id=eos),
        dict(max_new_tokens=6),
        dict(max_new_tokens=5, temperature=1.0, seed=5, eos_id=eos),
    ]
    eng = _engine(model, params, slots=2, prefill_chunk=2)
    reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
    eng.drain()
    for p, c, r in zip(prompts, cfgs, reqs):
        assert r.stream.tokens(timeout=10) == _solo(model, params, p, **c)
    assert reqs[0].stream.finish_reason == "eos"


# -- token-budget edge cases -------------------------------------------------


def test_budget_smaller_than_one_chunk():
    """tick_token_budget below prefill_chunk: each tick carries at most
    budget prompt tokens (the chunk is truncated, not starved), the
    prompt still lands whole, streams stay parity-exact."""
    model, params = _model_and_params()
    rng = np.random.default_rng(2)
    p = rng.integers(0, 64, size=11).astype(np.int32)
    eng = _engine(model, params, slots=1, prefill_chunk=8,
                  scheduler=FIFOScheduler(tick_token_budget=3))
    r = eng.submit(p, max_new_tokens=5)
    eng.drain()
    assert r.stream.tokens(timeout=10) == _solo(model, params, p,
                                                max_new_tokens=5)
    # 11 prompt tokens at <=3/tick -> at least ceil(11/3)=4 chunk ticks
    assert eng.ticks >= 4 + 5


def test_chunk_straddles_paged_block_boundary():
    """A chunk whose writes cross a block_size boundary scatters into
    two (or three) physical blocks in one dispatch — parity must hold
    (chunk=12 vs block_size=8, prompt 20)."""
    model, params = _model_and_params(pos_emb="rope", d_model=64)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, size=20).astype(np.int32)
               for _ in range(2)]
    eng = _engine(model, params, paged=True, slots=2, block_size=8,
                  prefill_chunk=12)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain()
    for p, r in zip(prompts, reqs):
        assert r.stream.tokens(timeout=10) == _solo(model, params, p,
                                                    max_new_tokens=6)


def test_prompt_length_not_divisible_by_chunk():
    """Last chunk is short: 7-, 11-, 5-token prompts through chunk=4."""
    model, params = _model_and_params()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (7, 11, 5)]
    eng = _engine(model, params, slots=2, prefill_chunk=4)
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.drain()
    for p, r in zip(prompts, reqs):
        assert r.stream.tokens(timeout=10) == _solo(model, params, p,
                                                    max_new_tokens=4)


def test_decoding_rows_saturate_budget_prefill_starves_boundedly():
    """With tick_token_budget == number of decoding rows, a prefilling
    slot gets zero tokens per tick (decodes are reserved first) — but
    decodes keep emitting every tick, and the starved prefill resumes
    the moment a decode finishes. Starvation is bounded, not a
    livelock."""
    model, params = _model_and_params()
    rng = np.random.default_rng(5)
    pa, pb = (rng.integers(0, 64, size=2).astype(np.int32)
              for _ in range(2))
    pc = rng.integers(0, 64, size=10).astype(np.int32)
    eng = _engine(model, params, slots=3,
                  scheduler=FIFOScheduler(tick_token_budget=2))
    ra = eng.submit(pa, max_new_tokens=12)
    rb = eng.submit(pb, max_new_tokens=12)
    # drive until both a and b are decoding (prompts fed)
    for _ in range(6):
        eng.step()
    assert all(st is None or st.decoding for st in eng._slots)
    rc = eng.submit(pc, max_new_tokens=3)
    eng.step()  # admits c into the free slot
    sc = next(s for s, st in enumerate(eng._slots)
              if st is not None and st.req.rid == rc.rid)
    before = eng._slots[sc].pending.size
    assert before == 10
    emitted0 = eng.tokens_generated
    for _ in range(3):
        eng.step()
        # both decoding rows emitted every tick: decode never stalls
    assert eng.tokens_generated - emitted0 == 6
    # c made zero prefill progress while the budget was saturated
    st = eng._slots[sc]
    assert st is not None and not st.decoding
    assert st.pending.size == before
    eng.drain()
    assert ra.stream.tokens(timeout=10) == _solo(model, params, pa,
                                                 max_new_tokens=12)
    assert rb.stream.tokens(timeout=10) == _solo(model, params, pb,
                                                 max_new_tokens=12)
    assert rc.stream.tokens(timeout=10) == _solo(model, params, pc,
                                                 max_new_tokens=3)


@pytest.mark.parametrize("pipeline", [False, True])
def test_eos_during_prefill_tick_refills_same_step(pipeline):
    """A decoding row samples its eos on a tick where its neighbour is
    mid-prefill: the freed slot refills from the queue in the same
    step() call (the strictly alternating loop; a tick ahead, on the
    next step's admit), the replacement's chunks share the budget with
    the still-prefilling neighbour, and every stream stays
    parity-exact."""
    model, params = _model_and_params()
    rng = np.random.default_rng(6)
    pa = rng.integers(0, 64, size=4).astype(np.int32)
    # 6 chunk ticks (8 where the plan is two ticks ahead of the read)
    pb = rng.integers(0, 64, size=16 if pipeline else 12).astype(np.int32)
    pc = rng.integers(0, 64, size=5).astype(np.int32)
    probe = _solo(model, params, pa, max_new_tokens=10)
    eos = probe[2]
    want_a = _solo(model, params, pa, max_new_tokens=10, eos_id=eos)
    # a finishes within its first 3 tokens (tick 5 at the latest)...
    assert 1 <= len(want_a) <= 3
    eng = _engine(model, params, slots=2, prefill_chunk=2,
                  pipeline=pipeline)
    ra = eng.submit(pa, max_new_tokens=10, eos_id=eos)
    # ...while b's 12-token prompt needs 6 chunk ticks: a's eos lands
    # while b is still mid-prefill (a: 2 chunk ticks + <=3 decode)
    rb = eng.submit(pb, max_new_tokens=4)
    rc = eng.submit(pc, max_new_tokens=4)
    refill_tick = None
    while eng.step():
        if refill_tick is None and ra.done_t is not None:
            refill_tick = eng.ticks
            if pipeline:
                # the eos was read behind the next tick's dispatch: the
                # slot is free now and the next step's admit refills it
                assert rc.rid not in eng.slot_requests
                eng.step()
            assert rc.rid in eng.slot_requests  # same-step refill
            sb = next(s for s, st in enumerate(eng._slots)
                      if st is not None and st.req.rid == rb.rid)
            assert not eng._slots[sb].decoding  # b still mid-prefill
    assert refill_tick is not None
    assert ra.stream.tokens(timeout=10) == want_a
    assert rb.stream.tokens(timeout=10) == _solo(model, params, pb,
                                                 max_new_tokens=4)
    assert rc.stream.tokens(timeout=10) == _solo(model, params, pc,
                                                 max_new_tokens=4)


# -- scheduler: budget plan + deprecation shim -------------------------------


def test_plan_prefill_allocation():
    sched = FIFOScheduler(tick_token_budget=10,
                          registry=telemetry.MetricRegistry(),
                          tracer=telemetry.Tracer())
    # decodes reserved first; remainder dealt FIFO in chunk-sized bites
    assert sched.plan_prefill(4, [20, 20], chunk=4) == [4, 2]
    assert sched.plan_prefill(0, [3, 20], chunk=8) == [3, 7]
    # saturation: nothing left for prefill
    assert sched.plan_prefill(10, [5], chunk=4) == [0]
    assert sched.plan_prefill(12, [5], chunk=4) == [0]
    assert sched.plan_prefill(0, [], chunk=4) == []


def test_max_prefills_per_tick_shim_warns_and_maps():
    with pytest.warns(DeprecationWarning, match="max_prefills_per_tick"):
        sched = FIFOScheduler(max_prefills_per_tick=2,
                              registry=telemetry.MetricRegistry(),
                              tracer=telemetry.Tracer())
    assert sched.tick_token_budget == 2 * DEFAULT_PREFILL_CHUNK
    # the legacy cap still bounds admissions per pop
    assert sched.max_prefills_per_tick == 2
    # an explicit budget wins over the mapping
    with pytest.warns(DeprecationWarning):
        sched2 = FIFOScheduler(max_prefills_per_tick=2,
                               tick_token_budget=17,
                               registry=telemetry.MetricRegistry(),
                               tracer=telemetry.Tracer())
    assert sched2.tick_token_budget == 17
    # and an engine built on the shim still serves correctly
    model, params = _model_and_params()
    rng = np.random.default_rng(7)
    p = rng.integers(0, 64, size=6).astype(np.int32)
    eng = _engine(model, params, slots=1, scheduler=sched)
    r = eng.submit(p, max_new_tokens=4)
    eng.drain()
    assert r.stream.tokens(timeout=10) == _solo(model, params, p,
                                                max_new_tokens=4)


# -- telemetry: ITL histogram + decode-stall counter -------------------------


def test_itl_histogram_and_stall_counter():
    """Chunked engines record per-stream inter-token gaps in
    serving_itl_ms and never stall (counter 0); a monolithic engine
    prefilling while another slot decodes increments
    serving_decode_stalls_total. Both are scrapeable and in stats()."""
    from distkeras_tpu.telemetry.exposition import render_prometheus

    model, params = _model_and_params()
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 64, size=5).astype(np.int32)
               for _ in range(3)]
    eng = _engine(model, params, slots=2)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.drain()
    for r in reqs:
        r.stream.tokens(timeout=10)
    stats = eng.stats()
    assert stats["decode_stalls"] == 0
    assert stats["itl_ms"]["p50"] is not None
    assert stats["itl_ms"]["p99"] is not None
    hist = eng.registry.histogram("serving_itl_ms").value
    # 3 streams x 6 tokens -> 5 gaps each
    assert hist["count"] == 15
    text = render_prometheus(eng.registry)
    assert "serving_itl_ms" in text
    assert "serving_decode_stalls_total" in text

    # monolithic: the second admission prefills while slot 0 decodes
    mono = _engine(model, params, slots=2, prefill_chunk=None)
    m0 = mono.submit(prompts[0], max_new_tokens=6)
    mono.step()  # admit + first tick: slot 0 is now decoding
    m1 = mono.submit(prompts[1], max_new_tokens=6)
    mono.drain()
    for r in (m0, m1):
        r.stream.tokens(timeout=10)
    assert mono.stats()["decode_stalls"] >= 1


# -- bench drift guard -------------------------------------------------------


def test_serve_bench_interference_smoke():
    """The --long-prompt-interference --smoke bench must keep (a) stream
    parity with solo generate() in both modes and (b) chunked p99 ITL
    strictly below monolithic p99 ITL; run it exactly as run_all
    config9 does."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "benchmarks"))
    import serve_bench

    out = serve_bench.bench_long_prompt_interference(smoke=True)
    assert out["chunked_itl_ms_p99"] < out["monolithic_itl_ms_p99"]
    assert out["monolithic_decode_stalls"] > 0
    assert out["chunked_decode_stalls"] == 0
