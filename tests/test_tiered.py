"""Tiered KV cache: the host-RAM spill tier under the paged BlockPool.

The tier's contract is that it is INVISIBLE except for capacity: token
streams must be bit-identical with the tier on or off (a restored block
holds exactly the bytes the demoted block held), across plain, COW,
pipelined, speculative, and tensor-parallel serving; RESTORING rows may
not charge the token budget, starve decode, or over-commit blocks; and
the host pool itself must stay within its bound with pinned entries
protected. The seeded-replay fallback (a restore losing its host entry)
must degrade to recompute with — again — identical streams.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import (
    BlockPool,
    FIFOScheduler,
    HostBlockPool,
    RadixPrefixIndex,
    ServingEngine,
)

V = 64
BS = 8  # block size


@pytest.fixture(scope="module")
def model_and_params():
    model = get_model(
        "transformer_lm", vocab_size=V, d_model=32, num_heads=4,
        num_layers=2, max_len=64, dtype=jnp.float32, attention="dense",
    )
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _engine(model, params, *, host_blocks, num_blocks, slots=2,
            scheduler=None, **kw):
    return ServingEngine(
        model, params, slots=slots, paged=True, block_size=BS,
        num_blocks=num_blocks, host_blocks=host_blocks,
        prefill_chunk=BS, scheduler=scheduler,
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
        **kw,
    )


def _churn_trace(n_prefixes=3, reps=3, prefix_len=32, tail=3, seed=0):
    """Round-robin over n_prefixes shared prefixes: a device pool
    sized below the working set must evict (demote) each prefix before
    its revisit."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, V, size=prefix_len).astype(np.int32)
                for _ in range(n_prefixes)]
    out = []
    for _ in range(reps):
        for p in prefixes:
            t = rng.integers(0, V, size=tail).astype(np.int32)
            out.append(np.concatenate([p, t]))
    return prefixes, out


def _serve(eng, prompts, max_new=4, temperature=0.7, seed=11):
    streams = []
    for p in prompts:
        r = eng.submit(p, max_new_tokens=max_new,
                       temperature=temperature, seed=seed)
        eng.drain(timeout=300)
        streams.append(r.stream.tokens(timeout=60))
    return streams


# -- round-trip bit-identity ----------------------------------------------


def test_demote_restore_round_trip_bit_identity(model_and_params):
    """Streams with the tier on == streams with the tier off, on a
    trace that actually demotes AND restores (asserted non-vacuous)."""
    model, params = model_and_params
    _, trace = _churn_trace()
    eng_t = _engine(model, params, host_blocks=32, num_blocks=12)
    eng_d = _engine(model, params, host_blocks=None, num_blocks=12)
    toks_t = _serve(eng_t, trace)
    toks_d = _serve(eng_d, trace)
    s = eng_t.stats()
    assert s["block_demotions"] > 0 and s["block_restores"] > 0
    assert toks_t == toks_d
    # the tier is why the hit fraction survives the churn
    assert (s["prefix_hit_fraction"]
            > eng_d.stats()["prefix_hit_fraction"])
    # restore-wait histogram saw the waits
    assert s["restore_wait_ms"]["p50"] is not None


def test_pipelined_restore_parity(model_and_params):
    """pipeline=True overlaps restores with in-flight ticks; streams
    stay identical to the sync tier and the tier-less engine."""
    model, params = model_and_params
    _, trace = _churn_trace()
    eng_p = _engine(model, params, host_blocks=32, num_blocks=12,
                    pipeline=True)
    eng_d = _engine(model, params, host_blocks=None, num_blocks=12)
    toks_p = _serve(eng_p, trace)
    assert eng_p.stats()["block_restores"] > 0
    assert toks_p == _serve(eng_d, trace)


@pytest.mark.slow
def test_speculative_restore_parity(model_and_params):
    """The tier under speculative decoding (ngram drafter): spec+tier
    streams == spec-without-tier streams (sampled spec streams are
    distributionally exact vs non-spec, so spec is its own
    reference)."""
    model, params = model_and_params
    _, trace = _churn_trace()
    kw = dict(draft="ngram", spec_k=3)
    eng_t = _engine(model, params, host_blocks=32, num_blocks=12, **kw)
    eng_r = _engine(model, params, host_blocks=None, num_blocks=64, **kw)
    toks_t = _serve(eng_t, trace)
    assert eng_t.stats()["block_restores"] > 0
    assert toks_t == _serve(eng_r, trace)


def test_tp4_reshard_on_upload_parity(model_and_params):
    """Tensor parallel: blocks are gathered UNSHARDED at demotion and
    re-sharded onto the mesh at upload — tp=4 tier streams must equal
    tp=1 tier streams (themselves equal to the tier-less reference)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (forced host devices in CI)")
    model, params = model_and_params
    _, trace = _churn_trace(reps=2)
    eng4 = _engine(model, params, host_blocks=32, num_blocks=12,
                   mesh=make_mesh({"model": 4}))
    eng1 = _engine(model, params, host_blocks=32, num_blocks=12)
    eng_d = _engine(model, params, host_blocks=None, num_blocks=12)
    toks4 = _serve(eng4, trace)
    assert eng4.stats()["block_restores"] > 0
    toks1 = _serve(eng1, trace)
    assert toks4 == toks1 == _serve(eng_d, trace)


# -- COW on a restored block ----------------------------------------------


def test_cow_on_restored_block(model_and_params):
    """A prefix is demoted, restored by one request, then a second
    request diverges MID-BLOCK inside the restored span: the partial
    hit must come back as copy-on-write off the restored (again
    device-resident) block, with the stream identical to a tier-less
    engine's."""
    model, params = model_and_params
    prefixes, _ = _churn_trace(n_prefixes=3, prefix_len=32)
    P = prefixes[0]
    rng = np.random.default_rng(5)
    tail = rng.integers(0, V, size=3).astype(np.int32)
    # B shares 28 of P's 32 tokens — diverges 4 tokens into P's last
    # block — then continues with its own suffix
    div = np.concatenate([P[:28], (P[28:32] + 1) % V, tail])
    warm = [np.concatenate([p, tail]) for p in prefixes]
    probe = [np.concatenate([P, tail]),  # restores P's blocks
             div]                        # COWs off the restored block

    def run(host_blocks, num_blocks):
        eng = _engine(model, params, host_blocks=host_blocks,
                      num_blocks=num_blocks)
        toks = _serve(eng, warm + warm[1:] + probe)
        return eng, toks

    eng_t, toks_t = run(32, 12)
    s = eng_t.stats()
    assert s["block_demotions"] > 0 and s["block_restores"] > 0
    # the COW hit shows as a non-block-multiple hit count
    assert s["prefix_hit_tokens"] % BS != 0
    _, toks_d = run(None, 64)
    assert toks_t == toks_d


# -- seeded-replay fallback (restore racing eviction) ---------------------


def test_restore_fallback_recomputes_bit_identical(model_and_params):
    """A RESTORING row whose host entries vanish mid-restore (the
    restore-racing-eviction shape) falls back to seeded replay:
    the spans recompute through ordinary chunked prefill and the
    stream is still bit-identical to the tier-less engine's."""
    model, params = model_and_params
    prefixes, _ = _churn_trace(n_prefixes=3, prefix_len=32)
    rng = np.random.default_rng(6)
    tails = [rng.integers(0, V, size=3).astype(np.int32)
             for _ in range(6)]
    # p1/p2 churn twice after p0 so LRU demotion climbs p0's WHOLE
    # chain (bottom-up demotion takes one tree level per round)
    warm_p = [prefixes[0], prefixes[1], prefixes[2],
              prefixes[1], prefixes[2]]
    warm = [np.concatenate([p, t]) for p, t in zip(warm_p, tails)]
    probe = np.concatenate([prefixes[0], tails[5]])

    sched = FIFOScheduler(restore_budget=1)  # one block per tick
    eng = _engine(model, params, host_blocks=32, num_blocks=12,
                  scheduler=sched)
    toks = _serve(eng, warm)
    assert eng.stats()["block_demotions"] > 0
    req = eng.submit(probe, max_new_tokens=4, temperature=0.7, seed=11)
    eng.step()  # admits the row RESTORING; first restore issues
    st = next(s for s in eng._slots if s is not None)
    assert st.restoring, "probe should be admitted RESTORING"
    # the tier loses every remaining entry the row still waits on
    for h, _ in list(st.restoring):
        eng.host.discard(h)
    eng.drain(timeout=300)
    toks_probe = req.stream.tokens(timeout=60)

    eng_ref = _engine(model, params, host_blocks=None, num_blocks=64)
    ref = _serve(eng_ref, warm + [probe])
    assert toks + [toks_probe] == ref
    # accounting rewound: hits never exceed prompt tokens and the
    # drained pool is clean
    s = eng.stats()
    assert 0 <= s["prefix_hit_tokens"] <= s["prompt_tokens"]
    ps = eng.pool.stats()
    assert ps["live"] == 0 and ps["in_use"] == ps["cached"]


# -- RESTORING-row admission accounting under block pressure --------------


def test_restoring_row_charges_no_budget_and_never_overcommits(
        model_and_params):
    """While a row restores: (a) live decode streams keep emitting
    every tick (restores can't starve decode — the budget is never
    charged for a RESTORING row), (b) the pool never over-commits
    (admission's worst-case reservation covers restore destinations),
    and (c) the row emits nothing until its blocks are resident."""
    model, params = model_and_params
    prefixes, _ = _churn_trace(n_prefixes=3, prefix_len=32)
    rng = np.random.default_rng(7)
    tails = [rng.integers(0, V, size=3).astype(np.int32)
             for _ in range(5)]
    warm = [np.concatenate([p, t]) for p, t in zip(prefixes, tails)]
    sched = FIFOScheduler(restore_budget=1)
    eng = _engine(model, params, host_blocks=32, num_blocks=13,
                  scheduler=sched)
    _serve(eng, warm)
    assert eng.stats()["block_demotions"] > 0
    # a long decode occupies one slot...
    dec = eng.submit(warm[2][:9], max_new_tokens=20, temperature=0.7,
                     seed=3)
    for _ in range(3):
        eng.step()
    # ...while a demoted-prefix hit enters the other slot RESTORING
    # (restore_budget=1 -> it waits several ticks)
    res = eng.submit(np.concatenate([prefixes[0], tails[4]]),
                     max_new_tokens=4, temperature=0.7, seed=11)
    seen_restoring = 0
    decode_progress = 0
    for _ in range(40):
        before = eng.tokens_generated
        eng.step()
        st = [s for s in eng._slots if s is not None]
        restoring = [s for s in st if s.restoring is not None]
        if restoring:
            seen_restoring += 1
            # the RESTORING row has emitted nothing...
            assert restoring[0].req.first_token_t is None
            # ...while the decode row still makes progress this tick
            if eng.tokens_generated > before:
                decode_progress += 1
        # pool invariant: never more allocated than physically present
        ps = eng.pool.stats()
        assert ps["in_use"] + ps["free"] == ps["total"]
    assert seen_restoring > 0, "probe never observed RESTORING"
    assert decode_progress > 0, "decode starved during restores"
    eng.drain(timeout=300)
    assert dec.stream.tokens(timeout=60)
    assert len(res.stream.tokens(timeout=60)) == 4


# -- host-pool LRU bound --------------------------------------------------


def test_host_pool_lru_bound_and_pinning():
    reg = telemetry.MetricRegistry()
    pool = HostBlockPool(capacity=3, block_size=8, registry=reg)
    leaves = lambda v: [np.full((8, 2, 4), v, np.float32)]  # noqa: E731
    handles = []
    for i in range(3):
        h, ev = pool.put(leaves(i))
        assert h is not None and ev == []
        handles.append(h)
    assert pool.count() == 3
    # 4th entry LRU-evicts the oldest
    h4, ev = pool.put(leaves(3))
    assert ev == [handles[0]] and pool.count() == 3
    # touch refreshes recency: handles[1] survives the next eviction
    pool.touch(handles[1])
    _, ev = pool.put(leaves(4))
    assert ev == [handles[2]]
    # pinned entries are never LRU victims
    pool.pin(handles[1])
    _, ev = pool.put(leaves(5))
    assert handles[1] not in ev
    # a pool full of pinned entries refuses instead of growing
    for h in list(pool._entries):
        pool.pin(h)
    h_refused, ev = pool.put(leaves(6))
    assert h_refused is None
    assert pool.count() == 3
    # take pops + counts a restore; a second take misses
    got = pool.take(handles[1])
    assert got is not None and float(got[0][0, 0, 0]) == 1.0
    assert pool.take(handles[1]) is None
    assert reg.counter("serving_block_restores_total").value == 1
    # gauges track the decomposition
    assert reg.gauge("host_blocks_cached").value == pool.count()
    assert reg.gauge("host_bytes").value == pool.stats()["bytes"]


def test_host_pool_capacity_bound_under_engine_churn(model_and_params):
    """End-to-end: a tiny host tier stays within its bound while the
    engine churns far more prefixes through it."""
    model, params = model_and_params
    _, trace = _churn_trace(n_prefixes=4, reps=3)
    eng = _engine(model, params, host_blocks=6, num_blocks=12)
    toks = _serve(eng, trace)
    assert eng.host.count() <= 6
    assert eng.stats()["block_demotions"] > 0
    # dropped host entries are a capacity effect, not a correctness
    # one: streams still match the tier-less engine
    eng_d = _engine(model, params, host_blocks=None, num_blocks=12)
    assert toks == _serve(eng_d, trace)


# -- pool / index / scheduler units ---------------------------------------


def test_blockpool_evict_returns_handle_and_stats_decomposition():
    reg = telemetry.MetricRegistry()
    host = HostBlockPool(capacity=4, block_size=4, registry=reg)
    pool = BlockPool(8, 4, registry=reg, host_tier=host)
    blocks = pool.alloc(3)
    pool.incref(blocks)
    assert pool.decref([blocks[0]]) == [blocks[0]]
    # the bugfix: evict() returns the freed block id so demotion is
    # pinned to exactly the block released
    assert pool.evict(blocks[0]) == blocks[0]
    host.put([np.zeros((4, 2), np.float32)])
    s = pool.stats()
    assert s["total"] == 7 and s["live"] == 2 and s["cached"] == 0
    assert s["in_use"] == 2 and s["free"] == 5
    assert s["host"] == 1  # one coherent live/cached/host snapshot
    assert s["in_use"] + s["free"] == s["total"]


def test_prefix_residency_transitions():
    idx = RadixPrefixIndex(2)
    toks = [1, 2, 3, 4, 5, 6, 7]
    idx.insert(toks, [10, 11, 12])
    ref = np.zeros(64, np.int32)
    # bottom-up: only the deepest unreferenced node is a victim
    assert idx.peek_evictable(ref) == 12
    idx.demote(12, handle=100)
    assert idx.host_count() == 1 and not idx.contains_block(12)
    # the parent becomes demotable once its device child is gone
    assert idx.peek_evictable(ref) == 11
    idx.demote(11, handle=101)
    # match walks device chain then host chain
    m = idx.match(toks)
    assert m.blocks == [10] and m.host == [100 + 1, 100]
    assert m.hit_tokens == 6
    # insert STOPS at a host node: the duplicate device copy is not
    # registered (host copy stays authoritative)
    registered = idx.insert(toks, [20, 21, 22])
    assert registered == []
    # promote re-registers at the restore destination, top-down
    idx.promote(101, 30)
    m = idx.match(toks)
    assert m.blocks == [10, 30] and m.host == [100]
    idx.promote(100, 31)
    assert idx.host_count() == 0
    assert idx.match(toks).blocks == [10, 30, 31]
    # drop_host cascades through host subtrees
    idx.demote(31, handle=200)
    idx.demote(30, handle=201)
    dropped = idx.drop_host(201)
    assert sorted(dropped) == [200, 201]
    assert idx.host_count() == 0
    assert idx.match(toks).blocks == [10] and idx.match(toks).host == []


def test_prefix_cow_not_offered_from_host_frontier():
    idx = RadixPrefixIndex(4)
    idx.insert(range(8), [5, 6])
    ref = np.zeros(16, np.int32)
    idx.demote(6, handle=9)
    # divergence inside the HOST block: no COW (restoring a block to
    # copy part of it isn't worth the transfer), and the host chain
    # stops before it
    m = idx.match([0, 1, 2, 3, 4, 5, 99, 98, 97])
    assert m.blocks == [5] and m.host == [] and m.cow is None
    # full-chunk walk still traverses the host node
    m = idx.match(list(range(8)) + [42])
    assert m.blocks == [5] and m.host == [9]


def test_scheduler_restore_budget():
    s = FIFOScheduler(restore_budget=3)
    assert s.plan_restore(0) == 0
    assert s.plan_restore(2) == 2
    assert s.plan_restore(9) == 3
    with pytest.raises(ValueError):
        FIFOScheduler(restore_budget=0)


def test_engine_host_tier_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(model, params, host_blocks=4,
                      registry=telemetry.MetricRegistry(),
                      tracer=telemetry.Tracer())
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(model, params, host_blocks=4, num_blocks=12,
                prefix_cache=False)
    with pytest.raises(ValueError, match="chunked"):
        ServingEngine(model, params, paged=True, block_size=BS,
                      num_blocks=12, host_blocks=4, prefill_chunk=None,
                      registry=telemetry.MetricRegistry(),
                      tracer=telemetry.Tracer())


# -- telemetry / flight / report ------------------------------------------


def test_tier_telemetry_and_flight(model_and_params, tmp_path, capsys):
    from distkeras_tpu.telemetry.exposition import render_prometheus
    from distkeras_tpu.telemetry.report import report_flight

    model, params = model_and_params
    _, trace = _churn_trace()
    # restore_budget=1: a multi-block restore spans ticks, so the
    # RESTORING slot state is actually observable in snapshots — of the
    # strictly alternating loop: a tick ahead, a tick's snapshot is
    # taken behind the next tick's plan, which has issued the next
    # restore already
    eng = _engine(model, params, host_blocks=32, num_blocks=12,
                  scheduler=FIFOScheduler(restore_budget=1),
                  pipeline=False)
    _serve(eng, trace)
    s = eng.stats()
    assert s["block_demotions"] > 0 and s["block_restores"] > 0
    assert s["host_blocks_cached"] > 0 and s["host_bytes"] > 0
    text = render_prometheus(eng.registry)
    for fam in ("serving_block_demotions_total",
                "serving_block_restores_total",
                "serving_restore_wait_ms", "host_blocks_cached",
                "host_bytes"):
        assert fam in text, fam
    # flight snapshots carry per-tick swap counts, and the renderer
    # shows the tier line + RESTORING slot cells
    snaps = [r for r in eng.flight.snapshots() if r.get("kind") == "tick"]
    assert any(r.get("restored", 0) > 0 for r in snaps)
    assert any(r.get("demoted", 0) > 0 for r in snaps)
    assert any(
        (sl or {}).get("state") == "restore"
        for r in snaps for sl in (r.get("slots") or [])
    ), "no RESTORING slot ever snapshotted"
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path))
    report_flight(str(path))
    out = capsys.readouterr().out
    assert "host tier:" in out
    assert "demoted" in out


@pytest.mark.slow
def test_serve_bench_host_tier_smoke():
    """The self-asserting CI variant of the tier bench end-to-end:
    >=2x hit fraction on the 3x-capacity trace, bit-identical streams
    across tier/device-only/all-resident, zero steady-state recompiles,
    swap traffic recorded, restore waits hidden against the
    all-resident ITL (runs in the multichip CI job; the tier-1 job
    covers the engine-level equivalents above)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks"))
    import serve_bench

    out = serve_bench.bench_host_tier(smoke=True)
    assert out["parity"] is True
    assert out["steady_recompiles"] == {}
    assert out["restores"] > 0 and out["swap_in_bytes"] > 0


def test_router_spill_gate_counts_host_blocks():
    """The router's saturation gate treats host-cached capacity as one
    swap-in away: a replica with a tight device pool but a warm host
    tier is NOT spilled away from."""
    from distkeras_tpu.serving.fleet import Replica
    from distkeras_tpu.serving.router import Router

    r = Router.__new__(Router)
    r.spill_queue_depth = 8
    r.spill_min_free_blocks = 2
    rep = Replica.__new__(Replica)
    rep.last_stats = {"queue_depth": 0, "blocks_reclaimable": 1}
    assert r._saturated(rep)  # device-only: saturated
    rep.last_stats = {"queue_depth": 0, "blocks_reclaimable": 1,
                      "host_blocks_cached": 8}
    assert not r._saturated(rep)  # tiered: capacity is one swap away
