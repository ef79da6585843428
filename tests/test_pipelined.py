"""Pipelined async engine loop (ServingEngine(pipeline=True)): the
depth-2 software pipeline must be OBSERVABLY identical to the sync
reference loop — bit-identical token streams across slot/paged ×
chunked/monolithic × greedy/sampled × spec-ngram × tp=1/4, late-EOS
overruns dropped before streaming, expiry-during-flight, and no
double-admission against slots freed by unreconciled finishes — while
the flight recorder exposes the overlap telemetry (device_wait_ms,
pipeline_depth, overrun_tokens). Plus the FIFOScheduler head-of-line
short-circuit satellites and the serve_bench --pipeline --smoke drift
guard."""

import functools
import importlib
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.telemetry import report as telemetry_report
from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import generate
from distkeras_tpu.serving import FIFOScheduler, ServingEngine
from distkeras_tpu.serving.engine import (_pack_i32, _seed_slot,
                                          _unpack_i32)
from distkeras_tpu.telemetry.runtime import recompiles

KW = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2,
          max_len=64, dtype=jnp.float32, attention="dense",
          pos_emb="rope", num_kv_heads=2)


def _model_and_params(seed=0, **over):
    kw = dict(KW)
    kw.update(over)
    model = get_model("transformer_lm", **kw)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _workload(n=6, vocab=64, prompt_len=10):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(n)]
    lens = [7, 12, 5, 20, 9, 16][:n]
    temps = [0.0, 0.8, 0.0, 1.0, 0.0, 0.7][:n]
    return prompts, lens, temps


def _engine(model, params, paged, **kw):
    kw.setdefault("registry", telemetry.MetricRegistry())
    kw.setdefault("tracer", telemetry.Tracer())
    if paged:
        kw.setdefault("block_size", 8)
    return ServingEngine(model, params, paged=paged, **kw)


def _run(model, params, prompts, news, temps, eos=None, paged=False, **kw):
    """Serve the requests (the first one ends at ``eos`` where given);
    returns the streams, how each ended, and the engine."""
    eng = _engine(model, params, paged, **kw)
    reqs = [eng.submit(p, max_new_tokens=n, temperature=t, seed=i,
                       eos_id=eos if i == 0 else None)
            for i, (p, n, t) in enumerate(zip(prompts, news, temps))]
    eng.drain()
    return ([r.stream.tokens(timeout=60) for r in reqs],
            [r.stream.finish_reason for r in reqs], eng)


def _serve(model, params, paged, prompts, lens, temps, **kw):
    streams, _, eng = _run(model, params, prompts, lens, temps, paged=paged,
                           slots=3, **kw)
    return streams, eng


def _solo(model, params, prompts, lens, temps):
    return [
        np.asarray(generate(
            model, params, jnp.asarray(p)[None], m, temperature=t,
            seed=i))[0, len(p):].tolist()
        for i, (p, m, t) in enumerate(zip(prompts, lens, temps))
    ]


# -- async-vs-sync bit-parity matrix -----------------------------------------


@pytest.mark.parametrize("mode", ["slot", "paged"])
@pytest.mark.parametrize("prefill", ["chunked", "monolithic"])
def test_pipeline_parity_matrix(mode, prefill):
    """pipeline=True streams (greedy AND sampled RNG chains, mixed
    per-slot configs, every request finishing by length behind an
    unread tick) must be token-identical to the sync loop AND to solo
    generate()."""
    model, params = _model_and_params()
    prompts, lens, temps = _workload()
    kw = dict(prefill_chunk=4 if prefill == "chunked" else None)
    sync, _ = _serve(model, params, mode == "paged", prompts, lens,
                     temps, **kw)
    pipe, eng = _serve(model, params, mode == "paged", prompts, lens,
                       temps, pipeline=True, **kw)
    assert sync == _solo(model, params, prompts, lens, temps)
    assert pipe == sync
    st = eng.stats()
    assert st["pipeline"] is True
    if prefill == "chunked":
        # a length finish is host-known: the plan holds the row whose
        # budget the unread tick uses up, so nothing overruns
        assert st["overrun_tokens"] == 0 == st["overrun_pct"]
    else:
        # the plain decode tick has no control buffer to hold a row
        # back with: every request drops one overrun token
        # (but the last, which runs alone: nothing to run ahead with)
        assert st["overrun_tokens"] >= len(prompts) - 1
        assert st["overrun_pct"] == pytest.approx(
            100 * st["overrun_tokens"]
            / (st["tokens_generated"] + st["overrun_tokens"]))


@pytest.mark.parametrize("mode", ["slot", "paged"])
def test_pipeline_parity_spec_ngram(mode):
    """Speculative engines run the depth-1 pipeline (emission deferred
    past the next dispatch): streams must match the sync spec engine
    token for token, and greedy rows must still match solo
    generate()."""
    model, params = _model_and_params()
    prompts, lens, temps = _workload()
    kw = dict(prefill_chunk=4, draft="ngram", spec_k=3)
    sync, _ = _serve(model, params, mode == "paged", prompts, lens,
                     temps, **kw)
    pipe, _ = _serve(model, params, mode == "paged", prompts, lens,
                     temps, pipeline=True, **kw)
    assert pipe == sync
    solo = _solo(model, params, prompts, lens, temps)
    for i, t in enumerate(temps):
        if t == 0.0:  # sampled spec rows are distributionally exact,
            assert pipe[i] == solo[i]  # greedy rows bit-identical


@pytest.mark.parametrize(
    "mode",
    [pytest.param("slot"), pytest.param("paged", marks=pytest.mark.slow)],
)
def test_pipeline_parity_tp4(mode):
    """pipeline=True under a tp=4 mesh: the in-flight record holds
    sharded outputs; streams must still match the single-chip sync
    engine bit for bit."""
    from distkeras_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (forced host) devices")
    model, params = _model_and_params(num_heads=8, num_kv_heads=4)
    prompts, lens, temps = _workload(n=3)
    sync, _ = _serve(model, params, mode == "paged", prompts, lens,
                     temps, prefill_chunk=4)
    mesh = make_mesh({"model": 4})
    pipe, eng = _serve(model, params, mode == "paged", prompts, lens,
                       temps, prefill_chunk=4, pipeline=True, mesh=mesh)
    assert pipe == sync
    assert eng.stats()["tp"] == 4


# -- the loop under the newer programs ---------------------------------------

# tests/test_deepseek_v32.py's and tests/test_mimo_v2.py's sizes: every
# ratio of the published models kept, float32, weights from the plain
# references' seeds
DEEPSEEK = dict(
    vocab_size=96, d_model=64, num_layers=3, first_k_dense=1, num_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, index_n_heads=4, index_head_dim=16,
    index_topk=16, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    experts_held=4, expert_rank=0, rope_original_len=32, max_len=64,
    kv_tile=16, expert_tile=8)
RING = 16
MIMO = dict(
    vocab_size=97, d_model=64, num_layers=4, num_heads=8, head_dim=24,
    v_head_dim=16, num_kv_heads=2, swa_num_kv_heads=4, sliding_window=8,
    window_ring=RING, hybrid_layer_pattern=[0, 1, 1, 0],
    moe_layer_freq=[0, 1, 1, 1], intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
    experts_held=8, expert_rank=0, max_len=64, expert_tile=8)

# name -> (model, its size, engine arguments, routed (token, expert)
# pairs a live token makes over the model's expert layers)
NEWER = {
    # PR 32's packed mixed tick: a budget of 12 packs a [5, 8] tick's
    # live tokens to 16 rows
    "packed": ("transformer_lm", KW, dict(
        slots=5, prefill_chunk=8, scheduler={"tick_token_budget": 12}),
        None),
    "deepseek": ("deepseek_v32_lm", DEEPSEEK, dict(
        slots=3, max_len=64, prefill_chunk=8), 4 * 2),
    # rings beside full-length leaves, packed to 8 rows of a [3, 4] tick
    "mimo": ("mimo_v2_lm", MIMO, dict(
        slots=3, max_len=64, prefill_chunk=4,
        scheduler={"tick_token_budget": 6}), 4 * 3),
}


@functools.lru_cache(maxsize=None)
def _newer(name):
    if name == "transformer_lm":
        return _model_and_params()
    ref = importlib.import_module(
        "chipbench.references." + name.removesuffix("_lm"))
    size = {"deepseek_v32_lm": DEEPSEEK, "mimo_v2_lm": MIMO}[name]
    cfg = {"model": size, "precision": {"parameters": "float32"}}
    return (get_model(name, **size, dtype=jnp.float32),
            ref.make_params(cfg, 7))


def _fresh_token(stream, start=2):
    """``(index, token)`` of the first token from ``start`` on that the
    stream had not emitted before: an eos that ends it right there."""
    return next((i, t) for i, t in enumerate(stream)
                if i >= start and t not in stream[:i])


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("case", sorted(NEWER))
def test_pipeline_parity_newer_programs(case, sampling):
    """The loop that runs a tick ahead under the programs that came
    after it: the packed mixed tick, the latent model whose counters
    ride behind a tick's tokens, the model with rings beside full-length
    leaves. Token for token the alternating loop's streams, one request
    ending at its eos behind an unread tick; and each tick's record
    holds the counts of its own dispatch, read a period late."""
    name, size, kw, pairs = NEWER[case]
    model, params = _newer(name)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, size["vocab_size"], size=n).astype(np.int32)
               for n in (19, 7, 26, 11, 3)]
    news = [12, 9, 6, 14, 8]
    temps = ([0.0] * 5 if sampling == "greedy"
             else [0.9, 0.0, 0.7, 1.0, 0.8])
    free, _, _ = _run(model, params, prompts, news, temps, pipeline=False,
                      **kw)
    at, eos = _fresh_token(free[0])
    sync, sync_ends, es = _run(model, params, prompts, news, temps, eos,
                               pipeline=False, **kw)
    pipe, pipe_ends, ep = _run(model, params, prompts, news, temps, eos,
                               pipeline=True, **kw)
    assert sync[0] == free[0][:at + 1] and sync[1:] == free[1:]
    assert pipe == sync
    assert pipe_ends == sync_ends == ["eos"] + ["length"] * 4
    ss, sp = es.stats(), ep.stats()
    # the eos was read behind the next tick's dispatch: that tick's token
    # for the row is the one overrun; the four length finishes are held
    assert (ss["overrun_tokens"], sp["overrun_tokens"]) == (0, 1)
    assert sp["useful_query_tokens_total"] == (
        ss["useful_query_tokens_total"] + 1)
    if case != "deepseek":
        assert sp["packed_ticks_total"] > 0 and ss["packed_ticks_total"] > 0
    if pairs is not None:
        for st, eng in ((ss, es), (sp, ep)):
            assert st["routed_total_total"] == (
                pairs * st["useful_query_tokens_total"])
            assert st["expert_rows_computed_total"] >= st[
                "routed_here_total"] > 0
            # every record holds what ITS dispatch routed, whichever
            # period read it
            ticks = [t for t in eng.flight.snapshots()
                     if t["kind"] == "tick"]
            assert ticks and all(
                t["routed_total"] == pairs * (
                    t["decode_tokens"] + t["prefill_tokens"])
                for t in ticks)
        # a token is routed as it is whichever tick holds it: the totals
        # differ by the overrun token's pairs alone
        assert 0 <= (sp["routed_here_total"]
                     - ss["routed_here_total"]) <= pairs


def _cursors(eng):
    return [np.asarray(leaf) for leaf in jax.tree.leaves(eng._cache)
            if leaf.ndim == 1 and leaf.dtype == jnp.int32]


@pytest.mark.parametrize("edge", ["max_len", "ring_wrap"])
def test_a_tick_ahead_at_the_cache_edges(edge):
    """``mimo_v2_lm``, one slot, two tenants. ``max_len``: the first
    fills its full-length leaves to the last position and finishes by
    length: the plan holds the row, and no cursor passes ``max_len``.
    ``ring_wrap``: the first ends at an eos whose overrun token is fed
    at a multiple of the ring's size, so it lands on the ring's first
    entry, across the wrap. Both times the next tenant of the slot
    streams what the alternating loop streams."""
    model, params = _newer("mimo_v2_lm")
    kw = dict(slots=1, max_len=64, prefill_chunk=4)
    rng = np.random.default_rng(9)
    first = rng.integers(0, MIMO["vocab_size"], size=40).astype(np.int32)
    second = rng.integers(0, MIMO["vocab_size"], size=5).astype(np.int32)
    temps = [0.8, 0.0]
    eos = None
    if edge == "max_len":
        prompts, news = [first, second], [24, 9]
    else:
        free, _, _ = _run(model, params, [first[:5]], [40], temps[:1],
                          pipeline=False, **kw)
        # token i is fed at position 5 + i, the overrun token behind it
        # at 5 + i + 1: a multiple of the ring's 16
        at, eos = next((i, t) for i, t in enumerate(free[0])
                       if (5 + i + 1) % RING == 0
                       and t not in free[0][:i])
        prompts, news = [first[:5], second], [40, 9]
    sync, ends, _ = _run(model, params, prompts, news, temps, eos,
                         pipeline=False, **kw)
    eng = _engine(model, params, False, pipeline=True, **kw)
    reqs = [eng.submit(p, max_new_tokens=n, temperature=t, seed=i,
                       eos_id=eos if i == 0 else None)
            for i, (p, n, t) in enumerate(zip(prompts, news, temps))]
    tenant, high = None, 0
    while eng.step():
        tenant = tenant or eng._slots[0]
        high = max([high] + [int(c.max()) for c in _cursors(eng)])
    assert [r.stream.tokens(timeout=60) for r in reqs] == sync
    assert [r.stream.finish_reason for r in reqs] == ends
    assert tenant.req is reqs[0]
    if edge == "max_len":
        assert ends == ["length", "length"] and len(sync[0]) == 24
        assert tenant.cursor == 64 == high
        assert eng.stats()["overrun_tokens"] == 0
    else:
        assert ends == ["eos", "length"] and len(sync[0]) == at + 1
        # the last token the row was fed is the overrun one
        assert tenant.cursor % RING == 1 and tenant.cursor == 5 + at + 2
        assert eng.stats()["overrun_tokens"] == 1


def test_the_default_engine_runs_a_tick_ahead(tmp_path, capsys):
    """Built with no ``pipeline=``: the loop runs a tick ahead, compiles
    nothing after its first mixed and decode ticks, and reports the
    overrun share by its definition, in ``stats()`` and in ``report
    --flight``."""
    model, params = _model_and_params()
    prompts, _, _ = _workload(n=3)
    free = _solo(model, params, prompts[:1], [12], [0.0])[0]
    at, eos = _fresh_token(free)
    eng = ServingEngine(model, params, slots=2, prefill_chunk=4,
                        registry=telemetry.MetricRegistry(),
                        tracer=telemetry.Tracer())
    warm = eng.submit(prompts[2], max_new_tokens=3)
    eng.drain()  # a mixed tick and a decode tick have run
    assert len(warm.stream.tokens(timeout=60)) == 3
    eng.mark_steady()
    reqs = [eng.submit(prompts[0], max_new_tokens=12, eos_id=eos),
            eng.submit(prompts[1], max_new_tokens=7)]
    eng.drain()
    assert reqs[0].stream.tokens(timeout=60) == free[:at + 1]
    st = eng.stats()
    assert st["pipeline"] is True
    assert eng.recompiles_since_mark() == {} == st["recompiles_since_mark"]
    kinds = {(t["chunk"], t["prefill_tokens"] > 0)
             for t in eng.flight.snapshots() if t["kind"] == "tick"}
    assert kinds == {(4, True), (1, False)}
    assert max(t["pipeline_depth"] for t in eng.flight.snapshots()
               if t["kind"] == "tick") == 1
    assert st["overrun_tokens"] == 1
    assert st["tokens_generated"] == 3 + (at + 1) + 7
    assert st["overrun_pct"] == pytest.approx(
        100 * 1 / (st["tokens_generated"] + 1))
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    telemetry_report.main(["--flight", str(path)])
    sampled = st["tokens_generated"] + 1
    assert (f"overrun_pct: {100 / sampled:.2f} (1 of {sampled} sampled "
            f"tokens dropped)") in capsys.readouterr().out


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 3, -1])
@pytest.mark.parametrize("mode", ["slot", "paged"])
def test_an_admission_is_one_device_call(mode, seed):
    """The loop a tick ahead admits while the device runs: what an
    admission does on the device (park the slot's cursors, seed its RNG
    chain) is one enqueue behind the tick in flight, with slot and seed
    riding the call as host scalars; the key it writes is
    ``PRNGKey(seed)`` bit for bit, whatever the seed's size."""
    model, params = _model_and_params()
    eng = _engine(model, params, mode == "paged", slots=3, prefill_chunk=4)
    warm = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    eng.drain()
    assert len(warm.stream.tokens(timeout=60)) == 2
    # a tenant has left cursors behind in slot 0
    before = recompiles.counts()
    eng.submit(np.arange(1, 8, dtype=np.int32), max_new_tokens=3,
               temperature=0.9, seed=seed)
    eng._admit()
    # nothing new is traced: the one program the warm-up compiled
    assert recompiles.since(before) == {}
    want = {"slot": "serve.enter_slot", "paged": "serve.seed_slot"}[mode]
    assert recompiles.counts()[want] >= 1
    rngs = np.asarray(eng._rngs)
    assert (rngs[0] == np.asarray(jax.random.PRNGKey(seed))).all()
    if mode == "slot":
        assert all(int(c[0]) == 0 for c in _cursors(eng))
    # the same programs, called alone: other rows are left as they were
    base = jnp.arange(8, dtype=jnp.uint32).reshape(4, 2)
    out = np.asarray(_seed_slot(base + 0, np.int32(2), np.int64(seed)))
    assert (out[2] == np.asarray(jax.random.PRNGKey(seed))).all()
    assert (np.delete(out, 2, 0) == np.delete(np.asarray(base), 2, 0)).all()
    eng.drain()


# -- late-EOS on the pipeline boundary ---------------------------------------


def test_eos_on_pipeline_boundary():
    """A row that samples its eos while the next tick is already in
    flight: the finish must be reconciled late, the overrun token
    dropped before any consumer sees it, and the stream must equal the
    sync engine's (and solo generate's) eos-truncated stream."""
    model, params = _model_and_params()
    prompts, lens, temps = _workload(n=1)
    # find a token the greedy stream actually emits mid-stream and use
    # it as the eos id — guarantees an EOS finish on a pipeline
    # boundary rather than a length finish
    ref = _solo(model, params, prompts, [16], [0.0])[0]
    eos = ref[len(ref) // 2]

    def run(pipeline):
        eng = _engine(model, params, False, slots=2, prefill_chunk=4,
                      pipeline=pipeline)
        req = eng.submit(prompts[0], max_new_tokens=16, eos_id=eos)
        eng.drain()
        return req.stream.tokens(timeout=60), req, eng

    sync, rs, _ = run(False)
    pipe, rp, eng = run(True)
    want = ref[:ref.index(eos) + 1]
    assert sync == pipe == want
    assert rs.stream.finish_reason == rp.stream.finish_reason == "eos"
    assert eng.stats()["overrun_tokens"] >= 1


def test_eos_refill_from_queue_under_pipeline():
    """An EOS'd slot is cancelled and refilled from the queue on tick
    N+2; the replacement request's stream must be untouched by the
    overrun (fresh RNG chain, fresh cursors/blocks)."""
    model, params = _model_and_params()
    prompts, lens, temps = _workload(n=6)
    ref = _solo(model, params, prompts, [12] * 6, [0.0] * 6)
    eos = ref[0][3]  # request 0 eos-finishes early iff it emits this

    def run(pipeline, paged):
        eng = _engine(model, params, paged, slots=2, prefill_chunk=4,
                      pipeline=pipeline)
        reqs = [eng.submit(p, max_new_tokens=12,
                           eos_id=eos if i == 0 else None)
                for i, p in enumerate(prompts)]
        eng.drain()
        return [r.stream.tokens(timeout=60) for r in reqs]

    for paged in (False, True):
        assert run(True, paged) == run(False, paged)


# -- expiry during flight ----------------------------------------------------


def test_expiry_during_flight():
    """Requests whose deadline passes while ticks are in flight are
    expired by the scheduler (never admitted), with the usual stream
    sentinel — and the served streams keep bit-parity."""
    model, params = _model_and_params()
    prompts, lens, temps = _workload(n=4)
    eng = _engine(model, params, False, slots=1, prefill_chunk=4,
                  pipeline=True)
    keep = eng.submit(prompts[0], max_new_tokens=20)
    doomed = [eng.submit(p, max_new_tokens=4, deadline_s=0.0)
              for p in prompts[1:]]
    time.sleep(0.01)
    eng.drain()
    assert keep.stream.tokens(timeout=60) == _solo(
        model, params, prompts[:1], [20], [0.0])[0]
    for r in doomed:
        assert r.stream.tokens(timeout=60) == []
        assert r.stream.finish_reason == "expired"


# -- no double-admit against unreconciled finishes ---------------------------


def test_paged_pipeline_no_double_admit_under_block_pressure():
    """A paged pool sized so admission must wait for finishes: slots
    and blocks are only freed at reconciliation, so the optimistic
    plan-ahead must never admit against capacity a still-in-flight
    finish will free. Every stream must complete, bit-identical to the
    sync engine, with the pool fully drained."""
    model, params = _model_and_params()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, size=9).astype(np.int32)
               for _ in range(8)]

    def run(pipeline):
        eng = _engine(
            model, params, True, slots=2, prefill_chunk=4,
            pipeline=pipeline,
            # worst case per request: ceil((9 + 12) / 8) = 3 blocks;
            # 2 slots * 3 + trash + 1 spare — admission has to gate
            num_blocks=8, prefix_cache=False,
        )
        reqs = [eng.submit(p, max_new_tokens=12, seed=i)
                for i, p in enumerate(prompts)]
        eng.drain()
        streams = [r.stream.tokens(timeout=120) for r in reqs]
        return streams, eng

    sync, _ = run(False)
    pipe, eng = run(True)
    assert pipe == sync
    assert all(len(s) == 12 for s in pipe)
    assert eng.pool.in_use_count() == 0


# -- flight-recorder overlap telemetry ---------------------------------------


def test_flight_records_overlap_fields():
    """Pipelined snapshots carry the overlap decomposition — dispatch
    vs device-wait, the in-flight depth, per-tick overruns — and the
    device-wait percentile helper reads them. The blocking wait must
    not exceed the sync engine's (and must DROP when the runtime can
    actually overlap)."""
    model, params = _model_and_params()
    prompts, lens, temps = _workload()

    def run(pipeline):
        _, eng = _serve(model, params, False, prompts, [20] * 6,
                        [0.0] * 6, prefill_chunk=4, pipeline=pipeline)
        return eng

    es = run(False)
    ep = run(True)
    snaps = [s for s in ep.flight.snapshots() if s["kind"] == "tick"]
    assert snaps
    assert all("device_wait_ms" in s and "dispatch_ms" in s
               and "pipeline_depth" in s and "overrun_tokens" in s
               for s in snaps)
    assert max(s["pipeline_depth"] for s in snaps) >= 1
    # every finish here is by length, which the plan knows: none overruns
    assert sum(s["overrun_tokens"] for s in snaps) == 0
    p_sync = es.flight.percentile("device_wait_ms", 50)
    p_pipe = ep.flight.percentile("device_wait_ms", 50)
    assert p_sync is not None and p_pipe is not None
    # readback blocking must never grow vs sync (1 ms jitter floor);
    # where the sync loop is actually READBACK-BOUND (accelerator-style
    # whole-program d2h sync — the regime the pipeline exists for) it
    # must strictly drop. The XLA CPU thunk runtime materializes the
    # early token thunk immediately (wait ~0 in both arms), so there
    # the drop is vacuous and only the no-growth bound is meaningful.
    assert p_pipe <= p_sync + 1.0
    sync_dispatch = es.flight.percentile("dispatch_ms", 50)
    if p_sync > sync_dispatch:  # readback-bound runtime
        assert p_pipe < p_sync
    assert ep.stats()["device_wait_ms"]["p50"] is not None


# -- packed control-buffer transfer ------------------------------------------


def test_pack_unpack_roundtrip():
    """The single packed int32 transfer: pack order and the traced
    unpack views must agree for every tick's argument layout."""
    rng = np.random.default_rng(0)
    tables = rng.integers(0, 9, size=(3, 4)).astype(np.int32)
    lens = rng.integers(0, 5, size=(3,)).astype(np.int32)
    fed = rng.integers(0, 64, size=(3, 6)).astype(np.int32)
    valid = rng.integers(0, 6, size=(3,)).astype(np.int32)
    mask = np.array([1, 0, 1], np.int32)
    packed = _pack_i32(tables, lens, fed, valid, mask)
    assert packed.dtype == np.int32 and packed.ndim == 1
    out = _unpack_i32(jnp.asarray(packed),
                      ((3, 4), (3,), (3, 6), (3,), (3,)))
    for got, want in zip(out, (tables, lens, fed, valid, mask)):
        assert np.array_equal(np.asarray(got), want)


def test_upload_reuses_unchanged_plan():
    """An unchanged control plan must not re-upload: the steady
    all-decode slot state re-dispatches the previous device buffer
    (zero per-tick transfers)."""
    model, params = _model_and_params()
    eng = _engine(model, params, False, slots=2, prefill_chunk=4,
                  pipeline=True)
    a = eng._upload(np.arange(5, dtype=np.int32))
    b = eng._upload(np.arange(5, dtype=np.int32))
    assert b is a
    c = eng._upload(np.arange(6, dtype=np.int32))
    assert c is not a


# -- scheduler satellites ----------------------------------------------------


def _sched():
    return FIFOScheduler(registry=telemetry.MetricRegistry(),
                         tracer=telemetry.Tracer())


def _req(prompt=(1, 2), deadline_s=None):
    from distkeras_tpu.serving.scheduler import Request

    return Request(prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=4, deadline_s=deadline_s)


def test_head_blocked_short_circuit():
    """A head that fails the admissible gate twice running is
    short-circuited: the gate stops being re-evaluated until
    note_capacity_change()."""
    s = _sched()
    s.submit(_req())
    calls = [0]

    def gate(req):
        calls[0] += 1
        return False

    for _ in range(2):
        assert s.pop_admissible(1, admissible=gate) == ([], [])
    assert calls[0] == 2
    # third and fourth pops: the short-circuit answers, the gate is
    # never invoked
    for _ in range(2):
        assert s.pop_admissible(1, admissible=gate) == ([], [])
    assert calls[0] == 2
    assert s.head_blocked_skips == 2
    # capacity changed -> gate re-evaluated (and now admits)
    s.note_capacity_change()
    ok = [False]

    def gate2(req):
        calls[0] += 1
        return ok[0]

    s.pop_admissible(1, admissible=gate2)
    assert calls[0] == 3
    s.note_capacity_change()
    ok[0] = True
    admitted, _ = s.pop_admissible(1, admissible=gate2)
    assert len(admitted) == 1
    assert s.depth() == 0


def test_short_circuit_still_expires_head():
    """The short-circuit must never keep a deadline-passed head queued:
    expiry sweeps run before it."""
    s = _sched()
    s.submit(_req(deadline_s=0.01))
    always_no = lambda r: False  # noqa: E731
    s.pop_admissible(1, admissible=always_no)
    s.pop_admissible(1, admissible=always_no)  # streak armed
    time.sleep(0.02)
    admitted, expired = s.pop_admissible(1, admissible=always_no)
    assert admitted == [] and len(expired) == 1
    assert expired[0].stream.tokens(timeout=5) == []
    assert expired[0].stream.finish_reason == "expired"
    assert s.depth() == 0


def test_short_circuit_resets_on_new_head():
    """The streak is per-request: a new head after the blocked one is
    admitted gets a fresh gate evaluation."""
    s = _sched()
    a, b = _req(), _req()
    s.submit(a)
    s.submit(b)
    answers = {a.rid: False, b.rid: False}
    calls = [0]

    def gate(req):
        calls[0] += 1
        return answers[req.rid]

    s.pop_admissible(2, admissible=gate)
    s.pop_admissible(2, admissible=gate)
    assert calls[0] == 2
    s.note_capacity_change()
    answers[a.rid] = True
    admitted, _ = s.pop_admissible(1, admissible=gate)
    assert [r.rid for r in admitted] == [a.rid]
    # b is the new head: evaluated (not short-circuited) on next pop
    n = calls[0]
    s.pop_admissible(1, admissible=gate)
    assert calls[0] == n + 1


def test_oldest_age_incremental_head_tracking():
    """oldest_age_s reads the incrementally cached head timestamp —
    correct across submits, pops, and empty queues."""
    s = _sched()
    assert s.oldest_age_s() == 0.0
    a = s.submit(_req())
    time.sleep(0.01)
    assert s.oldest_age_s() >= 0.01
    s.submit(_req())
    admitted, _ = s.pop_admissible(1)
    assert admitted == [a]
    assert s.oldest_age_s() < 0.01  # the younger head
    s.pop_admissible(1)
    assert s.oldest_age_s() == 0.0


def test_engine_completion_invalidates_short_circuit():
    """End to end: a paged engine whose admission gate blocked the head
    re-evaluates it after a finish frees blocks (the engine calls
    note_capacity_change from _complete)."""
    model, params = _model_and_params()
    # two slots but blocks for ONE request (worst case 3 blocks each,
    # 4 usable): the queue head keeps failing the gate from the free
    # second slot while the first decodes — no capacity change between
    # those pops, so the short-circuit must engage (skips > 0) and a
    # completion must disarm it
    eng = _engine(model, params, True, slots=2, prefill_chunk=4,
                  num_blocks=5, prefix_cache=False, pipeline=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=9).astype(np.int32)
               for _ in range(3)]
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.drain()
    for r in reqs:
        assert len(r.stream.tokens(timeout=60)) == 8
    assert eng.scheduler.head_blocked_skips > 0


# -- serve_bench drift guard -------------------------------------------------


def test_serve_bench_pipeline_smoke():
    """The --pipeline bench's tiny self-asserting variant: parity
    across the matrix, zero steady-state recompiles, bounded flight
    overhead; the overlap speedup is recorded with the regime that
    produced it, never asserted on the CPU."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks"))
    import serve_bench

    r = serve_bench.bench_pipeline(smoke=True)
    assert r["parity"] is True
    assert r["pipe_steady_recompiles"] == {}
    assert r["sync_steady_recompiles"] == {}
    assert r["speedup"] > 0 and "overlap_capable" in r
