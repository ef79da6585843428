"""Speculative decoding inside the continuous-batching mixed tick:
greedy bit-parity with solo generate() across slot/paged × MHA/GQA ×
int8 × tp=1/4 × draft model/ngram (core slice tier-1, full matrix on
the multichip CI job), rejection-sampling distributional correctness
(two-sample chi-square of token marginals vs the non-speculative engine
at T=1), eos-inside-accepted-prefix same-tick refill, verify-token
budget coexistence with chunked prefill, rollback block-accounting
under fragmentation pressure (BlockPool.stats() leaks nothing after 1k
speculative ticks straddling block boundaries), zero steady-state
recompiles, telemetry exposure, and constructor validation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import generate
from distkeras_tpu.serving import FIFOScheduler, ServingEngine

TP = 4

KW = dict(vocab_size=64, d_model=32, num_heads=8, num_layers=2,
          max_len=48, dtype=jnp.float32, attention="dense",
          pos_emb="rope")

DRAFT_KW = dict(vocab_size=64, d_model=32, num_heads=2, num_layers=1,
                max_len=48, dtype=jnp.float32, attention="dense")


def _model_and_params(heads="mha", cache_dtype="model", seed=0, **over):
    kw = dict(KW, cache_dtype=cache_dtype)
    if heads == "gqa":
        kw["num_kv_heads"] = 4
    kw.update(over)
    model = get_model("transformer_lm", **kw)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _draft_and_params(seed=7):
    draft = get_model("transformer_lm", **DRAFT_KW)
    dparams = draft.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 4), jnp.int32))
    return draft, dparams


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
    kw.setdefault("prefill_chunk", 4)
    if paged:
        kw.setdefault("block_size", 8)
    return ServingEngine(model, params, paged=paged, **kw)


def _spec_kw(draft_kind):
    if draft_kind == "ngram":
        return dict(draft="ngram")
    draft, dparams = _draft_and_params()
    return dict(draft=draft, draft_params=dparams)


# -- greedy bit-parity matrix ------------------------------------------------
#
# The full 32-combo matrix (slot/paged × MHA/GQA × model/int8 × tp 1/4 ×
# draft model/ngram); a slice covering every dimension at least twice
# stays tier-1, the rest ride the multichip CI job (slow).

_CORE = {
    ("slot", "mha", "model", 1, "ngram"),
    ("slot", "gqa", "int8", 1, "model"),
    ("paged", "gqa", "int8", 1, "ngram"),
    ("paged", "mha", "model", 1, "model"),
    ("paged", "gqa", "int8", TP, "ngram"),
    ("slot", "mha", "model", TP, "model"),
}
_MATRIX = [
    pytest.param(m, h, d, tp, dk,
                 marks=() if (m, h, d, tp, dk) in _CORE
                 else pytest.mark.slow)
    for m in ("slot", "paged")
    for h in ("mha", "gqa")
    for d in ("model", "int8")
    for tp in (1, TP)
    for dk in ("model", "ngram")
]


@pytest.mark.parametrize("mode,heads,cache_dtype,tp,draft_kind", _MATRIX)
def test_spec_greedy_parity_matrix(mode, heads, cache_dtype, tp,
                                   draft_kind):
    """Greedy streams through the speculative engine are token-identical
    to solo generate() — rejections (an independently-initialized
    random draft disagrees with the target constantly) and acceptances
    (the n-gram drafter on repetitive greedy streams) both preserve
    every bit, on both cache layouts, under the mesh, with sampled
    rows decoding in the neighbouring slots."""
    model, params = _model_and_params(heads, cache_dtype)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (9, 5, 13)]
    cfgs = [
        dict(max_new_tokens=10),  # greedy: the bit-parity claim
        dict(max_new_tokens=6, temperature=1.0, seed=3),
        dict(max_new_tokens=8),   # greedy again (refill path)
    ]
    mesh = None
    if tp > 1:
        from distkeras_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"model": tp})
    eng = _engine(model, params, paged=(mode == "paged"), slots=2,
                  mesh=mesh, spec_k=3, **_spec_kw(draft_kind))
    reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
    eng.drain()
    for p, c, r in zip(prompts, cfgs, reqs):
        if c.get("temperature", 0.0) == 0.0:
            assert r.stream.tokens(timeout=30) == _solo(
                model, params, p, **c)
        else:
            # sampled rows: full length, correctness is distributional
            # (test_spec_rejection_sampling_marginals)
            assert len(r.stream.tokens(timeout=30)) == c["max_new_tokens"]
    st = eng.stats()
    assert st["draft"] == draft_kind
    assert st["tp"] == tp if mesh else st["tp"] == 1


def test_spec_sampled_streams_identical_across_layouts():
    """At T>0 the speculative engine's streams are not bit-identical to
    solo generate() (different RNG consumption) — but they ARE
    bit-identical across cache layouts and meshes, because the accept
    draws and residual sampling ride the same replicated chain."""
    model, params = _model_and_params("gqa", "int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (7, 11)]
    cfgs = [dict(max_new_tokens=8, temperature=1.0, seed=5),
            dict(max_new_tokens=6, temperature=0.8, seed=9, top_k=8)]

    def run(paged):
        eng = _engine(model, params, paged=paged, slots=2,
                      draft="ngram", spec_k=3)
        reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
        eng.drain()
        return [r.stream.tokens(timeout=30) for r in reqs]

    assert run(False) == run(True)


# -- rejection-sampling distributional correctness ---------------------------


def _marginals(model, params, prompt, n, t, **spec_kw):
    eng = ServingEngine(
        model, params, slots=8, prefill_chunk=4,
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
        scheduler=FIFOScheduler(max_queue_depth=n + 1,
                                registry=telemetry.MetricRegistry(),
                                tracer=telemetry.Tracer()),
        **spec_kw,
    )
    reqs = [eng.submit(prompt, max_new_tokens=t, temperature=1.0,
                       seed=1000 + i) for i in range(n)]
    eng.drain()
    return np.array([r.stream.tokens(timeout=60) for r in reqs]), eng


def _chi2_two_sample(a, b, vocab):
    """Two-sample chi-square statistic over token counts (df <= V-1)."""
    c1 = np.bincount(a, minlength=vocab).astype(float)
    c2 = np.bincount(b, minlength=vocab).astype(float)
    tot = c1 + c2
    return float(np.sum(
        np.where(tot > 0, (c1 - c2) ** 2 / np.maximum(tot, 1.0), 0.0)))


def test_spec_rejection_sampling_marginals():
    """Per-position token marginals at T=1 through the speculative
    engine (one-hot n-gram q: the residual path fires constantly)
    match the non-speculative engine's — whose streams are themselves
    bit-identical to solo generate(). Fixed seeds: deterministic, not
    a flaky statistical test; the threshold is the chi-square 0.001
    critical value for df=15."""
    model = get_model("transformer_lm", vocab_size=16, d_model=16,
                      num_heads=2, num_layers=1, max_len=16,
                      dtype=jnp.float32, attention="dense")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    prompt = np.random.default_rng(0).integers(
        0, 16, size=4).astype(np.int32)
    n, t = 250, 3
    base, _ = _marginals(model, params, prompt, n, t)
    spec, eng = _marginals(model, params, prompt, n, t,
                           draft="ngram", spec_k=3)
    assert eng.stats()["draft_tokens"] > 0  # speculation actually ran
    for pos in range(t):
        stat = _chi2_two_sample(base[:, pos], spec[:, pos], 16)
        assert stat < 37.7, (pos, stat)  # chi2 crit at alpha=0.001, df 15


@pytest.mark.slow
def test_spec_rejection_sampling_marginals_model_draft():
    """Same marginal check against a random independent draft model —
    low acceptance, so the residual distribution norm(max(p - q, 0))
    with a full (non-one-hot) q dominates the emitted tokens."""
    model = get_model("transformer_lm", vocab_size=16, d_model=16,
                      num_heads=2, num_layers=1, max_len=16,
                      dtype=jnp.float32, attention="dense")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    dmodel = get_model("transformer_lm", vocab_size=16, d_model=16,
                       num_heads=2, num_layers=1, max_len=16,
                       dtype=jnp.float32, attention="dense")
    dparams = dmodel.init(jax.random.PRNGKey(5),
                          jnp.zeros((1, 4), jnp.int32))
    prompt = np.random.default_rng(0).integers(
        0, 16, size=4).astype(np.int32)
    n, t = 250, 3
    base, _ = _marginals(model, params, prompt, n, t)
    spec, _ = _marginals(model, params, prompt, n, t,
                         draft=dmodel, draft_params=dparams, spec_k=3)
    for pos in range(t):
        stat = _chi2_two_sample(base[:, pos], spec[:, pos], 16)
        assert stat < 37.7, (pos, stat)


# -- eos inside the accepted prefix ------------------------------------------


def test_eos_inside_accepted_prefix_same_tick_refill():
    """A draft prefix can carry the eos mid-window: the stream must
    truncate at eos (tokens accepted beyond it are discarded), the
    finish reason must be 'eos', and the freed slot must refill from
    the queue in the SAME step() call — the next tick already serves
    the replacement request."""
    model, params = _model_and_params()
    rng = np.random.default_rng(2)
    p0 = rng.integers(0, 64, size=6).astype(np.int32)
    p1 = rng.integers(0, 64, size=5).astype(np.int32)
    probe = _solo(model, params, p0, max_new_tokens=10)
    # an eos ends the stream where it FIRST stands, so it is taken from
    # the observed stream: the deepest token new to it there, behind
    # enough tokens that verify windows run first and one spans it
    depth = max(i for i, t in enumerate(probe) if t not in probe[:i])
    assert depth >= 2, probe
    eos = probe[depth]
    eng = _engine(model, params, slots=1, draft="ngram", spec_k=4)
    r0 = eng.submit(p0, max_new_tokens=10, eos_id=eos)
    r1 = eng.submit(p1, max_new_tokens=4)
    while eng.scheduler.depth() > 0 or r0.stream.finish_reason is None:
        before = eng.slot_requests
        if not eng.step():
            break
        # the step that finished r0 must have admitted r1 already
        if r0.stream.finish_reason is not None and before[0] == r0.rid:
            assert eng.slot_requests[0] == r1.rid
            break
    eng.drain()
    assert r0.stream.tokens(timeout=10) == probe[:depth + 1]
    assert r0.stream.finish_reason == "eos"
    assert r1.stream.tokens(timeout=10) == _solo(model, params, p1,
                                                 max_new_tokens=4)


# -- budget coexistence ------------------------------------------------------


def test_spec_and_chunked_prefill_share_budget():
    """Verify tokens charge the same tick_token_budget as prompt
    chunks: with a budget too small for full windows plus a chunk,
    decode still reserves first, prefill still progresses (bounded
    starvation), speculation shrinks — and every stream stays correct."""
    model, params = _model_and_params()
    rng = np.random.default_rng(3)
    short = rng.integers(0, 64, size=4).astype(np.int32)
    longp = rng.integers(0, 64, size=24).astype(np.int32)
    sched = FIFOScheduler(tick_token_budget=6,
                          registry=telemetry.MetricRegistry(),
                          tracer=telemetry.Tracer())
    eng = _engine(model, params, slots=2, scheduler=sched,
                  draft="ngram", spec_k=4)
    r0 = eng.submit(short, max_new_tokens=16)
    r1 = eng.submit(longp, max_new_tokens=4)
    eng.drain()
    assert r0.stream.tokens(timeout=10) == _solo(model, params, short,
                                                 max_new_tokens=16)
    assert r1.stream.tokens(timeout=10) == _solo(model, params, longp,
                                                 max_new_tokens=4)


def test_plan_spec_allocation():
    sched = FIFOScheduler(tick_token_budget=12,
                          registry=telemetry.MetricRegistry(),
                          tracer=telemetry.Tracer())
    # 2 decoding rows reserve 2; prefill wants 8 of the remaining 10
    # (chunk 8); 2 left widen the first window only
    takes, widths = sched.plan_spec(2, [20], 8, [4, 4])
    assert takes == [8]
    assert widths == [2, 0]
    # no prefill pressure: windows get the whole remainder
    takes, widths = sched.plan_spec(2, [], 8, [4, 4])
    assert takes == []
    assert widths == [4, 4]


# -- paged rollback / fragmentation pressure ---------------------------------


def test_block_pool_leaks_nothing_after_spec_ticks():
    """Fragmentation-pressure guard for rejected-draft rollback: 1k+
    speculative ticks whose verify windows straddle block boundaries
    (block_size 4 < spec_k+1) with constant rejections (random model
    draft) and completions/refills. Every block a rollback touches is
    row-private by construction (chains preallocated at admission,
    shared prefix blocks end before the write region), so
    BlockPool.stats() must come back to zero live blocks with nothing
    leaked once the engine drains."""
    model, params = _model_and_params()
    draft, dparams = _draft_and_params()
    rng = np.random.default_rng(4)
    eng = _engine(model, params, paged=True, slots=2, block_size=4,
                  draft=draft, draft_params=dparams, spec_k=6,
                  prefix_cache=False)
    done = 0
    for round_ in range(40):
        reqs = [eng.submit(rng.integers(0, 64, size=int(n)).astype(np.int32),
                           max_new_tokens=int(m))
                for n, m in zip(rng.integers(3, 14, size=4),
                                rng.integers(4, 20, size=4))]
        eng.drain()
        done += len(reqs)
        for r in reqs:
            r.stream.tokens(timeout=30)
    assert eng.ticks > 1000, eng.ticks
    st = eng.pool.stats()
    # prefix cache off: drained engine must return EVERY block
    assert st["live"] == 0 and st["in_use"] == 0, st
    assert st["free"] == st["total"], st
    assert np.all(eng.pool.ref == 0)


def test_block_accounting_with_prefix_cache_under_spec():
    """Same pressure with the radix prefix cache on: cached blocks may
    stay allocated (that is the cache), but no block may leak as
    unreachable — in_use always decomposes into live + cached, and
    live returns to 0 at drain."""
    model, params = _model_and_params()
    rng = np.random.default_rng(5)
    system = rng.integers(0, 64, size=8).astype(np.int32)
    eng = _engine(model, params, paged=True, slots=2, block_size=4,
                  draft="ngram", spec_k=6)
    for round_ in range(10):
        reqs = [eng.submit(
            np.concatenate([system,
                            rng.integers(0, 64, size=3).astype(np.int32)]),
            max_new_tokens=8) for _ in range(3)]
        eng.drain()
        for r in reqs:
            r.stream.tokens(timeout=30)
    st = eng.pool.stats()
    assert st["live"] == 0, st
    assert st["in_use"] == st["cached"], st
    assert eng.stats()["prefix_hit_tokens"] > 0


# -- recompiles, telemetry, validation ---------------------------------------


def test_spec_zero_steady_state_recompiles():
    """Acceptance-length variation must never retrigger compilation:
    after a warm pass (both speculative shapes traced), repeated
    workloads hit every jit cache."""
    model, params = _model_and_params()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (9, 5, 13)]
    cfgs = [dict(max_new_tokens=8),
            dict(max_new_tokens=6, temperature=1.0, seed=3),
            dict(max_new_tokens=5)]
    eng = _engine(model, params, paged=True, slots=2, draft="ngram",
                  spec_k=3)

    def one_pass():
        reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
        eng.drain()
        return [r.stream.tokens(timeout=30) for r in reqs]

    first = one_pass()
    second = one_pass()  # prefix-hit steady state (pass 1 inserted)
    eng.mark_steady()
    third = one_pass()
    assert eng.recompiles_since_mark() == {}, (
        eng.recompiles_since_mark())
    assert second == first and third == first


def _draftable_prompt(model, params, k, max_new):
    """A seeded prompt on whose greedy continuation the n-gram drafter
    is right at least once, found by probing: what a random tiny model
    emits decides whether its stream ever repeats itself. Until its
    first hit every verify window emits one token, so window ``i``
    proposes from the prompt plus the first ``i`` tokens."""
    from distkeras_tpu.serving.engine import _ngram_propose

    for seed in range(16):
        prompt = np.random.default_rng(seed).integers(
            0, 64, size=6).astype(np.int32)
        probe = _solo(model, params, prompt, max_new_tokens=max_new)
        for i in range(1, max_new - 1):
            toks, found = _ngram_propose(
                np.concatenate([prompt, probe[:i]]).astype(np.int32), k)
            if found and toks[0] == probe[i]:
                return prompt
    pytest.fail("no seeded prompt whose stream the n-gram drafter hits")


def test_spec_telemetry_exposed():
    from distkeras_tpu.telemetry.exposition import render_prometheus

    model, params = _model_and_params()
    registry = telemetry.MetricRegistry()
    eng = _engine(model, params, slots=2, registry=registry,
                  draft="ngram", spec_k=3)
    prompt = _draftable_prompt(model, params, k=3, max_new=12)
    r = eng.submit(prompt, max_new_tokens=12)
    eng.drain()
    r.stream.tokens(timeout=10)
    st = eng.stats()
    assert st["draft"] == "ngram" and st["spec_k"] == 3
    assert st["draft_tokens"] > 0
    assert 0.0 <= st["acceptance_rate"] <= 1.0
    assert st["accepted_tokens"] == round(
        st["acceptance_rate"] * st["draft_tokens"])
    exposition = render_prometheus(registry)
    assert "serving_draft_tokens_total" in exposition
    assert "serving_accepted_tokens_total" in exposition
    assert "serving_accept_len" in exposition
    # the flight ring records per-tick accepted/proposed counts
    snaps = eng.flight.snapshots()
    spec_snaps = [s for s in snaps if "draft_tokens" in s]
    assert spec_snaps, "no speculative tick reached the flight ring"
    assert any(s["accepted_tokens"] > 0 for s in spec_snaps)


def test_flight_report_renders_spec_ticks(tmp_path, capsys):
    from distkeras_tpu.telemetry.report import report_flight

    model, params = _model_and_params()
    eng = _engine(model, params, slots=1, draft="ngram", spec_k=3)
    prompt = np.random.default_rng(8).integers(
        0, 64, size=5).astype(np.int32)
    eng.submit(prompt, max_new_tokens=10)
    eng.drain()
    path = str(tmp_path / "flight.jsonl")
    eng.flight.dump(path)
    report_flight(path)
    out = capsys.readouterr().out
    assert "spec=" in out  # accepted/proposed column rendered


def test_spec_validation():
    model, params = _model_and_params()
    draft, dparams = _draft_and_params()
    with pytest.raises(ValueError, match="chunked prefill"):
        _engine(model, params, prefill_chunk=None, draft="ngram")
    with pytest.raises(ValueError, match="spec_k"):
        _engine(model, params, draft="ngram", spec_k=0)
    with pytest.raises(ValueError, match="Unknown draft"):
        _engine(model, params, draft="lookahead")
    with pytest.raises(ValueError, match="draft_params"):
        _engine(model, params, draft=draft)
    with pytest.raises(ValueError, match="no draft_params"):
        _engine(model, params, draft="ngram", draft_params=dparams)
    bad = get_model("transformer_lm", **{**DRAFT_KW, "vocab_size": 32})
    bad_params = bad.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="vocab_size"):
        _engine(model, params, draft=bad, draft_params=bad_params)


def test_draft_param_specs_shard_or_replicate():
    from jax.sharding import PartitionSpec as P

    from distkeras_tpu.parallel.spmd import draft_param_specs

    draft, dparams = _draft_and_params()
    # 2 heads on a tp=4 mesh: replicate
    specs, dtp = draft_param_specs(
        {"params": dparams["params"]}, num_heads=2, num_kv_heads=None,
        tp_size=4, tp_axis="model")
    assert dtp == 1
    assert all(s == P() for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))
    # 8 heads on a tp=4 mesh: shard like the flagship
    specs, dtp = draft_param_specs(
        {"params": dparams["params"]}, num_heads=8, num_kv_heads=4,
        tp_size=4, tp_axis="model")
    assert dtp == 4
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert any(s != P() for s in leaves)


def test_ngram_propose():
    from distkeras_tpu.serving.engine import _ngram_propose

    # repeat-token stream: matches at distance 1, proposes the repeat
    h = np.array([3, 9, 9, 9], np.int32)
    toks, found = _ngram_propose(h, 4)
    assert found == 4 and toks.tolist() == [9, 9, 9, 9]
    # periodic stream: proposes the continuation of the earlier cycle
    h = np.array([1, 2, 3, 1, 2], np.int32)
    toks, found = _ngram_propose(h, 3)
    assert found == 3 and toks.tolist() == [3, 1, 2]
    # no structure: no proposal
    toks, found = _ngram_propose(np.array([1, 2, 3, 4], np.int32), 3)
    assert found == 0


# -- bench drift guard -------------------------------------------------------


@pytest.mark.slow
def test_serve_bench_speculative_smoke():
    """The --speculative --smoke bench must keep greedy bit-parity
    spec-vs-baseline, >= 1.5x decode tok/s at the high-acceptance
    config, p50 ITL <= baseline, and zero steady-state recompiles; run
    it exactly as run_all config11 does. Slow: it overfits the smoke
    flagship (~7 s) and times two engines — the multichip CI job runs
    it; tier-1 covers the same invariants on the unit matrix above."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "benchmarks"))
    import serve_bench

    out = serve_bench.bench_speculative(smoke=True)
    assert out["parity"]
    assert out["decode_speedup"] >= 1.5
    assert out["acceptance_rate"] > 0.5
    assert out["spec_steady_recompiles"] == {}
