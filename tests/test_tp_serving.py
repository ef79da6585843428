"""Tensor-parallel serving: the mesh-parity suite.

The engine's jitted tick bodies run under ``shard_map`` on a 1-D
``model`` mesh (Q/KV heads column-sharded, out-proj row-sharded with one
psum per block, cache KV-head axis sharded) — and the whole point is
that NOTHING observable changes: token streams must be bit-identical to
the single-chip engine across every cache layout (slot + paged), head
layout (MHA + GQA), cache dtype (bf16-model + int8), and prefill mode
(chunked mixed ticks + monolithic), with zero steady-state recompiles.
Runs on the conftest's forced-host-device CPU mesh (the tier1.yml
multichip job forces 4); a core slice of the matrix is tier-1, the full
16 combos run under the dedicated CI job (``-m ''``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.serving import ServingEngine

TP = 4

KW = dict(vocab_size=64, d_model=32, num_heads=8, num_layers=2,
          max_len=24, dtype=jnp.float32, attention="dense",
          pos_emb="rope")


def _model_and_params(heads, cache_dtype):
    kw = dict(KW, cache_dtype=cache_dtype)
    if heads == "gqa":
        kw["num_kv_heads"] = 4
    model = get_model("transformer_lm", **kw)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _workload():
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (9, 5, 13)]
    cfgs = [
        dict(max_new_tokens=5),  # greedy
        dict(max_new_tokens=6, temperature=1.0, seed=3),
        dict(max_new_tokens=4, temperature=0.8, seed=7, top_k=8),
    ]
    return prompts, cfgs


def _run(model, params, mesh, mode, prefill, **kw):
    eng = ServingEngine(
        model, params, slots=2,
        paged=(mode == "paged"), block_size=8,
        prefill_chunk=4 if prefill == "chunked" else None,
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
        mesh=mesh, **kw,
    )
    prompts, cfgs = _workload()
    reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
    eng.drain()
    return [r.stream.tokens(timeout=30) for r in reqs], eng


# the full 16-combo matrix; a representative slice covering every
# dimension at least twice stays tier-1, the rest ride the dedicated
# multichip CI job (slow)
_CORE = {
    ("slot", "mha", "model", "chunked"),
    ("slot", "gqa", "int8", "monolithic"),
    ("paged", "gqa", "int8", "chunked"),
    ("paged", "mha", "model", "monolithic"),
}
_MATRIX = [
    pytest.param(m, h, d, p,
                 marks=() if (m, h, d, p) in _CORE
                 else pytest.mark.slow)
    for m in ("slot", "paged")
    for h in ("mha", "gqa")
    for d in ("model", "int8")
    for p in ("chunked", "monolithic")
]


@pytest.mark.parametrize("mode,heads,cache_dtype,prefill", _MATRIX)
def test_tp_streams_bit_identical(mode, heads, cache_dtype, prefill):
    """tp=4 mesh engine vs single-chip engine: token streams (greedy
    AND sampled chains) must match token for token."""
    model, params = _model_and_params(heads, cache_dtype)
    base, _ = _run(model, params, None, mode, prefill)
    mesh = make_mesh({"model": TP})
    got, eng = _run(model, params, mesh, mode, prefill)
    assert got == base
    assert eng.stats()["tp"] == TP


@pytest.mark.parametrize("heads", ["mha", "gqa"])
def test_tp_streams_match_with_the_cache_kernel_forced(heads):
    """The cursor-bounded attention kernel under the mesh (interpret
    mode; each shard walks its own KV heads, chunk ticks and decode
    ticks alike) against the single-chip dense attend: the same
    streams, and the same count of K/V positions fetched on the host."""
    model, params = _model_and_params(heads, "model")
    base, dense = _run(model, params, None, "slot", "chunked",
                       prefill_kernel="gather")
    got, eng = _run(model, params, make_mesh({"model": TP}), "slot",
                    "chunked", prefill_kernel="splash")
    assert got == base
    st, st_dense = eng.stats(), dense.stats()
    assert st["cache_positions_total"] == st_dense["cache_positions_total"]
    assert (0 < st["key_positions_fetched_total"]
            < st_dense["key_positions_fetched_total"]
            == st_dense["cache_positions_total"])


def test_tp_zero_steady_state_recompiles():
    """After one full warm pass through the sharded paged chunked
    engine (admission, COW-free prefix reuse, mixed ticks, completion,
    refill), repeating the identical workload must hit every jit cache
    — recompiles_since_mark() == {} is the same contract serve_bench
    asserts single-chip."""
    model, params = _model_and_params("gqa", "int8")
    mesh = make_mesh({"model": TP})
    eng = ServingEngine(
        model, params, slots=2, paged=True, block_size=8,
        prefill_chunk=4,
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
        mesh=mesh,
    )
    prompts, cfgs = _workload()

    def pass_once():
        reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
        eng.drain()
        return [r.stream.tokens(timeout=30) for r in reqs]

    first = pass_once()
    # second pass reaches the prefix-hit steady state: pass 1 inserted
    # the prompts into the radix index at finish, so pass 2's chunk
    # timing (fewer prefill ticks) differs from the cold pass and traces
    # one more slot-config combo — exactly like the single-chip engine
    second = pass_once()
    eng.mark_steady()
    third = pass_once()
    assert eng.recompiles_since_mark() == {}, (
        eng.recompiles_since_mark())
    # sampled requests re-seed per submit, and prefix hits must not
    # perturb a token: every pass streams identically
    assert second == first
    assert third == first


def test_tp_prefix_sharing_and_cow_under_mesh():
    """Radix prefix hits and mid-block COW (the jitted _copy_block on a
    sharded cache) keep streams identical to the single-chip paged
    engine."""
    model, params = _model_and_params("gqa", "model")
    rng = np.random.default_rng(1)
    system = rng.integers(0, 64, size=8).astype(np.int32)  # one block
    prompts = [
        np.concatenate([system, rng.integers(0, 64, size=4)]).astype(
            np.int32),
        np.concatenate([system, rng.integers(0, 64, size=3)]).astype(
            np.int32),                       # full-block hit
        np.concatenate([system[:6], rng.integers(0, 64, size=4)]).astype(
            np.int32),                       # COW mid-block
    ]
    cfgs = [dict(max_new_tokens=4)] * 3

    def run(mesh):
        eng = ServingEngine(
            model, params, slots=1, paged=True, block_size=8,
            prefill_chunk=4, registry=telemetry.MetricRegistry(),
            tracer=telemetry.Tracer(), mesh=mesh,
        )
        out = []
        for p, c in zip(prompts, cfgs):
            r = eng.submit(p, **c)
            eng.drain()
            out.append(r.stream.tokens(timeout=30))
        return out, eng

    base, _ = run(None)
    got, eng = run(make_mesh({"model": TP}))
    assert got == base
    assert eng.stats()["prefix_hit_tokens"] > 0


def test_tp_mesh_validation():
    model, params = _model_and_params("mha", "model")
    with pytest.raises(ValueError, match="no 'model' axis"):
        ServingEngine(model, params, mesh=make_mesh({"dp": 2}),
                      registry=telemetry.MetricRegistry(),
                      tracer=telemetry.Tracer())
    with pytest.raises(ValueError, match="must be 1-D"):
        ServingEngine(model, params,
                      mesh=make_mesh({"dp": 2, "model": 2}),
                      registry=telemetry.MetricRegistry(),
                      tracer=telemetry.Tracer())
    tp_model = get_model("transformer_lm", tp_size=2, **KW)
    with pytest.raises(ValueError, match="tp_size=1"):
        ServingEngine(tp_model, params, mesh=make_mesh({"model": 2}),
                      registry=telemetry.MetricRegistry(),
                      tracer=telemetry.Tracer())
