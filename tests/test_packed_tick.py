"""The packed mixed tick: where the scheduler's budget deals a ``[S, C]``
tick fewer tokens than it holds, the per-token layers run over the live
tokens packed to ONE compiled count ``N`` and only the attend sees ``[S,
C]``. Same tokens, logits, cache bytes and cursors as the full-width
program; one program for every live count; a model whose result would change (a
capacity sized from the tokens given) keeps the full-width program, and
a model that packs by blocks (``deepseek_v32_lm``, PR 38) is handed ``S
* C`` and finds the blocks in use itself, while the models of one count
keep theirs and their programs, text for text."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import generate
from distkeras_tpu.serving import FIFOScheduler, ServingEngine
from distkeras_tpu.serving import engine as engine_mod

KW = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2,
          max_len=64, dtype=jnp.float32, attention="dense")
S, C, BUDGET = 5, 8, 12
N = 16  # round_up(max(BUDGET, S), 8), of S * C = 40

LAYOUTS = {"slot": dict(), "paged": dict(paged=True, block_size=8)}
CASES = [(layout, cache) for layout in sorted(LAYOUTS)
         for cache in ("model", "int8")]


def _lm(cache_dtype="model", **over):
    model = get_model("transformer_lm", **{**KW, **over},
                      cache_dtype=cache_dtype)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, params


def _engine(lm, scheduler=None, slots=S, chunk=C, budget=BUDGET, **kw):
    model, params = lm
    return ServingEngine(
        model, params, slots=slots, prefill_chunk=chunk,
        scheduler=scheduler or {"tick_token_budget": budget},
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
        **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, KW["vocab_size"], size=n).astype(np.int32)
            for n in lengths]


def _ticks(eng):
    return [s for s in eng.flight.snapshots() if s["kind"] == "tick"]


def _solo(lm, prompt, new):
    model, params = lm
    return np.asarray(generate(model, params, prompt[None], new))[
        0, len(prompt):].tolist()


def test_the_count_is_derived():
    count = engine_mod._packed_count
    assert count(256, 16, 64) == 256      # the GPT serving cells
    assert count(2048, 32, 64) == 2048    # deepseek-v3.2-exp-serve: S * C
    assert count(BUDGET, S, C) == N       # a multiple of the sublane
    assert count(3, 16, 64) == 16         # every decoding row reserves one
    assert count(256, 4, 8) == 32         # tier-1's tiny engines: S * C


# -- (1) one tick of every kind of row, against the full-width program ------


def _valid_spy(eng):
    """The valid lens each mixed tick uploads (the control buffer ends
    with fed [S, C], valid [S], sample mask [S] in both layouts)."""
    seen, upload = [], eng._upload

    def spy(packed):
        seen.append(np.asarray(packed)[-2 * eng.slots:-eng.slots].copy())
        return upload(packed)

    eng._upload = spy
    return seen


def _state(eng):
    cache = [np.asarray(leaf) for leaf in jax.tree.leaves(eng._cache)]
    if eng.paged:
        # block 0 is the trash block: the full-width tick writes its
        # padding there, the packed tick zeros
        cache = [leaf[1:] if leaf.ndim > 1 else leaf for leaf in cache]
    return (cache, np.asarray(eng._last_logits), np.asarray(eng._rngs),
            eng._seq_lens.copy() if eng.paged else None)


@pytest.mark.parametrize("layout,cache", CASES)
def test_a_tick_of_every_kind_of_row_equals_the_full_width_tick(
        layout, cache, monkeypatch):
    lm = _lm(cache)
    packed = _engine(lm, **LAYOUTS[layout])
    full = _engine(lm, **LAYOUTS[layout])
    monkeypatch.setattr(full, "_live_count", lambda C, dealt: None)
    valids = _valid_spy(packed)
    # A is fed whole in tick 1 and decodes in tick 2, where the budget
    # of 12 leaves 11: B a full chunk, C the 3 left, D starved, and the
    # fifth slot idle
    first, *rest = _prompts([3, 20, 13, 9])
    reqs = {}
    for name, eng in (("packed", packed), ("full", full)):
        reqs[name] = [eng.submit(first, max_new_tokens=6, seed=0)]
        eng.step()
        reqs[name] += [eng.submit(p, max_new_tokens=4, seed=1 + i)
                       for i, p in enumerate(rest)]
    for tick in range(2, 40):
        alive = [eng.step() for eng in (packed, full)]
        (pc, pl, pr, pn), (fc, fl, fr, fn) = _state(packed), _state(full)
        for a, b in zip(pc, fc):
            np.testing.assert_array_equal(a, b)
        read = valids[-1] > 0  # rows whose logits this tick left
        np.testing.assert_array_equal(pl[read], fl[read])
        np.testing.assert_array_equal(pr, fr)
        if pn is not None:
            np.testing.assert_array_equal(pn, fn)
        if not any(alive):
            break
    assert valids[1].tolist() == [1, 8, 3, 0, 0]
    for a, b in zip(reqs["packed"], reqs["full"]):
        assert a.stream.tokens(timeout=10) == b.stream.tokens(timeout=10)
    chunked = [t for t in _ticks(packed) if t["chunk"] == C]
    assert chunked and all(t["query_positions"] == N
                           and t["attend_query_positions"] == S * C
                           for t in chunked)
    assert packed.stats()["packed_ticks_total"] == len(chunked)
    assert full.stats()["packed_ticks_total"] == 0
    assert all(t["query_positions"] == S * C for t in _ticks(full)
               if t["chunk"] == C)


# -- (2) whole streams against solo generate() --------------------------------


@pytest.mark.parametrize("layout,cache", CASES)
def test_streams_stay_identical_to_solo_generate(layout, cache):
    lm = _lm(cache)
    eng = _engine(lm, **LAYOUTS[layout])
    prompts = _prompts([10, 7, 23, 5, 9, 17, 30, 2], seed=1)
    new = [8, 5, 6, 9, 3, 7, 4, 10]
    reqs = [eng.submit(p, max_new_tokens=n, seed=i)
            for i, (p, n) in enumerate(zip(prompts, new))]
    eng.drain()
    for r, p, n in zip(reqs, prompts, new):
        assert r.stream.tokens(timeout=10) == _solo(lm, p, n)
    assert eng.stats()["packed_ticks_total"] > 0


def test_rope_and_grouped_heads_pack_too():
    lm = _lm(pos_emb="rope", num_kv_heads=2)
    eng = _engine(lm)
    prompts = _prompts([10, 21, 6, 13], seed=2)
    reqs = [eng.submit(p, max_new_tokens=6, seed=i)
            for i, p in enumerate(prompts)]
    eng.drain()
    for r, p in zip(reqs, prompts):
        assert r.stream.tokens(timeout=10) == _solo(lm, p, 6)
    assert eng.stats()["packed_ticks_total"] > 0


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_packing_is_orthogonal_to_the_tp_mesh(layout):
    """``shard_map`` splits heads and hidden, packing is over tokens:
    the sharded packed engine's streams are the one-chip streams."""
    from distkeras_tpu.parallel.mesh import make_mesh

    lm = _lm()
    mesh = make_mesh({"model": 2}, devices=jax.devices()[:2])
    eng = _engine(lm, mesh=mesh, **LAYOUTS[layout])
    assert eng.tp == 2
    prompts = _prompts([10, 21, 6, 13], seed=4)
    reqs = [eng.submit(p, max_new_tokens=5, seed=i)
            for i, p in enumerate(prompts)]
    eng.drain()
    for r, p in zip(reqs, prompts):
        assert r.stream.tokens(timeout=10) == _solo(lm, p, 5)
    assert eng.stats()["packed_ticks_total"] > 0


# -- (3) who keeps the full-width program ----------------------------------------

DSV32_KW = dict(
    vocab_size=64, d_model=64, num_layers=3, first_k_dense=1, num_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, index_n_heads=4, index_head_dim=16,
    index_topk=16, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    experts_held=4, expert_rank=0, rope_original_len=32, max_len=64,
    kv_tile=16, expert_tile=8, dtype=jnp.float32)


def _model(name):
    kw = {"moe_lm": dict(KW, moe_experts=4), "transformer_lm": KW,
          "deepseek_v32_lm": DSV32_KW, "solar_open2_lm": SOLAR_KW}[name]
    model = get_model(name, **kw)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, {"params": params["params"]}


def test_a_model_that_does_not_pack_keeps_its_program(monkeypatch):
    """Routed experts size their capacity from the tokens they are
    given: under a budget that would pack a dense model ``moe_lm`` is
    dispatched the builder's full-width program, the one call the
    parent made."""
    eng = _engine(_model("moe_lm"), slots=3, chunk=8, budget=6)
    assert engine_mod._packed_count(6, 3, 8) < 3 * 8
    built, builder = [], engine_mod._mixed_tick_fn

    def spy(*args):
        built.append(args)
        return builder(*args)

    monkeypatch.setattr(engine_mod, "_mixed_tick_fn", spy)
    for i, p in enumerate(_prompts([13, 7, 10])):
        eng.submit(p, max_new_tokens=3, seed=i)
    eng.drain()
    chunked = [t for t in _ticks(eng) if t["chunk"] == 8]
    assert chunked and all(t["query_positions"] == 3 * 8 for t in chunked)
    assert eng.stats()["packed_ticks_total"] == 0
    assert built and all(args[4] is None for args in built)
    # and the text lowered for it holds the [S, C] matmuls and logits
    layout, cfgs, chunk, ctx, _ = next(a for a in built if a[2] == 8)
    packed = eng._layout.pack(eng, (np.zeros((3, 8), np.int32),
                                    np.zeros(3, np.int32),
                                    np.zeros(3, np.int32)))
    text = builder(layout, cfgs, chunk, ctx).lower(
        eng._params_only, eng._cache, eng._last_logits, eng._rngs,
        jnp.asarray(packed)).as_text()
    assert "tensor<3x8x64xf32>" in text


SOLAR_KW = dict(
    vocab_size=97, d_model=64, num_layers=4, num_heads=8, head_dim=16,
    num_kv_heads=2, gqa_layers=[0, 3], kda_num_heads=4, kda_head_dim=16,
    kda_gate_rank=8, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=4, experts_held=8, expert_rank=0, max_len=64,
    expert_tile=8, dtype=jnp.float32)
# sha256 of the lowered text of the [3, 8] mixed tick of a tiny engine
# under a budget of 6 (packed to 8 rows) at commit 67f0b59, before
# deepseek_v32_lm came to pack by blocks; tests/test_mimo_v2.py pins
# mimo_v2_lm's and a second transformer_lm the same way. PR 43:
# solar_open2_lm's expert layers take the grouped matmul's new form (a
# gather, three ragged matmuls, a scatter-add off the chip), a
# deliberate change; its tick was 771076a6...63698a
PARENT_PROGRAMS = {
    "solar_open2_lm": "49e32ae5ba65ae8d15a5145ecfc7594c764d8079c5fc3a4573fa7"
                      "01987df548f",
    "transformer_lm": "978dca6c49254a5ea430d9024c0600098ebab1960cf4422ded6aa"
                      "7f9ebcd1ae7"}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_models_of_one_count_keep_it_and_their_programs(name):
    """The engine hands them the one compiled ``N`` as before, and the
    program built for it is text for text the parent's."""
    eng = _engine(_model(name), slots=3, chunk=8, budget=6)
    assert not hasattr(eng._layout.dm, "live_block_rows")
    assert [eng._live_count(8, dealt) for dealt in (1, 4, 8, 9)] == [
        8, 8, 8, None]
    assert eng._live_count(1, 3) is None
    packed = eng._layout.pack(eng, (np.zeros((3, 8), np.int32),
                                    np.zeros(3, np.int32),
                                    np.zeros(3, np.int32)))
    text = engine_mod._mixed_tick_fn(
        eng._layout, (engine_mod._IDLE_CFG,) * 3, 8, None, 8).lower(
            eng._params_only, eng._cache, eng._last_logits, eng._rngs,
            jnp.asarray(packed)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[name]


def test_a_model_that_packs_by_blocks_is_handed_the_whole_tick():
    """``deepseek_v32_lm`` declares ``live_block_rows``: whatever was
    dealt, a chunk tick's count is ``S * C`` (the
    module finds the blocks in use on the device), the ``[S, 1]`` tick
    stays full width, and the positions its per-token layers ran over
    are whole blocks: one block of 24 rows at this size."""
    eng = _engine(_model("deepseek_v32_lm"), slots=3, chunk=8, budget=6)
    assert eng._layout.dm.live_block_rows(3 * 8) == 24
    assert eng._layout.dm.live_block_rows(32 * 64) == 512
    assert [eng._live_count(8, dealt) for dealt in (1, 6, 24)] == [24] * 3
    assert eng._live_count(1, 3) is None
    for i, p in enumerate(_prompts([13, 7, 10])):
        eng.submit(p, max_new_tokens=3, seed=i)
    eng.drain()
    chunked = [t for t in _ticks(eng) if t["chunk"] == 8]
    assert chunked and all(
        t["query_positions"] == 24 and t["live_blocks"] == 1
        for t in chunked)
    # nothing was left out, so none counts as a packed tick
    assert eng.stats()["packed_ticks_total"] == 0


# -- (4) one program for every live count ----------------------------------------


def _warm_like_the_harness(eng, chunk):
    """chipbench/harness/serve_runner.py: one prompt of ``chunk + 6``
    alone (a whole chunk, then six tokens), then its decode."""
    (p,) = _prompts([chunk + 6], seed=9)
    eng.submit(p, max_new_tokens=4, seed=0)
    eng.drain()
    eng.mark_steady()


def test_every_live_count_runs_the_one_warmed_program(monkeypatch):
    lm = _lm()
    slots, chunk, budget = 4, 8, 16  # N = 16 of 32
    eng = _engine(lm, slots=slots, chunk=chunk, budget=budget)
    built, builder = set(), engine_mod._mixed_tick_fn
    builder.cache_clear()

    def spy(*args):
        built.add(args)
        return builder(*args)

    monkeypatch.setattr(engine_mod, "_mixed_tick_fn", spy)
    _warm_like_the_harness(eng, chunk)
    # the packed [S, C] program and the [S, 1] one: the two the
    # full-width engine had, and no third
    assert builder.cache_info().currsize == 2
    # every count from 1 to N dealt in one tick: a prompt of k alone,
    # then prompts that fill the budget two, three and four rows wide
    lone = [[k] for k in range(1, chunk + 1)]
    pairs = [[chunk, k] for k in range(1, chunk + 1)]
    for lengths in lone + pairs + [[5, 5, 5], [4, 4, 4, 4], [8, 8, 8, 8]]:
        prompts = _prompts(lengths, seed=sum(lengths))
        reqs = [eng.submit(p, max_new_tokens=2, seed=i)
                for i, p in enumerate(prompts)]
        eng.drain()
        for r, p in zip(reqs, prompts):
            assert r.stream.tokens(timeout=10) == _solo(lm, p, 2)
    chunked = [t for t in _ticks(eng) if t["chunk"] == chunk]
    dealt = {t["decode_tokens"] + t["prefill_tokens"] for t in chunked}
    assert dealt >= set(range(1, 17))
    assert all(t["query_positions"] == 16 for t in chunked)
    assert eng.recompiles_since_mark() == {}
    wide = {args for args in built if args[2] == chunk}
    assert len(wide) == 1 and next(iter(wide))[4] == 16
    assert builder(*next(iter(wide)))._cache_size() == 1
    assert builder.cache_info().currsize == 2


class _Overrunning(FIFOScheduler):
    """A scheduler of the user's that deals every row its chunk whatever
    the budget says."""

    def plan_prefill(self, n_decoding, pending_lens, chunk, tiers=None):
        return [min(chunk, int(n)) for n in pending_lens]


def test_a_plan_that_overruns_the_count_takes_the_full_width_program():
    lm = _lm()
    eng = _engine(lm, slots=4, chunk=8,
                  scheduler=_Overrunning(tick_token_budget=16))
    _warm_like_the_harness(eng, 8)
    assert eng.stats()["packed_ticks_total"] == 2
    prompts = _prompts([8, 8, 8, 8], seed=3)
    reqs = [eng.submit(p, max_new_tokens=3, seed=i)
            for i, p in enumerate(prompts)]
    eng.drain()
    for r, p in zip(reqs, prompts):
        assert r.stream.tokens(timeout=10) == _solo(lm, p, 3)
    over = [t for t in _ticks(eng) if t["prefill_tokens"] == 32]
    assert len(over) == 1
    assert over[0]["query_positions"] == over[0]["attend_query_positions"] \
        == 32
    # counted as what it is: not a packed tick, and a program of its own
    assert eng.stats()["packed_ticks_total"] == 2
    assert eng.recompiles_since_mark() == {"serve.mixed_tick": 1}


# -- (5) what an operator reads ---------------------------------------------------


def test_report_flight_names_the_packed_ticks(tmp_path, capsys):
    from distkeras_tpu.telemetry import report

    eng = _engine(_lm())
    for i, p in enumerate(_prompts([20, 13, 9])):
        eng.submit(p, max_new_tokens=3, seed=i)
    eng.drain()
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    n = eng.stats()["packed_ticks_total"]
    assert n > 0
    assert (f"packed ticks: {n}/{n} chunk ticks ran their per-token layers "
            f"over {N} of {S * C} query positions") in out
