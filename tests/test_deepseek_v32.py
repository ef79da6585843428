"""``deepseek_v32_lm`` (MLA latent slot cache, lightning-indexer top-k
selection, dropless group-limited routing over one chip's share of the
experts) against its plain reference, ``chipbench/references/
deepseek_v32.py``, on seeded random weights at a small size with every
ratio kept: 4 heads, 16 experts in 4 groups of which 4 are held, 4 per
token, ``index_topk`` 16 so that the selection bites within 64
positions.

Everything here is float32 on the CPU, so the two sides differ only by
the order of float32 sums (absorbed against expanded attention, tiles
against whole rows, grouped rows against every expert over every token):
``TOL`` = 2e-4 on logits of magnitude ~4 is a hundred times the 6e-6
measured, and a hundredth of what either control moves them by.
"""

import contextlib
import functools
import gc
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import deepseek_v32 as ref
from distkeras_tpu.models import get_model
from distkeras_tpu.models.deepseek_v32 import RoutedExperts
from distkeras_tpu.ops import mla
from distkeras_tpu.ops.moe import dropless_held_experts, group_limited_route
from distkeras_tpu.serving import LMServer, ServingClient, ServingEngine
from distkeras_tpu.telemetry import report as telemetry_report

TOL = 2e-4
SMALL = dict(
    vocab_size=96, d_model=64, num_layers=3, first_k_dense=1, num_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, index_n_heads=4, index_head_dim=16,
    index_topk=16, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    experts_held=4, expert_rank=0, rope_original_len=32, max_len=64,
    kv_tile=16, expert_tile=8)


def _config(**over):
    return {"model": dict(SMALL, **over),
            "precision": {"parameters": "float32"}}


@pytest.fixture(scope="module")
def small():
    cfg = _config()
    params = ref.make_params(cfg, 7)
    model = get_model("deepseek_v32_lm", **cfg["model"], dtype=jnp.float32)
    return cfg, params, model


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], size=n).astype(np.int32)


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("length", [9, 48, 64])
def test_full_forward_agrees_with_the_reference(small, length):
    """Shorter than ``index_topk`` (every position attended), and long
    enough that three quarters of the positions are left out."""
    cfg, params, model = small
    toks = _tokens(length)
    got = np.asarray(model.apply(params, toks[None])[0])
    want = ref.forward_logits(cfg, params, toks, np.arange(length))
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 1.0  # logits of a size worth comparing


@pytest.mark.parametrize("precision,moved", [("dense", 0.5), ("int8", 0.1)])
def test_a_control_falls_outside_the_tolerance(small, precision, moved):
    """The selection switched off, and every operand rounded to int8,
    each move the logits by thousands of tolerances."""
    cfg, params, _ = small
    toks = _tokens(48)
    at = np.arange(48)
    want = ref.forward_logits(cfg, params, toks, at)
    low = ref.forward_logits(cfg, params, toks, at, precision)
    assert np.abs(low - want).max() > moved > 100 * TOL


def test_padding_the_reference_changes_nothing(small):
    cfg, params, _ = small
    toks = _tokens(21)
    at = np.arange(5, 21)
    plain = ref.forward_logits(cfg, params, toks, at)
    padded = ref.forward_logits(cfg, params, toks, at, "f32", 64)
    assert np.abs(plain - padded).max() < 1e-5


def test_the_weights_follow_the_seed_and_the_models_layout(small):
    cfg, params, model = small
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))
    assert jax.tree.map(lambda a: a.shape, params["params"]) == jax.tree.map(
        lambda a: a.shape, init["params"])
    again, other = ref.make_params(cfg, 7), ref.make_params(cfg, 2 ** 31 + 5)
    moe = params["params"]["layers_1"]["moe"]
    assert np.array_equal(moe["router"], again["params"]["layers_1"]["moe"][
        "router"])
    assert not np.array_equal(moe["router"], other["params"]["layers_1"][
        "moe"]["router"])
    # small and not zero, so that a dropped correction bias shows
    assert 0 < np.abs(moe["e_score_correction_bias"]).max() < 0.2


# -- through the engine's slot cache -------------------------------------------


def _serve(model, params, prompts, news, **engine):
    """Drive the engine tick by tick; returns the requests and, for every
    (request, position) the engine held next-token logits for, those
    logits."""
    eng = ServingEngine(model, params, **engine)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
    seen = {}
    while eng.step():
        logits = np.asarray(eng._last_logits)
        for s, st in enumerate(eng._slots):
            if st is not None and st.decoding:
                seen[(st.req.rid, st.cursor - 1)] = logits[s]
    return eng, reqs, seen


def test_chunked_prefill_then_decode_agrees_with_the_reference(small):
    """Five requests through three slots in chunks of 8: rows at
    different cursors, slots refilled after another tenant, chunks that
    straddle ``index_topk`` = 16 and decode far past it. Every logits
    row the engine sampled from is the reference's full forward at that
    position."""
    cfg, params, model = small
    prompts = [_tokens(n, i) for i, n in enumerate((30, 11, 21, 5, 40))]
    news = [12, 20, 9, 30, 10]
    eng, reqs, seen = _serve(model, params, prompts, news, slots=3,
                             max_len=64, prefill_chunk=8)
    compared = 0
    for r, p, n in zip(reqs, prompts, news):
        toks = r.stream.tokens(timeout=10)
        assert len(toks) == n and r.stream.finish_reason == "length"
        seq = np.concatenate([p, np.asarray(toks, np.int32)])
        want = ref.forward_logits(cfg, params, seq, np.arange(len(seq)),
                                  "f32", 64)
        at = sorted(pos for rid, pos in seen if rid == r.rid)
        assert at[0] == len(p) - 1 and len(at) >= n
        for pos in at:
            assert np.abs(seen[(r.rid, pos)] - want[pos]).max() < TOL
            compared += 1
        # greedy: each served token is the reference's own
        assert toks == want[len(p) - 1:len(seq) - 1].argmax(-1).tolist()
    assert compared >= sum(news)
    assert eng.requests_completed == 5


def test_the_engine_counts_what_the_model_selects_and_routes(small,
                                                             tmp_path,
                                                             capsys,
                                                             monkeypatch):
    cfg, params, model = small
    prompts = [_tokens(n, i) for i, n in enumerate((30, 11, 21))]
    spans = []

    class Span(contextlib.nullcontext):
        def __init__(self, name, **args):
            super().__init__()
            spans.append((name, args))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    eng, reqs, _ = _serve(model, params, prompts, [6, 6, 6], slots=2,
                          max_len=64, prefill_chunk=8)
    st = eng.stats()
    live = st["useful_query_tokens_total"]
    # every prompt token and every sampled token is fed once (the last
    # sampled token too: the tick that samples it feeds it)
    assert live == sum(len(p) for p in prompts) + 3 * 6
    # two expert layers, four experts a token, a quarter of them here
    assert st["routed_total_total"] == live * 4 * 2
    assert 0 < st["routed_here_total"] < st["routed_total_total"]
    rows = st["expert_rows_computed_total"]
    assert rows >= st["routed_here_total"] and rows % SMALL["expert_tile"] == 0
    # the banks the ticks had to read: whole experts (three matrices of
    # d_model x moe_intermediate_size in float32 here), at least one and
    # at most the four held a layer a tick, the same sum on the ring
    bank = 3 * SMALL["d_model"] * SMALL["moe_intermediate_size"] * 4
    read = st["expert_weight_bytes_total"]
    assert read % bank == 0 and 0 < read <= eng.ticks * 2 * 4 * bank
    # a query at position t scores t + 1 positions and may attend
    # min(t + 1, 16) of them
    assert st["index_positions_scored_total"] == st["attended_tokens_total"]
    want = sum(min(t + 1, 16) for p in prompts
               for t in range(len(p) + 6))
    assert st["keys_selected_total"] == want
    assert st["key_positions_fetched_total"] < st["cache_positions_total"]
    ticks = [t for t in eng.flight.snapshots() if t.get("kind") == "tick"]
    assert all("routed_here" in t and "keys_selected" in t for t in ticks)
    assert sum(t["expert_weight_bytes"] for t in ticks) == read
    # and each tick's on its engine.record span, beside the device
    # clock's values: on a profile's clock, under the same window as
    # the seconds of the scope moe_experts
    recorded = {a["tick"]: a for name, a in spans if name == "engine.record"}
    assert {t["tick"]: t["expert_weight_bytes"] for t in ticks} == {
        n: a["expert_weight_bytes"] for n, a in recorded.items()}
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    telemetry_report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    assert "index_positions_scored:" in out and "keys_selected:" in out
    assert "routed_here/routed_total:" in out
    assert "expert_rows_computed:" in out
    assert f"expert_weight_bytes: {read}" in out


# -- the packed mixed tick: per-token layers over the blocks in use -------------

# 6 rows x 256 = three blocks of 512 packed rows
WIDE = dict(SMALL, max_len=512, kv_tile=64)
BLOCK_TICKS = {
    # valid lens of one [6, 256] tick -> the blocks its live tokens fill
    "one_block": ([40, 1, 1, 200, 1, 1], 1),
    "two_blocks": ([256, 256, 1, 0, 100, 7], 2),
    "all_blocks": ([256] * 6, 3),
    "a_chunk_beside_single_tokens": ([1, 1, 256, 1, 1, 1], 1),
    "an_idle_row": ([256, 0, 1, 256, 0, 30], 2),
}


@pytest.fixture(scope="module")
def wide():
    """The decode module at [6, 256] with every row's cursor somewhere
    else, and its two compiled mixed ticks: full width and packed."""
    cfg = _config(**WIDE)
    params = ref.make_params(cfg, 7)["params"]
    dm = get_model("deepseek_v32_lm", **WIDE, dtype=jnp.float32).clone(
        decode=True, slot_cursor=True, parent=None)
    S, C = 6, 256

    def tick(live):
        def fn(cache, tokens, valid):
            return dm.apply({"params": params, "cache": cache}, tokens,
                            valid_lens=valid, mutable=["cache", "counters"],
                            **({"live_tokens": live} if live else {}))
        return jax.jit(fn)

    full, packed = tick(None), tick(S * C)
    cache = dm.init(jax.random.PRNGKey(0),
                    jnp.zeros((S, 1), jnp.int32))["cache"]
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, SMALL["vocab_size"], (S, C)), jnp.int32)
    _, grown = full(cache, tokens, jnp.asarray([100, 3, 0, 50, 1, 200]))
    return dm, full, packed, grown["cache"], tokens


@pytest.mark.parametrize("case", sorted(BLOCK_TICKS))
def test_the_packed_tick_equals_the_full_width_tick(wide, case):
    """Logits at each row's last valid token, every cache leaf and
    cursor, and the experts' counters, whichever blocks hold a token."""
    dm, full, packed, cache, tokens = wide
    lens, blocks = BLOCK_TICKS[case]
    assert -(-sum(lens) // dm.live_block_rows(tokens.size)) == blocks
    valid = jnp.asarray(lens, jnp.int32)
    want, left = full(cache, tokens, valid)
    got, kept = packed(cache, tokens, valid)
    assert got.shape == (6, 1, SMALL["vocab_size"])
    fed = np.asarray(lens) > 0
    last = np.maximum(np.asarray(lens) - 1, 0)
    np.testing.assert_allclose(
        np.asarray(got)[fed, 0], np.asarray(want)[np.arange(6), last][fed],
        atol=1e-5)
    for a, b in zip(jax.tree.leaves(kept["cache"]),
                    jax.tree.leaves(left["cache"])):
        if a.dtype == jnp.int32:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, atol=1e-6)
    assert jax.tree.map(int, kept["counters"]) == jax.tree.map(
        int, left["counters"])


def test_live_tokens_is_the_whole_tick_on_a_decode_module(wide):
    dm, _, _, cache, tokens = wide
    params = ref.make_params(_config(**WIDE), 7)["params"]
    with pytest.raises(ValueError, match="is S \\* C"):
        dm.apply({"params": params, "cache": cache}, tokens,
                 valid_lens=jnp.ones((6,), jnp.int32), live_tokens=512,
                 mutable=["cache", "counters"])


@pytest.fixture(scope="module")
def served_by_blocks():
    """Five requests through four slots in chunks of 256 (a [4, 256]
    tick is two blocks of 512), by the packed program and, the same
    requests again, by the full-width one."""
    cfg = _config(**WIDE)
    params = ref.make_params(cfg, 7)
    model = get_model("deepseek_v32_lm", **WIDE, dtype=jnp.float32)
    prompts = [_tokens(n, i) for i, n in enumerate((300, 40, 270, 5, 130))]
    news = [6, 10, 5, 8, 7]
    engine = dict(slots=4, max_len=512, prefill_chunk=256,
                  scheduler={"tick_token_budget": 1024})
    packed = _serve(model, params, prompts, news, **engine)
    eng = ServingEngine(model, params, **engine)
    eng._live_count = lambda C, dealt: None
    for p, n in zip(prompts, news):
        eng.submit(p, n)
    eng.drain()
    return cfg, params, prompts, news, packed, eng


def test_chunked_prefill_by_blocks_agrees_with_the_reference(
        served_by_blocks):
    cfg, params, prompts, news, (eng, reqs, seen), _ = served_by_blocks
    assert eng.stats()["packed_ticks_total"] > 0
    for r, p, n in zip(reqs, prompts, news):
        toks = r.stream.tokens(timeout=10)
        assert len(toks) == n and r.stream.finish_reason == "length"
        seq = np.concatenate([p, np.asarray(toks, np.int32)])
        want = ref.forward_logits(cfg, params, seq, np.arange(len(seq)),
                                  "f32", 512)
        at = sorted(pos for rid, pos in seen if rid == r.rid)
        assert at[0] == len(p) - 1 and len(at) >= n
        for pos in at:
            assert np.abs(seen[(r.rid, pos)] - want[pos]).max() < TOL
        assert toks == want[len(p) - 1:len(seq) - 1].argmax(-1).tolist()


def test_a_tick_counts_the_blocks_it_ran(served_by_blocks, tmp_path,
                                         capsys):
    """``query_positions`` is 512 x ceil(dealt / 512) on a chunk tick,
    and what the model selects and routes is what the full-width
    program counts."""
    _, _, _, _, (eng, _, _), full = served_by_blocks
    ticks = [t for t in eng.flight.snapshots() if t.get("kind") == "tick"]
    chunked = [t for t in ticks if t["chunk"] == 256]
    assert chunked and {t["attend_query_positions"] for t in chunked} == {
        1024}
    for t in chunked:
        dealt = t["decode_tokens"] + t["prefill_tokens"]
        assert t["query_positions"] == 512 * -(-dealt // 512)
        assert t["live_blocks"] == -(-dealt // 512)
    assert {t["query_positions"] for t in chunked} == {512, 1024}
    assert all(t["query_positions"] == 4 and "live_blocks" not in t
               for t in ticks if t["chunk"] == 1)
    st, was = eng.stats(), full.stats()
    assert st["packed_ticks_total"] == sum(
        t["query_positions"] == 512 for t in chunked)
    assert was["packed_ticks_total"] == 0
    assert st["query_positions_total"] < was["query_positions_total"]
    for name in ("routed_here_total", "routed_total_total",
                 "expert_rows_computed_total", "expert_weight_bytes_total",
                 "keys_selected_total",
                 "index_positions_scored_total", "attended_tokens_total",
                 "useful_query_tokens_total"):
        assert st[name] == was[name], name
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    telemetry_report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    assert "packed ticks:" in out and "blocks in use a tick: p50 " in out


@pytest.mark.parametrize("option,what", [
    (dict(paged=True), "paged"),
    (dict(draft="ngram"), "draft"),
    (dict(multi_step_k=2), "multi_step"),
    (dict(prefill_chunk=None), "monolithic_prefill"),
    (dict(mesh="any"), "mesh"),
])
def test_the_engine_refuses_what_the_model_lacks(small, option, what):
    _, params, model = small
    with pytest.raises(ValueError, match=f"cannot be served with {what}"):
        ServingEngine(model, params, slots=2, max_len=64, **option)


def test_the_engine_refuses_a_draft_model_and_an_int8_cache(small):
    _, params, model = small
    with pytest.raises(ValueError, match="cannot be served with draft"):
        ServingEngine(model, params, slots=2, max_len=64, draft=model,
                      draft_params=params)
    with pytest.raises(ValueError, match="int8 or fp8"):
        ServingEngine(model.clone(cache_dtype="int8"), params, slots=2,
                      max_len=64)
    with pytest.raises(ValueError, match="multiple of kv_tile"):
        ServingEngine(model, params, slots=2, max_len=40)


def test_a_scheduler_given_as_a_mapping_deals_every_row_a_chunk(small):
    """``scheduler={"tick_token_budget": S x C}`` (how a configuration
    file says it): three prompts of 24 are fed in three ticks of three
    chunks of 8, where the default budget would do the same here and a
    budget of 10 takes eight."""
    _, params, model = small
    prompts = [_tokens(24, i) for i in range(3)]

    def ticks_to_decode(scheduler):
        eng = ServingEngine(model, params, slots=3, max_len=64,
                            prefill_chunk=8, scheduler=scheduler)
        for p in prompts:
            eng.submit(p, 30)  # long enough that no row leaves meanwhile
        n = 0
        while not all(st is not None and st.decoding for st in eng._slots):
            eng.step()
            n += 1
            assert n < 20
        return n, eng.scheduler.tick_token_budget

    assert ticks_to_decode({"tick_token_budget": 24}) == (3, 24)
    assert ticks_to_decode({"tick_token_budget": 10}) == (8, 10)
    assert ticks_to_decode(None) == (3, 256)


def test_a_stopped_server_frees_its_engine(small):
    """Requests cut in flight by ``stop()`` get their streams ended, so
    no pump thread keeps the server, the engine and its cache alive."""
    _, params, model = small
    eng = ServingEngine(model, params, slots=2, max_len=64, prefill_chunk=8)
    server = LMServer(eng).start()
    client = ServingClient("127.0.0.1", server.port, timeout=None)
    rids = [client.generate(_tokens(20, i), 40) for i in range(6)]
    assert len(rids) == 6
    time.sleep(0.2)
    alive = weakref.ref(eng)
    client.close()
    server.stop()
    del eng, server, client
    for _ in range(20):
        gc.collect()
        if alive() is None:
            break
        time.sleep(0.1)
    assert alive() is None
    for _ in range(50):  # the pumps see their streams end and return
        pumps = [t for t in threading.enumerate() if "_pump" in t.name]
        if not pumps:
            break
        time.sleep(0.1)
    assert not pumps


# -- the expert layer ------------------------------------------------------------


def _layer_inputs(seed=3, n=40):
    m = ref.sizes(_config(experts_held=16))
    p = ref.make_params(_config(experts_held=16, num_layers=2), seed)[
        "params"]["layers_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(seed), (1, n, SMALL["d_model"]))
    return m, p, u


def _module(held, rank):
    return RoutedExperts(
        n_routed_experts=16, experts_held=held, expert_rank=rank,
        num_experts_per_tok=4, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, width=32, dtype=jnp.float32,
        expert_tile=8)


def _share(p, held, rank):
    lo = rank * held
    return {**p, **{k: p[k][lo:lo + held]
                    for k in ("w_gate", "w_up", "w_down")}}


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their routed parts, with the
    shared expert (which every chip computes alike) counted once, are
    the reference's layer over all sixteen."""
    m, p, u = _layer_inputs()
    live = jnp.ones(u.shape[:2], bool)
    with jax.default_matmul_precision("highest"):
        uncut = ref._expert_layer(m, p, u[0], "f32")
        shared = ref._swiglu(p["shared"], u[0], "f32")
    parts = [_module(4, r).apply({"params": _share(p, 4, r)}, u, live)[0]
             for r in range(4)]
    total = shared + sum(part - shared for part in parts)
    assert np.abs(np.asarray(total - uncut)).max() < TOL
    # and a part alone is not the layer: the cut is not a no-op
    assert np.abs(np.asarray(parts[0] - uncut)).max() > 100 * TOL
    whole = _module(16, 0).apply({"params": p}, u, live)[0]
    assert np.abs(np.asarray(whole - uncut)).max() < TOL


def test_no_token_is_dropped_when_every_token_chooses_held_experts():
    """A correction bias that sends every token to experts 0-3, all held
    here: 40 rows an expert, five tiles of 8, where a capacity of 1.25 x
    the even share would keep 12."""
    m, p, u = _layer_inputs()
    p = dict(p, e_score_correction_bias=jnp.zeros((16,)).at[:4].set(10.0))
    live = jnp.ones(u.shape[:2], bool)
    out, sown = _module(4, 0).apply({"params": _share(p, 4, 0)}, u, live,
                                    mutable=["counters"])
    counts = {k: int(v) for k, v in sown["counters"].items()}
    assert counts["routed_here"] == counts["routed_total"] == 40 * 4
    assert counts["expert_rows_computed"] == 4 * 40
    with jax.default_matmul_precision("highest"):
        want = ref._expert_layer(dict(m, experts_held=4), _share(p, 4, 0),
                                 u[0], "f32")
    assert np.abs(np.asarray(out[0] - want)).max() < TOL


def test_padding_tokens_are_not_routed():
    m, p, u = _layer_inputs()
    live = jnp.arange(40)[None, :] < 7
    out, sown = _module(16, 0).apply({"params": p}, u, live,
                                     mutable=["counters"])
    assert int(sown["counters"]["routed_total"]) == 7 * 4
    assert int(sown["counters"]["routed_here"]) == 7 * 4
    with jax.default_matmul_precision("highest"):
        want = ref._expert_layer(m, p, u[0], "f32")
    assert np.abs(np.asarray(out[0, :7] - want[:7])).max() < TOL


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_routing_agrees_with_the_reference(seed):
    m = ref.sizes(_config())
    rng = np.random.default_rng(seed)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(50, 16)),
                                        jnp.float32))
    bias = jnp.asarray(0.05 * rng.normal(size=(16,)), jnp.float32)
    experts, gates = group_limited_route(scores, bias, 4, 2, 4, 2.5)
    want_e, want_g = ref.route(m, scores, bias)
    assert np.array_equal(np.sort(experts, -1), np.sort(want_e, -1))
    assert np.allclose(np.sort(gates, -1), np.sort(want_g, -1), atol=1e-6)
    assert np.allclose(gates.sum(-1), 2.5, atol=1e-5)
    # at most two of the four groups hold a chosen expert
    assert (np.asarray([len(set(row // 4)) for row in np.asarray(experts)])
            <= 2).all()


# name: N, k, experts in all, held, first, D, F, tile, dtype, share of
# the tokens live, experts the router ever chooses (None: any)
_GROUPED = {
    # the case the tile loop was held to before PR 43 (a token may name
    # one expert twice)
    "the_tile_loop_s_case": (19, 2, 6, 3, 2, 8, 6, 4, "float32", 0.8, None),
    "a_held_subset": (24, 3, 12, 4, 4, 16, 32, 8, "float32", 1.0, None),
    "the_whole_bank": (16, 4, 8, 8, 0, 16, 32, 8, "float32", 1.0, None),
    "experts_sent_no_row": (12, 2, 16, 8, 8, 16, 32, 8, "float32", 1.0,
                            (8, 11, 3)),
    "more_rows_than_a_tile": (40, 2, 4, 2, 1, 16, 32, 8, "float32", 1.0,
                              (1, 2)),
    "dead_rows": (20, 2, 6, 6, 0, 16, 32, 8, "float32", 0.3, None),
    "every_row_dead": (9, 2, 6, 6, 0, 16, 32, 8, "float32", 0.0, None),
    "pairs_not_whole_tiles": (19, 3, 8, 5, 2, 16, 32, 8, "float32", 0.9,
                              None),
    "a_tile_of_128": (70, 4, 4, 3, 1, 128, 256, 128, "float32", 0.9, None),
    "bfloat16": (24, 3, 12, 4, 4, 128, 256, 8, "bfloat16", 0.9, None),
}


@pytest.mark.parametrize("form", ["kernel", "xla"])
@pytest.mark.parametrize("case", sorted(_GROUPED))
def test_the_grouped_matmul_gathers_combines_and_counts(case, form,
                                                        monkeypatch):
    """Both forms of the grouped matmul (the Pallas launches in
    interpret mode; the plain XLA the models take off the chip) against
    the per-token loop, the four counters against counts made by
    hand."""
    from distkeras_tpu.ops import grouped_experts as ge, moe

    N, k, E, E_l, first, D, F, tile, dtype, live_share, chosen = _GROUPED[
        case]
    rng = np.random.default_rng(sum(map(ord, case)))
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32).astype(dt)
    experts = jnp.asarray(rng.choice(chosen or E, size=(N, k)), jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(N, k)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]), jnp.float32
                     ).astype(dt)
         for s in ((E_l, D, F), (E_l, D, F), (E_l, F, D))]
    live = jnp.asarray(rng.uniform(size=N) < live_share)
    if form == "kernel":
        monkeypatch.setattr(
            moe, "_grouped_xla", functools.partial(
                ge.grouped_experts, tile=tile, interpret=True))
    y, counts = dropless_held_experts(x, experts, gates, live, *w,
                                      first=first, tile=tile)
    want = np.zeros((N, D), np.float32)
    sent = np.zeros(E_l, int)
    f32 = [np.asarray(m, np.float32) for m in w]
    for t in range(N):
        for j in range(k):
            e = int(experts[t, j]) - first
            if live[t] and 0 <= e < E_l:
                xt = np.asarray(x[t], np.float32)
                h = jax.nn.silu(xt @ f32[0][e]) * (xt @ f32[1][e])
                h = np.asarray(jnp.asarray(h).astype(dt), np.float32)
                want[t] += float(gates[t, j]) * (h @ f32[2][e])
                sent[e] += 1
    tol = 1e-4 if dtype == "float32" else 2e-2
    assert y.dtype == jnp.float32 and y.shape == (N, D)
    assert np.abs(np.asarray(y) - want).max() < tol
    assert int(counts["routed_here"]) == sent.sum()
    assert int(counts["routed_total"]) == int(live.sum()) * k
    assert int(counts["expert_rows_computed"]) == sum(
        -(-n // tile) * tile for n in sent)
    assert int(counts["experts_read"]) == (sent > 0).sum()
    assert all(c.dtype == jnp.int32 for c in counts.values())
    if case == "experts_sent_no_row":
        assert (sent > 0).sum() == 2 < E_l
    if case == "more_rows_than_a_tile":
        assert sent.max() > tile
    if case == "every_row_dead":
        assert not np.asarray(y).any()


@pytest.mark.parametrize("sizes,tile", [
    ((3, 0, 9, 8, 0), 8), ((0, 0, 0), 8), ((130, 1), 128), ((5,), 4)])
def test_the_walk_of_the_sorted_pairs_visits_each_tile_of_each_run(
        sizes, tile):
    from distkeras_tpu.ops.grouped_experts import visits

    pairs = sum(sizes) + 5
    expert, base, rows, n = (np.asarray(a) for a in visits(
        jnp.asarray(sizes, jnp.int32), tile, pairs))
    assert len(expert) == -(-pairs // tile) + len(sizes)
    want, at = [], 0
    for e, size in enumerate(sizes):
        want += [(e, at + i, min(tile, size - i))
                 for i in range(0, size, tile)]
        at += size
    assert int(n) == len(want)
    assert list(zip(expert[:n], base[:n], rows[:n])) == want
    # a visit past the count has no row and names rows that exist
    assert not rows[n:].any() and (base <= pairs).all()
    assert ((0 <= expert) & (expert < len(sizes))).all()


def test_the_host_keeps_the_experts_read_as_bytes_past_an_int32():
    """Six applies of glm-4.7-flash's whole bank are 7.25 GB a tick:
    the device counts the experts that were sent a row, an int32 like
    the other counters behind the tick's tokens, and the host keeps the
    bytes of their matrices, a count made by hand here."""
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import engine

    model = get_model("glm4_moe_lite_lm", dtype=jnp.bfloat16)
    assert model.tick_counters[-1] == "experts_read"
    sown = {f"layers_{i}": {"moe": {
        "experts_read": jnp.int32(64), "routed_here": jnp.int32(512)}}
        for i in range(6)}
    words = engine._counter_sums(sown, model.tick_counters)
    assert words.dtype == jnp.int32 and words.shape == (4,)
    bank = 64 * 3 * 2048 * 1536 * 2
    assert engine._counter_work(model, np.asarray(words).tolist()) == {
        "routed_here": 6 * 512, "routed_total": 0, "expert_rows_computed": 0,
        "expert_weight_bytes": 6 * bank}
    assert 6 * bank > 2 ** 32


# -- the selection and the rope -----------------------------------------------


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_the_threshold_search_finds_the_kth_largest(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(6, 32)).astype(np.float32)
    scores[0, :4] = 0.0          # ties and zeros
    scores[1] = -np.abs(scores[1])  # all negative
    keys = mla.sortable_keys(jnp.asarray(scores))
    got = np.asarray(mla.kth_largest_key(keys, k))
    if k > 32:  # fewer than k keys: threshold 0, everything is in
        assert (got == 0).all()
        return
    want = np.sort(np.asarray(keys), axis=1)[:, -k]
    assert np.array_equal(got, want)
    picked = np.asarray(keys) >= got[:, None]
    kth = np.sort(scores, axis=1)[:, -k]
    assert np.array_equal(picked, scores >= kth[:, None])


def test_yarn_frequencies_agree_with_the_reference():
    m = ref.sizes({"model": {}})
    got = mla.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    want = ref.yarn_inv_freq(m)
    assert np.allclose(got, want, rtol=1e-6)
    # fast channels keep their frequency, slow ones are slowed 40 times
    assert np.isclose(got[0], 1.0) and np.isclose(
        got[-1], 10000.0 ** (-62 / 64) / 40)
    assert np.isclose(mla.yarn_softmax_scale(192, 40.0),
                      192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)


def test_the_walk_reads_whole_tiles_up_to_the_cursor_and_no_further():
    # rows at 0 (idle), 5 + 8 fed, 40 + 1 fed, 33 starved (feeds nothing)
    assert mla.fetched_positions([0, 5, 40, 33], [0, 8, 1, 0], 16) == (
        0 + 16 + 48 + 0)


# -- the latent entry in two leaves of whole lanes ----------------------------


def test_the_split_score_is_the_576_wide_score():
    """The walk over ``latent [S, L, 512]`` and ``rope_key [S, 64, L]``
    against one product over the 576-wide entries they were cut from:
    the same products, summed in float32 in another order. ``topk`` is
    the cache's length, so the selection lets every position through."""
    S, C, H, R, rope, L, Di, J = 3, 8, 4, 512, 64, 128, 16, 2
    rng = np.random.default_rng(40)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, entries = 0.1 * normal(S, C, H, R + rope), normal(S, L, R + rope)
    qi, ki, w = normal(S, C, J, Di), normal(S, L, Di), normal(S, C, J)
    starts = jnp.asarray([0, 37, L - C], jnp.int32)
    got = mla.sparse_latent_attention(
        q, qi, w, entries[..., :R], entries[..., R:].swapaxes(1, 2), ki,
        starts, None, topk=L, tile=32, scale=0.3)
    score = jnp.einsum("schd,std->scht", q, entries,
                       preferred_element_type=jnp.float32) * 0.3
    seen = (jnp.arange(L)[None, None, :]
            <= (starts[:, None] + jnp.arange(C)[None])[:, :, None])
    p = jax.nn.softmax(jnp.where(seen[:, :, None, :], score, -jnp.inf), -1)
    want = jnp.einsum("scht,stv->schv", p, entries[..., :R])
    assert got.shape == (S, C, H, R)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0.5


@pytest.mark.parametrize("L,T", [(64, 1), (64, 8), (512, 1), (512, 200)])
def test_the_positions_minor_write_is_the_scatter_at_the_cursor(L, T):
    """``mla.write_positions_minor`` (a window of whole lanes a row)
    against the scatter the other leaves are written by: a row at 0,
    one that feeds nothing, one mid-cache, one that ends at the cache's
    last position and one that would run past it (dropped there)."""
    D = 8
    rng = np.random.default_rng(L + T)
    starts = np.asarray([0, 17, L // 2 + 3, L - T, L - 1], np.int32)
    fed = np.asarray([T, 0, max(T - 1, 1), T, T], np.int32)
    S = len(starts)
    leaf = jnp.asarray(rng.normal(size=(S, L, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(S, T, D)), jnp.float32)
    at = np.where(np.arange(T)[None] < fed[:, None],
                  starts[:, None] + np.arange(T)[None], L)
    want = leaf.at[np.arange(S)[:, None], at].set(new, mode="drop")
    got = jax.jit(mla.write_positions_minor)(
        leaf.swapaxes(1, 2), new, jnp.asarray(starts), jnp.asarray(fed))
    np.testing.assert_array_equal(got.swapaxes(1, 2), want)
    assert not np.array_equal(want, leaf)
