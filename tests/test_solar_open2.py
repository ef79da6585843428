"""``solar_open2_lm`` (gated delta-rule layers whose recurrent state lives
beside gated NoPE GQA layers' K/V in one slot cache, an expert layer in
every block with a shared expert) against its plain reference,
``chipbench/references/solar_open2.py``, on seeded random weights at a
small size with every ratio kept: GQA layers of 8 heads over 2 KV heads
of 16, KDA layers of 4 heads of 16 with a convolution of width 4 and
gate pairs of rank 8, 16 experts of which 8 are held, 4 a token, layers
``[gqa, kda, kda, gqa]`` so that both boundaries occur.

Everything here is float32 on the CPU, so the two sides differ only by
the order of float32 sums (the chunk form against the token-by-token
recurrence, tiles against whole rows, grouped rows against every expert
over every token): ``TOL`` = 2e-4 on logits of spread 1 is twenty times
the 1e-5 measured, and a thousandth of what either control moves them
by.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import solar_open2 as ref
from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.models.blocks import RoutedExperts
from distkeras_tpu.ops import delta_rule
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.telemetry import report as telemetry_report

TOL = 2e-4
CHUNK = 4
SMALL = dict(
    vocab_size=97, d_model=64, num_layers=4, num_heads=8, head_dim=16,
    num_kv_heads=2, gqa_layers=[0, 3], kda_num_heads=4, kda_head_dim=16,
    kda_gate_rank=8, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=4, experts_held=8, expert_rank=0, max_len=64,
    expert_tile=8)


def _config(**over):
    return {"model": dict(SMALL, **over),
            "precision": {"parameters": "float32"}}


@pytest.fixture(scope="module")
def small():
    cfg = _config()
    params = ref.make_params(cfg, 7)
    model = get_model("solar_open2_lm", **cfg["model"], dtype=jnp.float32)
    return cfg, params, model


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], size=n).astype(np.int32)


def _engine(model, params, **kw):
    return ServingEngine(model, params, registry=telemetry.MetricRegistry(),
                         tracer=telemetry.Tracer(), **kw)


# -- (a) the model's full forward and the controls ----------------------------


@pytest.fixture(scope="module")
def forty(small):
    cfg, params, _ = small
    toks = _tokens(40, 40)
    return toks, ref.forward_logits(cfg, params, toks, np.arange(40))


def test_full_forward_agrees_with_the_reference(small, forty):
    """40 tokens: the program runs its chunk form (one chunk, padded
    to 64), the reference the recurrence."""
    _, params, model = small
    toks, want = forty
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.clone(parent=None).apply(
            params, jnp.asarray(toks)[None])[0])
    assert np.abs(got - want).max() < TOL
    assert 0.5 < want.std() < 2.0  # logits that choose


@pytest.mark.parametrize("precision,moved", [("int8", 0.25),
                                             ("no_decay", 0.25)])
def test_a_control_falls_outside_the_tolerance(small, forty, precision,
                                               moved):
    """The int8 control and the KDA layers without their decay move the
    logits a thousand times ``TOL``."""
    cfg, params, _ = small
    toks, want = forty
    low = ref.forward_logits(cfg, params, toks, np.arange(40), precision)
    assert np.abs(low - want).max() > moved > 1000 * TOL


def test_the_weights_follow_the_seed_and_the_models_layout(small):
    cfg, params, model = small
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))
    assert jax.tree.map(lambda a: a.shape, params["params"]) == jax.tree.map(
        lambda a: a.shape, init["params"])
    p = params["params"]
    assert "attn" in p["layers_0"] and "kda" in p["layers_1"]
    assert "kda" in p["layers_2"] and "attn" in p["layers_3"]
    assert "shared" in p["layers_1"]["moe"]
    # exp(A_log) in [1, 16], softplus(dt_bias) in [1e-3, 1e-1]
    rate = np.exp(np.asarray(p["layers_1"]["kda"]["A_log"]))
    step = np.log1p(np.exp(np.asarray(p["layers_1"]["kda"]["dt_bias"])))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert 0.99e-3 < step.min() and step.max() < 1.01e-1
    other = ref.make_params(cfg, 2 ** 31 + 5)
    assert not np.array_equal(p["head"], other["params"]["head"])


def test_the_published_pattern_is_three_kda_layers_to_one_gqa():
    kinds = get_model("solar_open2_lm").layer_kinds()
    assert len(kinds) == 48 and kinds.count("full") == 12
    assert [i for i, k in enumerate(kinds) if k == "full"] == list(
        range(0, 48, 4))
    assert ref.sizes({"model": {}})["gqa_layers"] == tuple(range(0, 48, 4))


# -- (b) the delta rule against the token-by-token recurrence ------------------


def _recurrence(S, q, k, v, g, beta):
    """numpy float64, one token at a time: ``q``.. ``[T, H, d]``."""
    S = S.astype(np.float64)
    outs = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, :, None] * S
        kS = np.einsum("hk,hkv->hv", k[t], S)
        S = S + (beta[t][:, None] * k[t])[:, :, None] * (v[t] - kS)[:, None]
        outs.append(np.einsum("hk,hkv->hv", q[t], S) / math.sqrt(q.shape[-1]))
    return np.stack(outs) if outs else np.zeros((0,) + v.shape[1:]), S


def _delta_inputs(B, C, H, d, seed=0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q, k = (unit(rng.standard_normal((B, C, H, d))) for _ in range(2))
    v = rng.standard_normal((B, C, H, d))
    g = -np.exp(rng.uniform(-5, 1.5, (B, C, H, d)))
    beta = 2 / (1 + np.exp(-rng.standard_normal((B, C, H))))
    S0 = rng.standard_normal((B, H, d, d))
    return tuple(np.asarray(t, np.float32) for t in (S0, q, k, v, g, beta))


def test_the_chunk_form_is_the_recurrence_at_ragged_lengths():
    """Rows that fed 0, 1, 5 and all 64 tokens of a chunk, one channel
    decaying by 5 a token (-40 after 8 tokens, -320 over the chunk: the
    factorised form would overflow float32 at 88): outputs and final
    states are the recurrence's, and the padding changes nothing."""
    S0, q, k, v, g, beta = _delta_inputs(4, 64, 2, 16)
    g[..., 0] = -5.0
    valid = np.asarray([0, 1, 5, 64], np.int32)
    out, new = delta_rule.delta_chunk(*(jnp.asarray(t) for t in (
        S0, q, k, v, g, beta)), jnp.asarray(valid))
    out, new = np.asarray(out), np.asarray(new)
    assert np.isfinite(out).all() and np.isfinite(new).all()
    for b, n in enumerate(valid):
        want, S = _recurrence(S0[b], q[b, :n], k[b, :n], v[b, :n], g[b, :n],
                              beta[b, :n])
        assert np.abs(new[b] - S).max() < 2e-5
        if n:
            assert np.abs(out[b, :n] - want).max() < 2e-5
    assert np.array_equal(new[0], S0[0])  # fed nothing: bit for bit


def test_a_chunk_that_is_no_power_of_two_and_a_whole_sequence():
    S0, q, k, v, g, beta = _delta_inputs(2, 150, 2, 16, seed=1)
    out = np.asarray(delta_rule.delta_sequence(*(jnp.asarray(t) for t in (
        q, k, v, g, beta)), chunk=64))
    for b in range(2):
        want, _ = _recurrence(np.zeros_like(S0[b]), q[b], k[b], v[b], g[b],
                              beta[b])
        assert np.abs(out[b] - want).max() < 2e-5
    # a chunk of 6 is padded to 8 inside
    got, new = delta_rule.delta_chunk(jnp.asarray(S0), *(
        jnp.asarray(t[:, :6]) for t in (q, k, v, g, beta)))
    want, S = _recurrence(S0[0], q[0, :6], k[0, :6], v[0, :6], g[0, :6],
                          beta[0, :6])
    assert np.abs(np.asarray(got)[0] - want).max() < 2e-5
    assert np.abs(np.asarray(new)[0] - S).max() < 2e-5


def test_a_ragged_tick_steps_chunks_or_leaves_each_row():
    """Five rows of a packed tick: one fed nothing, two one token (one
    of them on a fresh slot whose stale state must read as zero), two a
    chunk (5 and 8 tokens). Each is the recurrence's; the starved row's
    state comes back bit for bit."""
    C, H, d = 8, 2, 16
    valid = np.asarray([1, 0, 5, 1, 8], np.int32)
    fresh = np.asarray([False, False, True, True, False])
    S0, q, k, v, g, beta = _delta_inputs(5, C, H, d, seed=2)
    first = np.cumsum(valid) - valid
    keep = np.arange(C)[None, :] < valid[:, None]

    def flat(t):  # the packed order, then three dead rows
        return jnp.asarray(np.concatenate([t[keep], t[~keep][:3]]))

    out, new = delta_rule.delta_ragged(
        jnp.asarray(S0), flat(q), flat(k), flat(v), flat(g), flat(beta),
        jnp.asarray(first), jnp.asarray(valid), jnp.asarray(fresh), C)
    out, new = np.asarray(out), np.asarray(new)
    for s, n in enumerate(valid):
        start = np.zeros_like(S0[s]) if fresh[s] else S0[s]
        want, S = _recurrence(start, q[s, :n], k[s, :n], v[s, :n], g[s, :n],
                              beta[s, :n])
        if n:
            assert np.abs(out[first[s]:first[s] + n] - want).max() < 2e-5
            assert np.abs(new[s] - S).max() < 2e-5
    assert np.array_equal(new[1], S0[1])
    assert not out[valid.sum():].any()  # no live token: zero


# -- (c) through the engine's slot cache --------------------------------------


def _serve(model, params, prompts, news, **engine):
    """Drive the engine tick by tick; returns the engine, the requests
    and, for every (request, position) the engine held next-token logits
    for, those logits."""
    eng = _engine(model, params, **engine)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
    seen = {}
    while eng.step():
        logits = np.asarray(eng._last_logits)
        for s, st in enumerate(eng._slots):
            if st is not None and st.decoding:
                seen[(st.req.rid, st.cursor - 1)] = logits[s]
    return eng, reqs, seen


def _hold_to_the_reference(cfg, params, reqs, prompts, news, seen):
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
    return compared


@pytest.mark.parametrize("attend_kernel", ["dense", "pallas"])
def test_chunked_prefill_then_decode_agrees_with_the_reference(
        small, attend_kernel):
    """Five requests through three slots in chunks of 4: a prompt of one
    chunk and a token (5: the convolution's tail is read across the
    chunk's edge), long ones (41, 30), one that ends on a chunk's edge
    (12), one shorter than the convolution (3), slots refilled after
    another tenant. Every logits row the engine sampled from is the
    reference's full forward at that position (the recurrence, token by
    token), with the GQA layers' attend dense and as the kernel
    (interpret mode)."""
    cfg, params, model = small
    prompts = [_tokens(n, i) for i, n in enumerate((5, 41, 12, 3, 30))]
    news = [12, 14, 9, 20, 10]
    eng, reqs, seen = _serve(
        model.clone(attend_kernel=attend_kernel), params, prompts, news,
        slots=3, max_len=64, prefill_chunk=CHUNK)
    assert _hold_to_the_reference(cfg, params, reqs, prompts, news,
                                  seen) >= sum(news)
    assert eng.requests_completed == 5


def test_a_reused_slot_starts_from_a_zero_state_and_tail(small):
    """One slot: a long request leaves its state and its convolution
    tail behind; the engine parks the slot by zeroing cursors alone (the
    leaves still hold the first tenant's numbers when the second
    arrives), and the second decodes to the reference's logits."""
    cfg, params, model = small
    prompts, news = [_tokens(45, 3), _tokens(3, 4)], [6, 9]
    eng = _engine(model, params, slots=1, max_len=64, prefill_chunk=CHUNK)
    eng.submit(prompts[0], news[0])
    eng.drain()
    kda = eng._cache["layers_1"]["kda"]
    assert np.abs(np.asarray(kda["state"])).max() > 1e-3
    assert np.abs(np.asarray(kda["conv_tail"])).max() > 1e-3
    eng2, reqs, seen = _serve(model, params, prompts, news, slots=1,
                              max_len=64, prefill_chunk=CHUNK)
    _hold_to_the_reference(cfg, params, reqs, prompts, news, seen)


def test_a_starved_rows_state_is_bit_for_bit_what_it_was(small):
    """A budget of 4: a batch-tier request is two chunks into its prompt
    when an interactive one arrives and takes every tick's budget. Across
    each tick that deals the first row nothing, its state, its tail and
    its cursor are the bits they were; then it goes on to its tokens."""
    _, params, model = small
    eng = _engine(model, params, slots=2, max_len=64, prefill_chunk=CHUNK,
                  scheduler={"tick_token_budget": 4}, pipeline=False)
    slow = eng.submit(_tokens(30, 20), 4, tier="batch")
    eng.step()
    eng.step()
    eng.submit(_tokens(30, 21), 4)
    starved = 0
    while True:
        before = jax.tree.map(np.asarray, eng._cache["layers_2"]["kda"])
        cursors = [st.cursor if st else None for st in eng._slots]
        if not eng.step():
            break
        after = jax.tree.map(np.asarray, eng._cache["layers_2"]["kda"])
        for s, st in enumerate(eng._slots):
            if (st is not None and not st.decoding
                    and st.cursor == cursors[s] and cursors[s] > 0):
                starved += 1
                assert np.abs(before["state"][s]).max() > 1e-3
                for name in ("state", "conv_tail", "cache_index"):
                    assert np.array_equal(before[name][s], after[name][s])
    assert starved > 3
    assert len(slow.stream.tokens(timeout=10)) == 4


def test_the_packed_tick_gives_the_full_width_ticks_tokens(small):
    """A budget of 6 packs a [3, 4] tick's live tokens to 8 rows; a
    budget of 12 runs the full-width program. Same streams, same
    counters of the experts and of the delta rule."""
    _, params, model = small
    prompts = [_tokens(n, 10 + i) for i, n in enumerate((19, 7, 26, 11))]
    streams, stats = [], []
    for budget in (6, 12):
        eng = _engine(model, params, slots=3, max_len=64,
                      prefill_chunk=CHUNK,
                      scheduler={"tick_token_budget": budget})
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.drain()
        streams.append([r.stream.tokens(timeout=10) for r in reqs])
        stats.append(eng.stats())
    assert streams[0] == streams[1]
    assert stats[0]["packed_ticks_total"] > 0 == stats[1]["packed_ticks_total"]
    assert stats[0]["query_positions_total"] < stats[0][
        "attend_query_positions_total"]
    for st in stats:
        # a live token is routed whichever program ran it, padding never;
        # every live token took the step or lay in a chunk, in both KDA
        # layers
        assert st["routed_total_total"] == 4 * 4 * (
            st["useful_query_tokens_total"])
        assert (st["state_rows_stepped_total"]
                + st["chunk_positions_live_total"]
                == 2 * st["useful_query_tokens_total"])


def test_an_eos_under_the_loop_a_tick_ahead_leaves_the_next_tenant_clean(
        small):
    """The pipelined loop has dispatched one more tick when it reads an
    eos: that overrun token entered the row's recurrent state and cannot
    be rewound. The request is finished and the slot re-entered at
    cursor 0, so the next tenant's logits are a fresh engine's, bit for
    bit."""
    _, params, model = small
    first, second = _tokens(9, 30), _tokens(11, 31)
    probe = _engine(model, params, slots=1, max_len=64, prefill_chunk=CHUNK)
    r = probe.submit(first, 12)
    probe.drain()
    eos = r.stream.tokens(timeout=10)[5]  # the stream's sixth token
    _, (alone,), seen = _serve(model, params, [second], [7], slots=1,
                               max_len=64, prefill_chunk=CHUNK)
    eng = _engine(model, params, slots=1, max_len=64, prefill_chunk=CHUNK)
    a = eng.submit(first, 12, eos_id=int(eos))
    b = eng.submit(second, 7)
    got = {}
    while eng.step():
        logits = np.asarray(eng._last_logits)
        st = eng._slots[0]
        if st is not None and st.decoding and st.req.rid == b.rid:
            got[st.cursor - 1] = logits[0]
    assert len(a.stream.tokens(timeout=10)) == 5  # the eos is not streamed
    assert a.stream.finish_reason == "eos"
    assert eng.stats()["overrun_tokens"] >= 1
    fresh = {pos: row for (rid, pos), row in seen.items()
             if rid == alone.rid}
    assert len(got) >= 7 and set(got) == set(fresh)
    for pos, row in got.items():
        assert np.array_equal(row, fresh[pos])
    assert b.stream.tokens(timeout=10) == alone.stream.tokens(timeout=10)


# -- engine counts, by kind ---------------------------------------------------


def test_the_engine_counts_by_kind_and_the_report_prints_it(small, tmp_path,
                                                            capsys):
    _, params, model = small
    eng = _engine(model, params, slots=2, max_len=64, prefill_chunk=CHUNK)
    eng.submit(_tokens(9, 1), 3)
    eng.drain()
    st = eng.stats()
    ticks = [s for s in eng.flight.snapshots() if s["kind"] == "tick"]
    # two GQA and two KDA layers; dense attend off the chip: every
    # position of both rows a GQA layer
    assert all(t["full_key_positions"] == 2 * 2 * 64 for t in ticks)
    # the prompt's 9 tokens: chunks of 4 and 4 (8 positions the chunk
    # form ran, all live), then one token through the step, as every
    # decoded token; both KDA layers
    assert st["chunk_positions_live_total"] == 2 * 8
    assert st["chunk_positions_computed_total"] == 2 * 2 * CHUNK
    assert st["state_rows_stepped_total"] == 2 * (
        st["useful_query_tokens_total"] - 8)
    # bytes: K and V 2 x 16 wide, float32, and an int32 cursor a row;
    # a state of 4 x 16 x 16 float32, a tail of 3 x 3 x 64, a cursor
    assert st["cache_bytes_full"] == 2 * (2 * 64 * 2 * 32 * 4 + 2 * 4)
    assert st["cache_bytes_state"] == 2 * 2 * (
        4 * 16 * 16 * 4 + 3 * 3 * 64 * 4 + 4)
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    telemetry_report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    assert (f"state_rows_stepped: {st['state_rows_stepped_total']}  "
            f"chunk_positions_live/computed: 16/16 (100.0% useful)  "
            f"full_key_positions: {st['full_key_positions_total']}") in out
    assert "cache_bytes_state: " in out and "cache_bytes_full: " in out


# -- (d) the shares add up ----------------------------------------------------


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """Sixteen chips of one expert each: their routed parts, with the
    shared expert (which every chip computes alike) counted once, are
    the reference's layer over all sixteen."""
    m = ref.sizes(_config(experts_held=None))
    p = ref.make_params(_config(experts_held=None), 5)["params"][
        "layers_1"]["moe"]
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 12, 64)), jnp.float32)
    live = jnp.ones(u.shape[:2], bool)

    def part(held, rank, shared):
        module = RoutedExperts(
            n_routed_experts=16, experts_held=held, expert_rank=rank,
            num_experts_per_tok=4, n_group=1, topk_group=1,
            routed_scaling_factor=1.0, width=32, n_shared_experts=shared,
            dtype=jnp.float32, expert_tile=8)
        lo = rank * held
        share = {**{k: v for k, v in p.items() if shared or k != "shared"},
                 **{k: p[k][lo:lo + held]
                    for k in ("w_gate", "w_up", "w_down")}}
        return module.apply({"params": share}, u, live,
                            mutable=["counters"])[0][0]

    with jax.default_matmul_precision("highest"):
        uncut = ref._expert_layer(m, p, u[0], "f32")
        parts = [part(1, r, int(r == 0)) for r in range(16)]
        whole = part(16, 0, 1)
    assert np.abs(np.asarray(sum(parts) - uncut)).max() < TOL
    assert np.abs(np.asarray(whole - uncut)).max() < TOL
    # and a part alone is not the layer: the cut is not a no-op
    assert np.abs(np.asarray(parts[0] - uncut)).max() > 100 * TOL


# -- (e) what the model refuses -----------------------------------------------


@pytest.mark.parametrize("option,what", [
    (dict(paged=True), "cannot be served with paged: it lacks a paged "
                       "cache beside a recurrent state"),
    (dict(draft="ngram"), "cannot be served with draft: it lacks "
                          "speculative decoding: a rejected suffix"),
    (dict(multi_step_k=2), "cannot be served with multi_step: it lacks "
                           "multi-step decode windows"),
    (dict(prefill_chunk=None), "cannot be served with monolithic_prefill"),
    (dict(mesh="any"), "cannot be served with mesh: it lacks tensor "
                       "parallelism"),
])
def test_the_engine_refuses_what_the_model_lacks(small, option, what):
    _, params, model = small
    with pytest.raises(ValueError, match=what):
        ServingEngine(model, params, slots=2, max_len=64, **option)


def test_the_engine_refuses_a_draft_model_and_an_int8_cache(small):
    _, params, model = small
    with pytest.raises(ValueError, match="cannot be served with draft"):
        ServingEngine(model, params, slots=2, max_len=64, draft=model,
                      draft_params=params)
    with pytest.raises(ValueError, match="cache_dtype='int8'"):
        ServingEngine(model.clone(cache_dtype="int8"), params, slots=2,
                      max_len=64)
