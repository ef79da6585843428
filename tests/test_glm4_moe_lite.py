"""``glm4_moe_lite_lm`` (dense multi-head latent attention over the
latent slot cache, the whole bank of routed experts with a shared one,
and the model's own multi-token-prediction module as the serving
engine's drafter, ``draft="mtp"``) against its plain reference,
``chipbench/references/glm4_moe_lite.py``, on seeded random weights at a
small size with every ratio kept: 4 heads of key 12 + 8 against value
16, 16 experts of which 4 a token, one dense layer and two expert
layers, the module behind them.

Everything here is float32 on the CPU, so the two sides differ only by
the order of float32 sums (absorbed against expanded attention, tiles
against whole rows, grouped rows against every expert over every token):
``TOL`` = 2e-4 on logits of magnitude ~3.5 is fifty times the 4e-6
measured, and a ten-thousandth of what either control moves them by.

The engine tests run at a vocabulary of 16, where the module's best
token is the model's about one time in eight: some drafts are accepted
and some refused in every run.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.kernels import glm4_moe_lite as counts
from chipbench.references import glm4_moe_lite as ref
from distkeras_tpu.models import get_model
from distkeras_tpu.ops import mla
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.telemetry import report as telemetry_report

TOL = 2e-4
SMALL = dict(
    vocab_size=16, d_model=64, num_layers=3, first_k_dense=1, num_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
    max_len=64, kv_tile=16, expert_tile=8)
PROMPTS = (30, 11, 21, 5, 40, 9)
NEWS = (20, 12, 9, 24, 10, 1)


def _config(**over):
    return {"model": dict(SMALL, **over),
            "precision": {"parameters": "float32"}}


@pytest.fixture(scope="module")
def small():
    cfg = _config()
    params = ref.make_params(cfg, 7)
    model = get_model("glm4_moe_lite_lm", **cfg["model"], dtype=jnp.float32)
    return cfg, params, model


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], size=n).astype(np.int32)


def _serve(model, params, news=NEWS, eos=None, **engine):
    """Six requests through three slots in chunks of 8, to the end."""
    engine.setdefault("slots", 3)
    engine.setdefault("prefill_chunk", 8)
    eng = ServingEngine(model, params, **engine)
    reqs = [eng.submit(_tokens(n, i), new, eos_id=eos)
            for i, (n, new) in enumerate(zip(PROMPTS, news))]
    while eng.step():
        pass
    return eng, [r.stream.tokens() for r in reqs]


@pytest.fixture(scope="module")
def undrafted(small):
    _, params, model = small
    return _serve(model, params, pipeline=False)


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("length", [9, 40, 64])
def test_full_forward_agrees_with_the_reference(small, length):
    cfg, params, model = small
    toks = _tokens(length)
    got = np.asarray(model.apply(params, toks[None])[0])
    want = ref.forward_logits(cfg, params, toks, np.arange(length))
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 1.0  # logits of a size worth comparing


def test_the_module_agrees_with_the_references_mtp_logits(small):
    """The hidden states a call with ``head_at`` returns, fed to
    ``draft`` beside each position's next token, give the reference's
    module logits at every position that has a next token."""
    cfg, params, model = small
    toks = _tokens(40)
    at = np.arange(40)
    hidden, logits = model.apply(params, toks[None], head_at=at[None])
    assert np.abs(np.asarray(logits[0]) - ref.forward_logits(
        cfg, params, toks, at)).max() < TOL
    got = model.apply(params, hidden, np.roll(toks, -1)[None],
                      method="draft")
    want = ref.mtp_logits(cfg, params, toks, at[:-1])
    assert np.abs(np.asarray(got[0, :-1]) - want).max() < TOL
    assert np.abs(want).max() > 1.0
    with pytest.raises(ValueError, match="needs tokens"):
        ref.mtp_logits(cfg, params, toks, at)


@pytest.mark.parametrize("precision,moved", [("no_shared", 0.5),
                                             ("int8", 0.1)])
def test_a_control_falls_outside_the_tolerance(small, precision, moved):
    """The shared expert left out, and every operand rounded to int8,
    each move the logits by thousands of tolerances."""
    cfg, params, _ = small
    toks = _tokens(40)
    at = np.arange(40)
    want = ref.forward_logits(cfg, params, toks, at)
    low = ref.forward_logits(cfg, params, toks, at, precision)
    assert np.abs(low - want).max() > moved > 100 * TOL


def test_padding_the_reference_changes_nothing(small):
    cfg, params, _ = small
    toks = _tokens(21)
    at = np.arange(5, 20)
    for fn in (ref.forward_logits, ref.mtp_logits):
        plain = fn(cfg, params, toks, at)
        assert np.abs(plain - fn(cfg, params, toks, at, "f32", 64)
                      ).max() < 1e-5


def test_the_weights_follow_the_seed_and_the_models_layout(small):
    cfg, params, model = small
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))
    assert jax.tree.map(lambda a: a.shape, params["params"]) == jax.tree.map(
        lambda a: a.shape, init["params"])
    # the module brings a projection, two norms, a layer and a final
    # norm: the embedding and the head are the main model's leaves
    assert sorted(params["params"]["mtp"]) == [
        "eh_proj", "enorm", "hnorm", "layer", "norm"]
    again, other = ref.make_params(cfg, 7), ref.make_params(cfg, 2 ** 31 + 5)
    moe = params["params"]["mtp"]["layer"]["moe"]
    assert np.array_equal(
        moe["router"], again["params"]["mtp"]["layer"]["moe"]["router"])
    assert not np.array_equal(
        moe["router"], other["params"]["mtp"]["layer"]["moe"]["router"])
    assert 0 < np.abs(moe["e_score_correction_bias"]).max() < 0.2
    # a decode module's cache: a latent layer a main layer, and the
    # module's, each with its cursor
    dm = model.clone(decode=True, slot_cursor=True, parent=None)
    cache = jax.eval_shape(dm.init, jax.random.PRNGKey(0),
                           jnp.zeros((3, 1), jnp.int32))["cache"]
    assert sorted(cache) == ["layers_0", "layers_1", "layers_2", "mtp"]
    leaves = cache["mtp"]["layer"]["attn"]
    assert {k: v.shape for k, v in leaves.items()} == {
        "latent": (3, 64, 16), "rope_key": (3, 8, 64), "cache_index": (3,)}


# -- the dense walk -----------------------------------------------------------


def _plain_attend(q, latent, rope_keys, pos, scale):
    """One query ``[H, D]`` at ``pos`` over a row's positions ``<=
    pos``: ``[H, rank]``."""
    rank = latent.shape[-1]
    keys = np.concatenate([latent, rope_keys.T], axis=-1)[:pos + 1]
    s = q @ keys.T * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ keys[:, :rank]


@pytest.mark.parametrize("packed", [False, True])
def test_the_dense_walk_attends_every_position_up_to_the_querys(packed):
    """Rows that feed nothing, one token, a verify window of two and a
    chunk in one tick, laid ``[S, C]`` and packed to the front: every
    live query's result is the plain softmax over its row's positions
    up to its own, whichever of the two walks took it."""
    rng = np.random.default_rng(3)
    S, C, H, R, rope, L, tile = 5, 6, 2, 8, 4, 32, 8
    D = R + rope
    starts = np.array([7, 0, 20, 3, 11], np.int32)
    fed = np.array([1, 0, 2, 6, 4], np.int32)
    latent = rng.normal(size=(S, L, R)).astype(np.float32)
    rope_keys = rng.normal(size=(S, rope, L)).astype(np.float32)
    q_sc = rng.normal(size=(S, C, H, D)).astype(np.float32)
    if packed:
        offsets = np.cumsum(fed) - fed
        N = 16
        q = np.zeros((N + C, H * D), np.float32)
        for s in range(S):
            q[offsets[s]:offsets[s] + fed[s]] = q_sc[s, :fed[s]].reshape(
                    fed[s], H * D)
    else:
        offsets = np.arange(S) * C
        N = S * C
        q = np.concatenate([q_sc.reshape(N, -1),
                            np.zeros((C, H * D), np.float32)])
    got = np.asarray(mla.dense_latent_attention(
        jnp.asarray(q), jnp.asarray(latent), jnp.asarray(rope_keys),
        jnp.asarray(starts), jnp.asarray(fed), jnp.asarray(offsets), C,
        small=2, tile=tile, scale=0.3))
    assert got.shape == (N, H * R)
    for s in range(S):
        for j in range(fed[s]):
            want = _plain_attend(q_sc[s, j], latent[s], rope_keys[s],
                                 starts[s] + j, 0.3)
            assert np.abs(got[offsets[s] + j].reshape(H, R) - want
                          ).max() < 1e-5, (s, j)


def test_a_tick_no_wider_than_the_window_walks_together():
    """The ``[S, 2]`` verify tick: every row's two queries in the one
    batched walk, a row that feeds one token masked at its second."""
    rng = np.random.default_rng(4)
    S, C, H, R, rope, L = 3, 2, 2, 8, 4, 16
    starts = np.array([5, 0, 9], np.int32)
    fed = np.array([2, 0, 1], np.int32)
    latent = rng.normal(size=(S, L, R)).astype(np.float32)
    rope_keys = rng.normal(size=(S, rope, L)).astype(np.float32)
    q = rng.normal(size=(S, C, H, R + rope)).astype(np.float32)
    got = np.asarray(mla.dense_latent_attention(
        jnp.asarray(np.concatenate([q.reshape(S * C, -1),
                                    np.zeros((C, H * (R + rope)),
                                             np.float32)])),
        jnp.asarray(latent), jnp.asarray(rope_keys), jnp.asarray(starts),
        jnp.asarray(fed), jnp.arange(S) * C, C, small=2, tile=8,
        scale=0.3)).reshape(S, C, H, R)
    for s, j in ((0, 0), (0, 1), (2, 0)):
        assert np.abs(got[s, j] - _plain_attend(
            q[s, j], latent[s], rope_keys[s], starts[s] + j, 0.3)
        ).max() < 1e-5
    assert np.all(got[1] == 0)
    assert mla.dense_fetched_positions(starts, fed, 8, 2) == 3 * 16
    assert mla.dense_fetched_positions([0, 40], [64, 1], 8, 2) == 2 * 48 + 64


# -- through the engine's slot cache ------------------------------------------


def _kept(sums, suffix=""):
    """``chipbench/kernels/glm4_moe_lite.py · kept_positions`` of a
    flight record or of ``stats()``'s totals."""
    return counts.kept_positions({
        "overrun_tokens": sums.get("overrun_tokens", 0),
        **{k: sums[k + suffix] for k in (
            "window_positions", "draft_tokens", "accepted_tokens")}})


def _held_logits(model, params, **engine):
    """Drive the engine tick by tick (the alternating loop); for every
    (request, position) it held next-token logits for, those logits."""
    eng = ServingEngine(model, params, slots=3, prefill_chunk=8,
                        pipeline=False, **engine)
    reqs = [eng.submit(_tokens(n, i), new)
            for i, (n, new) in enumerate(zip(PROMPTS, NEWS))]
    seen = {}
    while eng.step():
        logits = np.asarray(eng._last_logits)
        for s, st in enumerate(eng._slots):
            if st is not None and st.decoding:
                n = len(st.req.prompt) + st.req.n_emitted
                # drafting, the row's last emitted token is pending (not
                # yet fed): the logits held chose it
                seen[(st.req.rid, n - 1 - engine.get("spec_k", 0))] = (
                    logits[s])
    return reqs, seen


@pytest.mark.parametrize("draft", [{}, {"draft": "mtp", "spec_k": 1}],
                         ids=["undrafted", "mtp"])
def test_chunked_prefill_then_decode_agrees_with_the_reference(small, draft):
    """Six requests through three slots in chunks of 8: rows at
    different cursors, slots refilled after another tenant, verify
    windows beside chunks. Every logits row the engine sampled from is
    the reference's full forward at that position."""
    cfg, params, model = small
    reqs, seen = _held_logits(model, params, **draft)
    assert len(seen) > 40
    for r in reqs:
        seq = np.concatenate([r.prompt, r.stream.tokens()])
        at = sorted(p for rid, p in seen if rid == r.rid)
        want = ref.forward_logits(cfg, params, seq, at, "f32", 64)
        for row, p in zip(want, at):
            assert np.abs(seen[(r.rid, p)] - row).max() < TOL, (r.rid, p)


@pytest.mark.parametrize("engine", [
    {"pipeline": False}, {},
    {"scheduler": {"tick_token_budget": 12}},
    {"pipeline": False, "scheduler": {"tick_token_budget": 12}},
], ids=["alternating", "ahead", "ahead-packed", "alternating-packed"])
def test_a_drafted_stream_is_the_undrafted_stream(small, undrafted, engine,
                                                  tmp_path):
    """Greedy streams with the module drafting are the streams without
    it, token for token, on both loops and with the tick packed (a
    budget of 12 packs ``[3, 8]`` ticks to 16 rows); some drafts are
    accepted and some refused, and what the ticks say they emitted,
    drafted and accepted adds up to what the streams hold."""
    _, params, model = small
    plain, want = undrafted
    eng, got = _serve(model, params, draft="mtp", spec_k=1,
                      postmortem_dir=str(tmp_path), **engine)
    assert got == want
    stats = eng.stats()
    assert stats["tokens_generated"] == sum(NEWS)
    assert 0 < stats["accepted_tokens_total"] < stats["draft_tokens_total"]
    assert stats["spec_accept_pct"] == pytest.approx(
        100 * stats["accepted_tokens_total"] / stats["draft_tokens_total"])
    assert stats["overrun_tokens"] == 0  # no eos: no row ends unforeseen
    assert eng.ticks < plain.ticks  # an accepted draft saves a tick
    ticks = [t for t in eng.flight.snapshots() if t.get("kind") == "tick"]
    assert {t["program"] for t in ticks} == {"spec"}
    assert sum(t["emitted"] for t in ticks) == sum(NEWS)
    assert sum(t["decode_tokens"] for t in ticks) == sum(NEWS)
    assert sum(t["draft_tokens"] for t in ticks) == stats[
        "draft_tokens_total"]
    assert sum(t["accepted_tokens"] for t in ticks) == stats[
        "accepted_tokens_total"]
    assert sum(t["prefill_tokens"] for t in ticks) == sum(PROMPTS)
    # a window's positions: the prompt tokens, one a token emitted but
    # the last of a request (never fed) and a draft's, kept or not
    assert stats["window_positions_total"] == (
        sum(PROMPTS) + sum(NEWS) - len(NEWS)
        + stats["draft_tokens_total"] - stats["accepted_tokens_total"])
    assert stats["mtp_positions_fed_total"] == stats[
        "window_positions_total"]
    # the benchmark's count of the positions that entered a stream (what
    # its shares of the tick and of the peak take as useful): the
    # prompts and every token emitted and fed, tick by tick no more
    # than the window ran
    assert _kept(stats, "_total") == sum(PROMPTS) + sum(NEWS) - len(NEWS)
    for t in ticks:
        assert 0 <= _kept(t) <= t["window_positions"]
        assert _kept(t) >= t["prefill_tokens"]
    assert stats["routed_total_total"] > 0  # the counters pass through
    if "scheduler" in engine:
        assert stats["packed_ticks_total"] > 0
        assert any(t["query_positions"] == 16 for t in ticks)
    # the clock sums the verify ticks a prompt chunk rode in apart too
    assert "device_spec_tick_ms" in stats
    assert "device_spec_chunk_tick_ms" in stats
    path = eng.flight.dump_postmortem("test")
    out = io.StringIO()
    telemetry_report.report_flight(path, out=out)
    assert (f"drafts: {stats['draft_tokens_total']}  accepted: "
            f"{stats['accepted_tokens_total']}  rate ") in out.getvalue()


def test_the_engines_drafts_are_the_modules_best_tokens(small):
    """After every tick of the alternating loop, a decoding row's draft
    on the device is the reference module's best token for the position
    after its pending token (a near-tie aside: within ``TOL`` of the
    best), and every cursor, the module's too, stands at the tokens the
    row has in its cache: past no refused draft."""
    cfg, params, model = small
    eng = ServingEngine(model, params, slots=3, prefill_chunk=8,
                        pipeline=False, draft="mtp", spec_k=1)
    reqs = [eng.submit(_tokens(n, i), new)
            for i, (n, new) in enumerate(zip(PROMPTS, NEWS))]
    checked = refused = 0
    while eng.step():
        drafts = np.asarray(eng._mtp_state[1])[:, 0]
        cursors = [np.asarray(c) for c in jax.tree.leaves(eng._cache)
                   if c.ndim == 1]
        assert len(cursors) == 4  # three layers and the module
        for s, st in enumerate(eng._slots):
            if st is None or not st.decoding:
                continue
            # (read off the stream's queue without taking from it)
            seq = np.concatenate([st.req.prompt, np.asarray(
                [t for _, t in st.req.stream._q.queue], np.int32)])
            # all but the pending token are in the cache
            assert {int(c[s]) for c in cursors} == {len(seq) - 1}
            assert st.cursor == len(seq) - 1
            want = ref.mtp_logits(cfg, params, seq, [len(seq) - 2], "f32",
                                  64)[0]
            assert want.max() - want[drafts[s]] < TOL
            checked += 1
        snap = eng.flight.snapshots()[-1]
        refused += snap["draft_tokens"] - snap["accepted_tokens"]
    assert checked > 40 and refused > 10


def test_an_eos_under_the_loop_a_tick_ahead_is_the_undrafted_stream(
        small, undrafted):
    """A request that ends on an eos: the tick planned while the eos was
    unread is dropped (an overrun), the slot's next tenant starts clean,
    and every stream is the undrafted one."""
    _, params, model = small
    # a token the fourth stream holds past its first: the row decodes,
    # then ends early
    eos = undrafted[1][3][5]
    _, want = _serve(model, params, eos=eos, pipeline=False)
    assert any(len(w) < n for w, n in zip(want, NEWS))
    for loop in ({"pipeline": False}, {}):
        eng, got = _serve(model, params, eos=eos, draft="mtp", spec_k=1,
                          **loop)
        assert got == want
        stats = eng.stats()
        assert stats["tokens_generated"] == sum(map(len, want))
        assert (stats["overrun_tokens"] > 0) == (loop == {})
        # a row that had ended when its tick was read entered no stream
        fed = sum(PROMPTS) + sum(len(w) - 1 for w in want)
        assert fed - stats["overrun_tokens"] <= _kept(stats, "_total") <= fed
        for t in eng.flight.snapshots():
            if t.get("kind") == "tick":
                assert 0 <= _kept(t) <= t["window_positions"]


@pytest.mark.parametrize("loop", [{"pipeline": False}, {}],
                         ids=["alternating", "ahead"])
def test_a_sampled_row_is_accepted_against_the_modules_distribution(
        small, loop):
    """Rows at a temperature go through ``_spec_accept`` with the
    module's filtered distribution as ``q`` beside greedy rows in the
    same tick: the run is repeatable from the requests' seeds, the
    greedy rows' streams are the undrafted ones, and drafts are both
    accepted and refused."""
    _, params, model = small

    def run():
        eng = ServingEngine(model, params, slots=3, prefill_chunk=8,
                            draft="mtp", spec_k=1, **loop)
        reqs = [eng.submit(_tokens(n, i), new,
                           temperature=0.0 if i % 2 else 0.9, seed=11 + i)
                for i, (n, new) in enumerate(zip(PROMPTS, NEWS))]
        while eng.step():
            pass
        return eng, [r.stream.tokens() for r in reqs]

    eng, first = run()
    assert [len(t) for t in first] == list(NEWS)
    assert run()[1] == first
    plain = ServingEngine(model, params, slots=3, prefill_chunk=8)
    greedy = [plain.submit(_tokens(n, i), new)
              for i, (n, new) in enumerate(zip(PROMPTS, NEWS)) if i % 2]
    while plain.step():
        pass
    assert [r.stream.tokens() for r in greedy] == first[1::2]
    stats = eng.stats()
    assert 0 < stats["accepted_tokens_total"] < stats["draft_tokens_total"]


# -- what the engine refuses ---------------------------------------------------


@pytest.mark.parametrize("option,what", [
    ({"paged": True}, "paged"), ({"multi_step_k": 4}, "multi_step"),
    ({"prefill_chunk": None}, "monolithic_prefill"),
    ({"draft": "ngram"}, "draft"),
])
def test_the_engine_refuses_what_the_model_lacks(small, option, what):
    _, params, model = small
    with pytest.raises(ValueError, match=f"cannot be served with {what}"):
        ServingEngine(model, params, slots=2, **option)


def test_the_engine_refuses_a_second_draft_and_an_int8_cache(small):
    _, params, model = small
    with pytest.raises(ValueError, match="one draft a module a tick"):
        ServingEngine(model, params, slots=2, draft="mtp", spec_k=2)
    with pytest.raises(ValueError, match="takes no draft_params"):
        ServingEngine(model, params, slots=2, draft="mtp", spec_k=1,
                      draft_params=params)
    with pytest.raises(ValueError, match="cannot be served with draft"):
        ServingEngine(model, params, slots=2, draft=model,
                      draft_params=params)
    with pytest.raises(ValueError, match="int8 or fp8"):
        ServingEngine(model.clone(cache_dtype="int8"), params, slots=2)


@pytest.mark.parametrize("name,kwargs,message", [
    ("transformer_lm", dict(vocab_size=16, d_model=16, num_heads=2,
                            num_layers=1, max_len=16),
     "TransformerLM carries none"),
    ("deepseek_v32_lm", dict(
        vocab_size=16, d_model=16, num_layers=1, first_k_dense=1,
        num_heads=2, q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=4,
        qk_rope_head_dim=4, v_head_dim=4, index_n_heads=2,
        index_head_dim=8, index_topk=4, intermediate_size=16,
        moe_intermediate_size=8, n_routed_experts=4, num_experts_per_tok=2,
        n_group=1, topk_group=1, max_len=16, kv_tile=8),
     "its multi-token-prediction module is not built"),
])
def test_a_model_without_a_module_refuses_mtp(name, kwargs, message):
    model = get_model(name, **kwargs)
    with pytest.raises(ValueError, match=message):
        ServingEngine(model, {"params": {}}, slots=2, draft="mtp", spec_k=1)
