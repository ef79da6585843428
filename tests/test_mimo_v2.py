"""``mimo_v2_lm`` (window and full attention layers of different KV head
counts in one slot cache, keys wider than values, a learned sink, top-k
sigmoid routing over one chip's share of the experts) against its plain
reference, ``chipbench/references/mimo_v2.py``, on seeded random weights
at a small size with every ratio kept: 8 heads of 24/16 with rotary 8,
2 KV heads in full layers and 4 in window layers, window 8 in a ring of
16, 16 experts of which 8 are held, 4 a token.

Everything here is float32 on the CPU, so the two sides differ only by
the order of float32 sums (a ring against each query's own window,
tiles against whole rows, grouped rows against every expert over every
token): ``TOL`` = 2e-4 on logits of spread 1 is fifty times the 3e-6
measured, and a thousandth of what either control moves them by.

Also here: the blocks two models share (``models/blocks.py``) moved
without changing a program of the models that had them.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import mimo_v2 as ref
from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.models.blocks import RoutedExperts
from distkeras_tpu.ops import hybrid_attend, splash_prefill
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.serving import engine as engine_mod
from distkeras_tpu.telemetry import report as telemetry_report

TOL = 2e-4
WINDOW, RING, CHUNK = 8, 16, 4
SMALL = dict(
    vocab_size=97, d_model=64, num_layers=4, num_heads=8, head_dim=24,
    v_head_dim=16, num_kv_heads=2, swa_num_kv_heads=4, sliding_window=WINDOW,
    window_ring=RING, hybrid_layer_pattern=[0, 1, 1, 0],
    moe_layer_freq=[0, 1, 1, 1], intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
    experts_held=8, expert_rank=0, max_len=64, expert_tile=8)


def _config(**over):
    return {"model": dict(SMALL, **over),
            "precision": {"parameters": "float32"}}


@pytest.fixture(scope="module")
def small():
    cfg = _config()
    params = ref.make_params(cfg, 7)
    model = get_model("mimo_v2_lm", **cfg["model"], dtype=jnp.float32)
    return cfg, params, model


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], size=n).astype(np.int32)


# -- (a) the model's full forward ---------------------------------------------


@pytest.mark.parametrize("length", [5, 40])
def test_full_forward_agrees_with_the_reference(small, length):
    cfg, params, model = small
    toks = _tokens(length, length)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(toks)[None])[0])
    want = ref.forward_logits(cfg, params, toks, np.arange(length))
    assert np.abs(got - want).max() < TOL
    assert 0.5 < want.std() < 2.0  # logits that choose


@pytest.mark.parametrize("precision,moved", [("int8", 0.25),
                                             ("no_window", 0.25)])
def test_a_control_falls_outside_the_tolerance(small, precision, moved):
    """The int8 control and the window layers without their window move
    the logits a thousand times ``TOL``."""
    cfg, params, _ = small
    toks = _tokens(40, 1)
    want = ref.forward_logits(cfg, params, toks, np.arange(40))
    low = ref.forward_logits(cfg, params, toks, np.arange(40), precision)
    assert np.abs(low - want).max() > moved > 1000 * TOL


def test_the_weights_follow_the_seed_and_the_models_layout(small):
    cfg, params, model = small
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))
    assert jax.tree.map(lambda a: a.shape, params["params"]) == jax.tree.map(
        lambda a: a.shape, init["params"])
    # window layers have a sink a head, full layers none; no layer has
    # a shared expert
    assert "sink" in params["params"]["layers_1"]["attn"]
    assert "sink" not in params["params"]["layers_3"]["attn"]
    assert "shared" not in params["params"]["layers_1"]["moe"]
    other = ref.make_params(cfg, 2 ** 31 + 5)
    assert not np.array_equal(params["params"]["head"],
                              other["params"]["head"])


def test_the_published_pattern_is_five_window_layers_to_one_full():
    kinds = get_model("mimo_v2_lm").layer_kinds()
    assert len(kinds) == 48 and kinds.count("full") == 9
    assert [i for i, k in enumerate(kinds) if k == "full"] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert ref.sizes({"model": {}})["hybrid_layer_pattern"] == tuple(
        int(k == "window") for k in kinds)


# -- (b, c) through the engine's slot cache -----------------------------------


def _serve(model, params, prompts, news, **engine):
    """Drive the engine tick by tick; returns the engine, the requests
    and, for every (request, position) the engine held next-token logits
    for, those logits."""
    eng = ServingEngine(model, params, registry=telemetry.MetricRegistry(),
                        tracer=telemetry.Tracer(), **engine)
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
    """Five requests through three slots in chunks of 4: a prompt
    shorter than the window (5), prompts that go round the ring of 16
    several times (41, 30), one that ends on a chunk's edge (12), slots
    refilled after another tenant, decode across the ring's seam. Every
    logits row the engine sampled from is the reference's full forward
    at that position, with the full layers' attend dense and as the
    kernel (interpret mode)."""
    cfg, params, model = small
    prompts = [_tokens(n, i) for i, n in enumerate((5, 41, 12, 3, 30))]
    news = [12, 14, 9, 20, 10]
    eng, reqs, seen = _serve(
        model.clone(attend_kernel=attend_kernel), params, prompts, news,
        slots=3, max_len=64, prefill_chunk=CHUNK)
    assert _hold_to_the_reference(cfg, params, reqs, prompts, news,
                                  seen) >= sum(news)
    assert eng.requests_completed == 5


def test_a_reused_slot_attends_nothing_of_the_last_tenants_ring(small):
    """One slot: a long request fills the ring several times over, then
    a short one (3 tokens: most ring entries still hold the long one's
    keys, at positions the arithmetic calls negative) decodes to the
    reference's logits."""
    cfg, params, model = small
    prompts, news = [_tokens(45, 3), _tokens(3, 4)], [6, 9]
    eng, reqs, seen = _serve(model, params, prompts, news, slots=1,
                             max_len=64, prefill_chunk=CHUNK)
    ring = np.asarray(eng._cache["layers_1"]["attn"]["cached_key"])
    assert np.abs(ring[0]).min(axis=(1, 2)).all()  # every entry was written
    _hold_to_the_reference(cfg, params, reqs, prompts, news, seen)


def test_ring_positions_are_arithmetic_on_the_cursor():
    ends = jnp.asarray([0, 3, 16, 21])
    got = np.asarray(hybrid_attend.ring_positions(ends, 16))
    assert (got[0] < 0).all()                      # nothing written yet
    assert got[1].tolist()[:3] == [0, 1, 2] and (got[1][3:] < 0).all()
    assert got[2].tolist() == list(range(16))
    assert got[3].tolist() == [16, 17, 18, 19, 20] + list(range(5, 16))


# -- (d) the kernel and the window attend against the plain attend ------------


def _attend_inputs(B, T, H, Hk, dk, dv, L, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, T, H, dk)).astype(np.float32)
    k = rng.standard_normal((B, L, Hk, dk)).astype(np.float32)
    v = rng.standard_normal((B, L, Hk, dv)).astype(np.float32)
    return q, k, v


def _plain(q, k, v, qpos, kpos, window=None, sink=None):
    """numpy, one (row, query, head) at a time."""
    B, T, H, dk = q.shape
    G = H // k.shape[2]
    out = np.zeros((B, T, H, v.shape[-1]), np.float64)
    for b in range(B):
        for t in range(T):
            see = (kpos[b] >= 0) & (kpos[b] <= qpos[b, t])
            if window:
                see &= kpos[b] > qpos[b, t] - window
            for h in range(H):
                s = k[b, see, h // G].astype(np.float64) @ q[b, t, h] / \
                    np.sqrt(dk)
                m = max(s.max(initial=-np.inf),
                        -np.inf if sink is None else sink[h])
                e = np.exp(s - m)
                z = e.sum() + (0.0 if sink is None else np.exp(sink[h] - m))
                out[b, t, h] = (e / z) @ v[b, see, h // G]
    return out


@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("H,Hk,dk,dv", [
    (32, 2, 192, 128),  # the published widths, 16 queries a KV head
    (16, 2, 192, 128),  # 8 a KV head
    (8, 2, 24, 16),     # a pair that is no multiple of 128
    (3, 1, 24, 16),     # a ragged group, padded to a sublane
])
def test_the_full_kernel_agrees_with_the_plain_attend(T, H, Hk, dk, dv):
    """Rows at different cursors, one with a single valid token in a
    chunk-wide tick and one with none; what lies past a row's walk is
    NaN, so a tile read beyond the cursor would show."""
    B, L, tile = 4, 64, 16  # four tiles a row (the tile forced below)
    q, k, v = _attend_inputs(B, T, H, Hk, dk, dv, L, seed=T + H)
    starts = np.asarray([0, 17, 40, 9], np.int32)
    lens = np.asarray([T, T, 1, 0], np.int32)
    k_p, v_p = k.copy(), v.copy()
    for b in range(B):
        walked = int(splash_prefill.walk_tiles(
            starts[b:b + 1], lens[b:b + 1], tile, L // tile)[0]) * tile
        k_p[b, walked:], v_p[b, walked:] = np.nan, np.nan
    orig = splash_prefill._KV_BLOCKS
    try:
        splash_prefill._KV_BLOCKS = (tile,)
        hybrid_attend._full.clear_cache()
        got = np.asarray(hybrid_attend.full_attention(
            jnp.asarray(q), jnp.asarray(k_p.reshape(B, L, Hk * dk)),
            jnp.asarray(v_p.reshape(B, L, Hk * dv)), jnp.asarray(starts),
            jnp.asarray(lens)))
    finally:
        splash_prefill._KV_BLOCKS = orig
        hybrid_attend._full.clear_cache()
    qpos = starts[:, None] + np.arange(T)[None]
    want = _plain(q, k, v, qpos, np.broadcast_to(np.arange(L), (B, L)))
    dense = np.asarray(hybrid_attend.dense_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.broadcast_to(jnp.arange(L), (B, L)), jnp.asarray(starts)))
    assert np.isfinite(got).all()  # the row fed nothing divides by a floor
    for b in range(3):
        n = lens[b]
        assert np.abs(got[b, :n] - want[b, :n]).max() < 1e-4
        assert np.abs(dense[b, :n] - want[b, :n]).max() < 1e-4


@pytest.mark.parametrize("T", [1, 4])
def test_the_window_attend_sees_its_window_and_its_sink(T):
    """A ring of 16 behind cursors before the first turn, on the seam
    and several turns in: the window's 8 positions (fewer at a
    request's start) and the sink's mass, nothing else. A sink of -1e30
    is the plain softmax."""
    B, H, Hk, dk, dv = 4, 8, 4, 24, 16
    rng = np.random.default_rng(T)
    q = rng.standard_normal((B, T, H, dk)).astype(np.float32)
    starts = np.asarray([0, 13, 30, 53], np.int32)
    lens = np.asarray([T, T, 1, T], np.int32)
    total = 64
    k = rng.standard_normal((B, total, Hk, dk)).astype(np.float32)
    v = rng.standard_normal((B, total, Hk, dv)).astype(np.float32)
    # what the ring holds once each row's tokens are written: position p
    # at p % 16, the rest another tenant's (large, so a leak shows)
    ring_k = np.full((B, RING, Hk, dk), 50.0, np.float32)
    ring_v = np.full((B, RING, Hk, dv), 50.0, np.float32)
    for b in range(B):
        for p in range(starts[b] + lens[b]):
            ring_k[b, p % RING], ring_v[b, p % RING] = k[b, p], v[b, p]
    sink = rng.standard_normal(H).astype(np.float32)
    qpos = starts[:, None] + np.arange(T)[None]
    kpos = np.broadcast_to(np.arange(total), (B, total))
    for sk, plain_sink in ((sink, sink), (np.full(H, -1e30, np.float32),
                                          None)):
        got = np.asarray(hybrid_attend.window_attention(
            jnp.asarray(q), jnp.asarray(ring_k), jnp.asarray(ring_v),
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(sk),
            WINDOW))
        want = _plain(q, k, v, qpos, kpos, WINDOW, plain_sink)
        for b in range(B):
            assert np.abs(got[b, :lens[b]] - want[b, :lens[b]]).max() < 1e-4
    with pytest.raises(ValueError, match="cannot hold a window"):
        hybrid_attend.window_attention(
            jnp.zeros((1, 10, H, dk)), jnp.asarray(ring_k[:1]),
            jnp.asarray(ring_v[:1]), jnp.zeros((1,), jnp.int32), None,
            jnp.asarray(sink), WINDOW)


def test_the_kernel_gate_and_the_hosts_count():
    # the published shapes tile; a minor axis of 4 x 24 lanes does not
    assert hybrid_attend.supports(64, 16, 192, 128, 24576, 4)
    assert hybrid_attend.supports(1, 16, 192, 128, 24576, 4)
    assert not hybrid_attend.supports(64, 4, 24, 16, 24576, 4)
    assert not hybrid_attend.resolves_to_kernel("auto", 64, 16, 192, 128,
                                                24576, 4)  # no TPU here
    assert hybrid_attend.resolves_to_kernel("pallas", 4, 4, 24, 16, 64, 2)
    # rows at 300 and 20000 of 24576, tiles of 256: 2 + 79 tiles
    assert splash_prefill.fetched_positions(
        np.asarray([299, 19999]), np.asarray([1, 1]), 24576) == 81 * 256


# -- (e) the packed mixed tick ------------------------------------------------


def test_the_packed_tick_gives_the_full_width_ticks_tokens(small):
    """A budget of 6 packs a [3, 4] tick's live tokens to 8 rows; a
    budget of 12 runs the full-width program. Same streams, same
    counters of the experts."""
    _, params, model = small
    prompts = [_tokens(n, 10 + i) for i, n in enumerate((19, 7, 26, 11))]
    streams, stats = [], []
    for budget in (6, 12):
        eng = ServingEngine(
            model, params, slots=3, max_len=64, prefill_chunk=CHUNK,
            scheduler={"tick_token_budget": budget},
            registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer())
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.drain()
        streams.append([r.stream.tokens(timeout=10) for r in reqs])
        stats.append(eng.stats())
    assert streams[0] == streams[1]
    assert stats[0]["packed_ticks_total"] > 0 == stats[1]["packed_ticks_total"]
    assert stats[0]["query_positions_total"] < stats[0][
        "attend_query_positions_total"]
    # a live token is routed whichever program ran it, padding never
    assert stats[0]["routed_total_total"] == 3 * 4 * (
        stats[0]["useful_query_tokens_total"])


# -- engine counts, by kind ---------------------------------------------------


def test_the_engine_counts_by_kind_and_the_report_prints_it(small, tmp_path,
                                                            capsys):
    _, params, model = small
    eng = ServingEngine(model, params, slots=2, max_len=64,
                        prefill_chunk=CHUNK,
                        registry=telemetry.MetricRegistry(),
                        tracer=telemetry.Tracer())
    eng.submit(_tokens(9, 1), 3)
    eng.drain()
    st = eng.stats()
    ticks = [s for s in eng.flight.snapshots() if s["kind"] == "tick"]
    # two full and two window layers; dense attend off the chip: every
    # position of both rows a full layer, the whole ring a window layer
    assert all(t["full_key_positions"] == 2 * 2 * 64 for t in ticks)
    assert all(t["window_key_positions"] == 2 * 2 * RING for t in ticks)
    assert st["full_key_positions_total"] == len(ticks) * 2 * 2 * 64
    assert st["window_key_positions_total"] == len(ticks) * 2 * 2 * RING
    # bytes: K 24 + V 16 wide, float32, and an int32 cursor a row
    assert st["cache_bytes_full"] == 2 * (2 * 64 * 2 * 40 * 4 + 2 * 4)
    assert st["cache_bytes_window"] == 2 * (2 * RING * 4 * 40 * 4 + 2 * 4)
    assert st["routed_total_total"] == 3 * 4 * st["useful_query_tokens_total"]
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    telemetry_report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    assert (f"full_key_positions: {st['full_key_positions_total']}  "
            f"window_key_positions: {st['window_key_positions_total']}") in out
    assert "cache_bytes_full: " in out and "cache_bytes_window: " in out


# -- (f) the shares add up ----------------------------------------------------


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their parts (no shared expert
    to count once) are the reference's layer over all sixteen."""
    m = ref.sizes(_config(experts_held=None))
    p = ref.make_params(_config(experts_held=None), 5)["params"][
        "layers_1"]["moe"]
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 12, 64)), jnp.float32)
    live = jnp.ones(u.shape[:2], bool)

    def part(held, rank):
        module = RoutedExperts(
            n_routed_experts=16, experts_held=held, expert_rank=rank,
            num_experts_per_tok=4, n_group=1, topk_group=1,
            routed_scaling_factor=1.0, width=32, n_shared_experts=0,
            dtype=jnp.float32, expert_tile=8)
        lo = rank * held
        share = {**p, **{k: p[k][lo:lo + held]
                         for k in ("w_gate", "w_up", "w_down")}}
        return module.apply({"params": share}, u, live,
                            mutable=["counters"])[0][0]

    with jax.default_matmul_precision("highest"):
        uncut = ref._expert_layer(m, p, u[0], "f32")
        parts = [part(4, r) for r in range(4)]
        whole = part(16, 0)
    assert np.abs(np.asarray(sum(parts) - uncut)).max() < TOL
    assert np.abs(np.asarray(whole - uncut)).max() < TOL
    # and a part alone is not the layer: the cut is not a no-op
    assert np.abs(np.asarray(parts[0] - uncut)).max() > 100 * TOL


# -- (g) what the model refuses -----------------------------------------------


@pytest.mark.parametrize("option,what", [
    (dict(paged=True), "cannot be served with paged"),
    (dict(draft="ngram"), "cannot be served with draft"),
    (dict(multi_step_k=2), "cannot be served with multi_step"),
    (dict(prefill_chunk=None), "cannot be served with monolithic_prefill"),
    (dict(mesh="any"), "cannot be served with mesh"),
    (dict(prefill_chunk=10), "cannot hold a window of 8 behind a prefill "
                             "chunk of 10"),
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
    with pytest.raises(ValueError, match="names 4 layers of 5"):
        get_model("mimo_v2_lm", **dict(SMALL, num_layers=5)).layer_kinds()


# -- the shared blocks moved, and no program of their first owners changed ----

# sha256 of the parameter names and of the lowered text of the two tick
# programs (a [3, 8] mixed tick under a budget of 6, and the [3, 1] tick)
# of tiny engines at commit 7ce0293, before rms_norm, SwiGLU,
# RoutedExperts and the live-packing helpers moved to models/blocks.py
PARENT = {
    "deepseek_v32_lm": {
        "params": "a902f0f6869c63f47dd1a845795e2bfb1d8a4fc6e659c923c8be26f7"
                  "207a14ce",
        # PR 38: its [3, 8] tick packs by blocks (one block of 24 rows
        # here), a deliberate change; the full-width program was
        # 3fe1ad9b...21875a. PR 40: the latent entry lies in two cache
        # leaves (latent [S, L, 16] and rope_key [S, 8, L] here), a
        # deliberate change to both ticks; they were e239c0ed...44c41c
        # and 1f1b0d20...690d97 (the [3, 1] tick's since 7ce0293).
        # PR 43: the held experts' grouped matmul is a gather, three
        # ragged matmuls and a scatter-add off the chip (two Pallas
        # launches on it) where it was a loop of tiles, a deliberate
        # change to both ticks of every expert model; they were
        # c2d4df76...e58d3d and 20e82ef3...741664
        "mixed_8_24": "5288050ef4b8d2bafe89d1e58f986bd8682a68e18081ad04aafed"
                      "5476006a3fc",
        "mixed_1_None": "2a20d9af8fb2e013525787f572752d778f914b6aa55c667c794"
                        "04f3963c1b1bd"},
    "transformer_lm": {
        "params": "c01fb3e08f7b1c7d216c5ce24b4a4ef04b8e65fb722a7ae887ffa156"
                  "8870f503",
        "mixed_8_8": "978dca6c49254a5ea430d9024c0600098ebab1960cf4422ded6aa7"
                     "f9ebcd1ae7",
        "mixed_1_None": "20f6a0e27c447583c1b040ff3f49f643361647ef01a3327b78d"
                        "2f1768ed2a3a8"},
    # this model's own, at commit 8c19c14, before solar_open2_lm came to
    # share RoutedExperts, the live packing and the full attend with it.
    # PR 43: the grouped matmul's new form (above), a deliberate change
    # to both ticks; they were b4053b9a...9ca983 and 84176e4e...c6a67a
    "mimo_v2_lm": {
        "params": "c4302266a3b30edc927227c931ecc0593b0d89d337e16db7ac593ca61"
                  "e9c75e7",
        "mixed_8_8": "eb83d328046d1c9a6179a0206ebc97237c0d40bb77c4d6b3d8ed93"
                     "93edbe8a95",
        "mixed_1_None": "a787ee7246980d1758c970db789f40c9f3d985d95a4e85f6d4e"
                        "20008e4ec9880"}}
TINY = {
    "deepseek_v32_lm": dict(
        vocab_size=64, d_model=32, num_layers=3, first_k_dense=1,
        num_heads=4, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2,
        index_head_dim=16, index_topk=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=16,
        num_experts_per_tok=4, n_group=4, topk_group=2, experts_held=4,
        expert_rank=0, rope_original_len=32, max_len=64, kv_tile=16,
        expert_tile=8, dtype=jnp.float32),
    "transformer_lm": dict(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2, max_len=64,
        dtype=jnp.float32, attention="dense"),
    "mimo_v2_lm": dict(SMALL, dtype=jnp.float32)}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.fixture(scope="module")
def first_owners():
    out = {}
    for name, kw in TINY.items():
        model = get_model(name, **kw)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        params = {"params": params["params"]}
        eng = ServingEngine(model, params, slots=3, prefill_chunk=8,
                            scheduler={"tick_token_budget": 6},
                            registry=telemetry.MetricRegistry(),
                            tracer=telemetry.Tracer())
        names = sorted(
            "/".join(str(getattr(k, "key", k)) for k in path) for path, _
            in jax.tree_util.tree_flatten_with_path(params)[0])
        got = {"params": _sha("\n".join(names))}
        cfgs = (engine_mod._IDLE_CFG,) * 3
        for chunk in (8, 1):
            live = eng._live_count(chunk, 4)
            packed = eng._layout.pack(eng, (np.zeros((3, chunk), np.int32),
                                            np.zeros(3, np.int32),
                                            np.zeros(3, np.int32)))
            got[f"mixed_{chunk}_{live}"] = _sha(engine_mod._mixed_tick_fn(
                eng._layout, cfgs, chunk, None, live).lower(
                    eng._params_only, eng._cache, eng._last_logits,
                    eng._rngs, jnp.asarray(packed)).as_text())
        out[name] = got
    return out


@pytest.mark.parametrize("name,what", [
    (name, what) for name in sorted(PARENT) for what in sorted(PARENT[name])])
def test_the_first_owners_keep_their_names_and_programs(first_owners, name,
                                                        what):
    assert first_owners[name][what] == PARENT[name][what]
