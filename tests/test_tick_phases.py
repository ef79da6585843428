"""The engine loop's phase bracket and the trainer's spans: every tick
path times its phases through one bracket whose fields sum to the
period (``loop_ms``), the old flight fields keep their formulas, the
spans land in a ``jax.profiler`` trace with their arguments, the
mixed tick's work counters agree with a count by hand, and its count of
K/V positions fetched agrees with the device's own cursors."""

import glob
import os
import statistics
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import PartitionedDataset, telemetry
from distkeras_tpu.checkpoint import Checkpointer
from distkeras_tpu.models import get_model
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.trainers import LMTrainer

KW = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2,
          max_len=64, dtype=jnp.float32, attention="dense",
          pos_emb="rope", num_kv_heads=2)

PHASES = ("ctrl", "admit", "plan", "upload", "dispatch", "wait", "stream",
          "record", "idle")

# every tick path of the engine, by the constructor arguments that
# select it
PATHS = {
    "pipelined": dict(prefill_chunk=4),  # the constructor's default loop
    "decode_only": dict(prefill_chunk=None),
    "multi_step": dict(prefill_chunk=4, multi_step_k=4),
    "spec": dict(prefill_chunk=4, draft="ngram", spec_k=3),
    "paged": dict(prefill_chunk=4, paged=True, block_size=8),
}
# ... and each under the strictly alternating loop (a speculative engine
# reads before it plans in both)
PATHS.update({"sync_" + name: dict(kw, pipeline=False)
              for name, kw in list(PATHS.items()) if name != "spec"})
PATHS["sync_mixed"] = PATHS.pop("sync_pipelined")


@pytest.fixture(scope="module")
def lm():
    model = get_model("transformer_lm", **KW)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, params


def _engine(lm, **kw):
    model, params = lm
    return ServingEngine(model, params, slots=3,
                         registry=telemetry.MetricRegistry(),
                         tracer=telemetry.Tracer(), **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, KW["vocab_size"], size=n).astype(np.int32)
            for n in lengths]


def _ticks(eng):
    return [s for s in eng.flight.snapshots() if s["kind"] == "tick"]


# -- (1) the fields sum to the period; the old fields keep their formulas ---


@pytest.mark.parametrize("path", sorted(PATHS))
def test_phase_fields_sum_to_the_period(lm, path):
    eng = _engine(lm, **PATHS[path])
    for i, p in enumerate(_prompts([10, 7, 12, 5, 9, 11])):
        eng.submit(p, max_new_tokens=[9, 14, 6, 16, 11, 8][i], seed=i)
    eng.drain()
    ticks = _ticks(eng)
    assert len(ticks) >= 12
    if path.endswith("multi_step"):
        assert any("multi_k" in t for t in ticks)
    if path == "spec":
        assert any("draft_tokens" in t for t in ticks)
    for t in ticks:
        # what the fields have always meant
        assert t["device_ms"] == t["dispatch_ms"] + t["device_wait_ms"]
        assert t["tick_ms"] == t["plan_ms"] + t["device_ms"] + t["stream_ms"]
        assert 0.0 <= t["upload_ms"] <= t["dispatch_ms"]
        for f in ("ctrl_ms", "admit_ms", "record_ms", "idle_ms", "loop_ms"):
            assert t[f] >= 0.0
        assert t["idle_ms"] == 0.0  # drain() never dozes
    # the first period starts when the engine is built, not at a record
    gaps = []
    for t, nxt in zip(ticks[1:], ticks[2:] + [None]):
        own = t
        if eng.pipeline and not eng.spec:
            # tick N+1 is planned, uploaded and dispatched inside tick
            # N's period, before N is read back
            if nxt is None or nxt["tick"] != t["tick"] + 1:
                continue
            own = nxt
        phases = (t["ctrl_ms"] + t["admit_ms"] + own["plan_ms"]
                  + own["dispatch_ms"]  # upload + dispatch
                  + t["device_wait_ms"] + t["stream_ms"] + t["record_ms"]
                  + t["idle_ms"] + t.get("deferred_stream_ms", 0.0))
        # nothing is counted twice
        assert phases <= t["loop_ms"] + 1e-6, (path, t["tick"])
        gaps.append((t["loop_ms"] - phases) / t["loop_ms"])
    assert len(gaps) >= 8
    # and little is outside a bracket: the statements between brackets
    # take some 80 us a tick, a twelfth of this model's 1 ms tick on a
    # CPU (and a three-hundredth of a 25 ms tick on the chip)
    assert statistics.median(gaps) < 0.2, (path, gaps)


# -- (2) the spans reach a profile, with their arguments ---------------------


def _program_spans(trace_dir):
    """``{line name: [(span name, stats dict), ...]}`` of the program's
    spans in the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "lm_trainer.")):
                    out.setdefault(line.name, []).append(
                        (ev.name, dict(ev.stats)))
    return out


def _profile_engine(lm, trace_dir, tmp_path):
    eng = _engine(lm, prefill_chunk=4)
    stop = threading.Event()
    loop = threading.Thread(target=eng.serve_forever, args=(stop,),
                            name="engine-loop")
    jax.profiler.start_trace(trace_dir)
    try:
        loop.start()
        reqs = [eng.submit(p, max_new_tokens=6, seed=i)
                for i, p in enumerate(_prompts([9, 6]))]
        for r in reqs:
            r.stream.tokens(timeout=60)
    finally:
        stop.set()
        loop.join(timeout=60)
        jax.profiler.stop_trace()
    assert not loop.is_alive()
    by_line = _program_spans(trace_dir)
    # all on the engine thread's line
    assert len(by_line) == 1, list(by_line)
    spans = next(iter(by_line.values()))
    assert {n for n, _ in spans} == {"engine." + p for p in PHASES}
    for name, stats in spans:
        assert "tick" in stats, name
    dispatched = [s for n, s in spans if n == "engine.dispatch"]
    ticks = {t["tick"]: t for t in _ticks(eng)}
    assert len(dispatched) == len(ticks)
    for s in dispatched:
        t = ticks[s["tick"]]
        assert s["fed_tokens"] == t["prefill_tokens"]
        assert s["n_dec"] == t["decode_tokens"]
        assert s["attended_tokens"] == t["attended_tokens"] > 0
        assert s["query_positions"] == t["query_positions"]
        assert (s["attend_query_positions"]
                == t["attend_query_positions"] >= t["query_positions"])


def _profile_trainer(lm, trace_dir, tmp_path):
    tokens = np.random.default_rng(0).integers(
        0, KW["vocab_size"], size=(32, 16)).astype(np.int32)
    ds = PartitionedDataset.from_arrays({"tokens": tokens}, num_partitions=2)
    model = get_model("transformer_lm", **{**KW, "max_len": 16})
    trainer = LMTrainer(
        model, axes={"dp": 2}, batch_size=8, num_epoch=2,
        worker_optimizer="adam", learning_rate=1e-2,
        checkpointer=Checkpointer(str(tmp_path / "ckpt"), every_steps=1))
    jax.profiler.start_trace(trace_dir)
    try:
        trainer.train(ds)
    finally:
        jax.profiler.stop_trace()
    by_line = _program_spans(trace_dir)
    assert len(by_line) == 1, list(by_line)
    spans = next(iter(by_line.values()))
    names = [n for n, _ in spans]
    assert set(names) == {"lm_trainer.stage", "lm_trainer.dispatch",
                          "lm_trainer.drain", "lm_trainer.checkpoint"}
    # staged once; one drain and one checkpoint an epoch
    assert names.count("lm_trainer.stage") == 1
    assert names.count("lm_trainer.drain") == 2
    assert names.count("lm_trainer.checkpoint") == 2
    assert sorted({s["epoch"] for n, s in spans
                   if n == "lm_trainer.dispatch"}) == [0, 1]
    assert all("window" in s for n, s in spans
               if n == "lm_trainer.dispatch")


@pytest.mark.parametrize("program", ["engine", "lm_trainer"])
def test_spans_land_in_a_profile(lm, tmp_path, program):
    run = {"engine": _profile_engine, "lm_trainer": _profile_trainer}[program]
    run(lm, str(tmp_path / "trace"), tmp_path)


# -- (3) the mixed tick's work counters, against a count by hand -------------

# (attended_tokens, key_positions, query_positions) per tick, 3 slots,
# chunk 4, 3 new tokens a request. A prompt of 6 alone: chunk of 4 from
# an empty cache attends 1+2+3+4; the last 2 tokens attend 4 cached and
# 1, 2 of their own; then one query a tick over 7, 8, 9 keys. With a
# prompt of 3 admitted behind it: both chunks in tick 1 (10 + 6 pairs,
# 4 + 3 keys); in tick 2 the short row decodes (4 keys) beside the long
# row's last chunk (11 pairs, 6 keys); then 7+5, 8+6, and 9 alone.
BY_HAND = {
    "one_prompt": ([6], dict(), [(10, 4, 12), (11, 6, 12), (7, 7, 3),
                                 (8, 8, 3), (9, 9, 3)]),
    "two_prompts": ([6, 3], dict(), [(16, 7, 12), (15, 10, 12), (12, 12, 3),
                                     (14, 14, 3), (9, 9, 3)]),
    "two_prompts_paged": ([6, 3], dict(paged=True, block_size=8),
                          [(16, 7, 12), (15, 10, 12), (12, 12, 3),
                           (14, 14, 3), (9, 9, 3)]),
}


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_work_counters_against_a_hand_count(lm, case):
    lengths, kw, want = BY_HAND[case]
    eng = _engine(lm, prefill_chunk=4, **kw)
    for i, p in enumerate(_prompts(lengths)):
        eng.submit(p, max_new_tokens=3, seed=i)
    eng.drain()
    ticks = _ticks(eng)
    got = [(t["attended_tokens"], t["key_positions"], t["query_positions"])
           for t in ticks]
    assert got == want
    st = eng.stats()
    assert st["attended_tokens_total"] == sum(w[0] for w in want)
    assert st["query_positions_total"] == sum(w[2] for w in want)
    # the default budget covers these ticks whole: nothing is packed
    assert st["attend_query_positions_total"] == sum(w[2] for w in want)
    assert st["packed_ticks_total"] == 0
    assert st["useful_query_tokens_total"] == sum(
        t["decode_tokens"] + t["prefill_tokens"] for t in ticks)
    assert st["useful_query_tokens_total"] <= st["query_positions_total"]


def test_packed_tick_counters_against_a_hand_count(lm):
    """``one_prompt`` again under a budget of 6: ``N`` = 8 of the ``[3,
    4]`` tick's 12 positions, so the two ticks that feed a chunk report
    the 8 their per-token layers ran over beside the attend's 12; the
    ``[3, 1]`` decode ticks have nothing to leave out. The model's work
    (pairs attended, keys read) is what it was."""
    eng = _engine(lm, prefill_chunk=4, scheduler={"tick_token_budget": 6})
    (p,) = _prompts([6])
    eng.submit(p, max_new_tokens=3, seed=0)
    eng.drain()
    ticks = _ticks(eng)
    got = [(t["attended_tokens"], t["key_positions"], t["query_positions"],
            t["attend_query_positions"]) for t in ticks]
    assert got == [(10, 4, 8, 12), (11, 6, 8, 12), (7, 7, 3, 3),
                   (8, 8, 3, 3), (9, 9, 3, 3)]
    st = eng.stats()
    assert st["packed_ticks_total"] == 2
    assert st["query_positions_total"] == 8 + 8 + 3 * 3
    assert st["attend_query_positions_total"] == 12 + 12 + 3 * 3
    assert st["useful_query_tokens_total"] == 6 + 3


# -- (4) K/V positions the attend copies in, against the device's cursors ----

# a cache of three 32-position KV tiles, so rows cross tile edges
FETCH_KW = {**KW, "max_len": 96}
FETCH_CASES = {
    # the kernel forced (interpret mode): every row's walk, in tiles
    "splash": (dict(prefill_kernel="splash"), True),
    # the dense attend reads every position of every row, every tick
    "gather": (dict(prefill_kernel="gather"), False),
    # 'auto' off the chip resolves to the dense attend
    "auto_cpu": (dict(prefill_kernel="auto"), False),
    # the paged layout's gathered view is built whole whatever reads it
    "splash_paged": (dict(prefill_kernel="splash", paged=True,
                          block_size=8), False),
}


@pytest.mark.parametrize("case", sorted(FETCH_CASES))
def test_key_positions_fetched_follows_the_cursors(case):
    from distkeras_tpu.ops import splash_prefill as sp

    kw, bounded = FETCH_CASES[case]
    model = get_model("transformer_lm", **FETCH_KW)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    eng = _engine((model, params), prefill_chunk=4, **kw)
    S, L = 3, FETCH_KW["max_len"]
    kb = sp.choose_kv_block(L)
    assert L // kb == 3
    # what the device holds as each row's cursor when a tick is planned
    # (admissions done), and the valid-token counts the tick uploads:
    # the truth the host's count is held to (an idle row feeds nothing
    # and walks one tile wherever its last tenant left its cursor)
    device_starts, valid_lens = [], []
    plan, upload = eng._plan_dispatch_mixed, eng._upload

    def spy_plan():
        if not eng.paged:
            device_starts.append(np.asarray(next(
                leaf for leaf in jax.tree.leaves(eng._cache)
                if leaf.ndim == 1)))
        return plan()

    def spy_upload(packed):
        # slot layout: fed [S, C], then valid [S], then the sample mask
        valid_lens.append(np.asarray(packed)[-2 * S:-S])
        return upload(packed)

    eng._plan_dispatch_mixed, eng._upload = spy_plan, spy_upload
    # five requests over three slots: rows finish at different depths,
    # idle for a few ticks, and are taken again
    lengths, new = [30, 6, 41, 9, 5], [6, 3, 30, 2, 40]
    for i, p in enumerate(_prompts(lengths)):
        eng.submit(p, max_new_tokens=new[i], seed=i)
    eng.drain()
    ticks = _ticks(eng)
    assert len(ticks) > 30
    for i, t in enumerate(ticks):
        assert t["cache_positions"] == S * L
        assert (t["key_positions"] <= t["key_positions_fetched"]
                <= t["cache_positions"])
        if bounded:
            # a row walks to the tile of its last valid token, one tile
            # where it has none
            want = sum(min((int(c) + int(n) - 1) // kb, L // kb - 1) + 1
                       if n else 1
                       for c, n in zip(device_starts[i], valid_lens[i])) * kb
            assert t["key_positions_fetched"] == want, (i, device_starts[i])
        else:
            assert t["key_positions_fetched"] == S * L
    fetched = [t["key_positions_fetched"] for t in ticks]
    if not eng.paged:
        # idle rows were planned with no valid token, and their device
        # cursors held while they idled
        # (a slot empty after the tick before and after this one)
        idle = {(i, s) for i in range(1, len(ticks)) for s in range(S)
                if ticks[i - 1]["slots"][s] is None
                and ticks[i]["slots"][s] is None}
        assert idle and all(valid_lens[i][s] == 0 for i, s in idle)
        assert all(device_starts[i][s] == device_starts[i + 1][s]
                   for i, s in idle if (i + 1, s) in idle)
    if bounded:
        # rows at different depths: the count moves with them
        assert min(fetched) == S * kb and max(fetched) > S * kb
        assert max(fetched) < S * L
    st = eng.stats()
    assert st["key_positions_fetched_total"] == sum(fetched)
    assert st["cache_positions_total"] == S * L * len(ticks)
