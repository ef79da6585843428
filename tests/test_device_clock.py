"""The device clock (``serving/engine.py · _DeviceClock``): a tick's
device time and the time the device ran dry before it, from readiness
probes at the loop's phase boundaries, with no profiler running.

The arithmetic runs under a made-up clock and a made-up device (a tick
starts when the call that dispatches it begins, which is where the call
enqueues it, or when the tick before it ends, whichever is later, and
its tokens are ready when it ends), so every case knows
the true values the estimates are held to. The engine's own tests check
the surfaces: flight fields, ``stats()`` since the mark, what the
repaired series take, the report's line, and that an engine handed no
writer holds nothing that grows with its ticks. No time of the real
machine is asserted anywhere."""

import json
from collections import deque

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.serving import ServingEngine
from distkeras_tpu.serving import engine as engine_mod
from distkeras_tpu.serving.engine import _DeviceClock, _InflightTick
from distkeras_tpu.telemetry import report as telemetry_report


# -- a made-up clock and a made-up device -------------------------------------


class Time:
    """Seconds, moved by hand."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def pass_ms(self, ms):
        self.t += ms / 1e3


class Toks:
    """A tick's tokens: ready once the made-up time has reached the end
    of the tick."""

    def __init__(self, time, ready_at):
        self.time, self.ready_at = time, ready_at

    def is_ready(self):
        return self.time.t >= self.ready_at


class Loop:
    """The engine thread's calls to the clock, in the order the engine
    makes them, over a device that runs one tick at a time. Keeps the
    true device time, starved time and unasked time beside what the
    clock says."""

    def __init__(self):
        self.time = Time()
        self.clock = _DeviceClock(now=self.time)
        self.device_free_at = None
        self.dozed = False
        self.true = {}      # rec id -> (tick_ms, gap_ms, dozed)
        self.said = []      # (rec, (tick, starved, unasked, err))

    def phase(self, name, ms):
        self.time.pass_ms(ms)
        if name == "idle":
            self.dozed = True
        self.clock.boundary(name)

    def dispatch(self, tick_ms, program="decode", host=None):
        """``host``: the phases that lead to the dispatch, the last of
        them the call itself: the device takes the tick up at its head
        and the call returns when it ends."""
        host = list(host or ())
        for name, ms in host[:-1]:
            self.phase(name, ms)
        self.clock.dispatching()
        now = self.time.t
        free = self.device_free_at
        start = now if free is None else max(now, free)
        gap = 0.0 if free is None else max(0.0, now - free) * 1e3
        self.device_free_at = start + tick_ms / 1e3
        rec = _InflightTick(
            toks=Toks(self.time, self.device_free_at), rows=[], tick=0,
            plan_ms=0.0, upload_ms=0.0, dispatch_ms=0.0, n_dec=0,
            fed_tokens=0, chunk=None, program=program)
        self.true[id(rec)] = (tick_ms, gap, self.dozed)
        self.dozed = False
        for name, ms in host[-1:]:
            self.phase(name, ms)
        self.clock.dispatched(rec)
        return rec

    def read(self, rec):
        self.clock.read_begins(rec)
        blocked = self.time.t < rec.toks.ready_at
        if blocked:
            self.time.t = rec.toks.ready_at
        out = self.clock.read_ends(rec)
        self.said.append((rec, out))
        self.clock.boundary("wait")
        return out, blocked

    def ahead(self, ticks, host, tick_ms=10.0, program="decode"):
        """The loop a tick ahead: dispatch N+1, then read N."""
        pending = deque()
        for _ in range(ticks):
            pending.append(self.dispatch(tick_ms, program, host))
            while len(pending) > 1:
                self.read(pending.popleft())
                self.phase("stream", 0.4)
                self.phase("record", 0.2)
        while pending:
            self.read(pending.popleft())

    def check_each_tick(self):
        """Every estimate lies within its own error of the truth."""
        for rec, (tick, starved, unasked, err) in self.said:
            true_tick, gap, dozed = self.true[id(rec)]
            assert abs(tick - true_tick) <= err + 1e-6
            assert abs(starved + unasked - gap) <= err + 1e-6
            assert (unasked if not dozed else starved) == 0.0


FAST_HOST = (("ctrl", 0.2), ("admit", 0.3), ("plan", 1.0), ("upload", 0.5),
             ("dispatch", 1.0))


def back_to_back_blocked_reads():
    loop = Loop()
    loop.ahead(6, FAST_HOST, tick_ms=10.0)
    for _, (tick, starved, unasked, err) in loop.said[1:]:
        assert (tick, starved, unasked, err) == (
            pytest.approx(10.0), 0.0, 0.0, 0.0)
    # the first found the device free: it started somewhere in the call
    assert loop.said[0][1] == (pytest.approx(10.0), 0.0, 0.0,
                               pytest.approx(1.0))
    s = loop.clock.stats()
    assert s["device_clock_exact_pct"] == pytest.approx(100 * 5 / 6)
    assert s["device_starved_pct"] == 0.0
    assert s["device_decode_tick_ms"] == pytest.approx(10.0)
    assert s["device_mixed_tick_ms"] is None


def host_late_by_3ms_seen_at_the_plan_boundary():
    loop = Loop()
    a = loop.dispatch(5.0)
    # a ends 5 ms from here; the plan phase is the one that passes it,
    # and the next tick is handed over 3 ms after a's end (by a call
    # that returns at once: the next case has one that does not)
    b = loop.dispatch(20.0, host=(("ctrl", 2.0), ("admit", 2.0),
                                  ("plan", 2.0), ("upload", 2.0),
                                  ("dispatch", 0.0)))
    (_, _, _, _), blocked_a = loop.read(a)
    assert not blocked_a
    (tick, starved, unasked, err), blocked_b = loop.read(b)
    assert blocked_b
    assert loop.true[id(b)][1] == pytest.approx(3.0)
    assert a.ready_hi - a.ready_lo == pytest.approx(2e-3)  # the plan phase
    assert err == pytest.approx(1.0)          # half the phase
    assert abs(starved - 3.0) <= 2.0          # within one phase of 3 ms
    assert starved == pytest.approx(3.0)      # (a ended mid-phase)
    assert unasked == 0.0
    assert tick == pytest.approx(20.0)
    loop.check_each_tick()


def a_call_that_returns_late_hands_over_where_it_begins():
    """Beside a server's threads the jitted call enqueues the program at
    its head and returns when it has the interpreter lock back: here
    after the tick has ended. The hand-over is where the call begins."""
    loop = Loop()
    a = loop.dispatch(8.0)
    loop.read(a)
    loop.phase("stream", 1.0)
    loop.phase("record", 0.5)
    b = loop.dispatch(8.0, host=(("plan", 0.5), ("dispatch", 10.0)))
    assert b.dispatched_t - b.dispatching_t == pytest.approx(10e-3)
    (tick, starved, unasked, err), blocked = loop.read(b)
    assert not blocked                      # it ended inside the call
    assert starved == pytest.approx(2.0)    # not the 12 to the return
    assert unasked == 0.0
    # all the clock can know of the end is that it lies in the call
    assert (b.ready_lo, b.ready_hi) == (b.dispatching_t, b.dispatched_t)
    assert tick == pytest.approx(5.0) and err == pytest.approx(15.0)
    # a tick that outlasts the call is timed from the call's head
    c = loop.dispatch(14.0, host=(("plan", 1.0), ("dispatch", 10.0)))
    (tick, starved, _, err), blocked = loop.read(c)
    assert blocked and tick == pytest.approx(14.0)
    assert starved == pytest.approx(3.0 + 3.0)   # (b's end read 3 early)
    assert err == pytest.approx(5.0 + 10.0)
    loop.check_each_tick()


def a_doze_before_the_tick_is_unasked_not_starved():
    loop = Loop()
    a = loop.dispatch(5.0)
    loop.read(a)
    loop.phase("stream", 0.5)
    loop.phase("record", 0.5)
    for _ in range(4):
        loop.phase("ctrl", 0.1)
        loop.phase("idle", 2.0)
    b = loop.dispatch(5.0, host=FAST_HOST)
    (tick, starved, unasked, err), _ = loop.read(b)
    assert starved == 0.0
    assert unasked == pytest.approx(1.0 + 4 * 2.1 + 2.0)
    assert tick == pytest.approx(5.0) and err == pytest.approx(1.0)
    # the doze is this tick's alone
    c = loop.dispatch(5.0, host=FAST_HOST)
    (_, starved, unasked, _), _ = loop.read(c)
    assert unasked == 0.0 and starved == pytest.approx(2.0)
    s = loop.clock.stats()
    assert s["device_unasked_pct"] == pytest.approx(
        100 * 11.4 / (15.0 + 11.4 + 2.0))
    loop.check_each_tick()


def the_first_tick_after_the_mark():
    loop = Loop()
    loop.ahead(3, FAST_HOST)
    before = loop.clock.stats()
    assert before["device_clock_ticks"] == 3
    loop.phase("idle", 50.0)
    loop.clock.mark()
    # the mark shows at once, whatever thread asks
    assert loop.clock.stats()["device_busy_ms"] == 0.0
    assert loop.clock.stats()["device_clock_ticks"] == 0
    first_dispatch = loop.time.t + 2e-3
    a = loop.dispatch(5.0, host=FAST_HOST)
    assert a.dispatching_t == pytest.approx(first_dispatch)
    (tick, starved, unasked, err), _ = loop.read(a)
    # the record says what happened; the sums start at the dispatch
    assert unasked == pytest.approx(52.0)
    s = loop.clock.stats()
    assert s["device_clock_ticks"] == 1
    assert s["device_unasked_ms"] == 0.0 and s["device_starved_ms"] == 0.0
    assert s["device_busy_ms"] == pytest.approx(5.0)
    assert s["device_clock_span_ms"] == pytest.approx(
        (loop.time.t - first_dispatch) * 1e3)


def a_mark_with_a_tick_in_flight():
    loop = Loop()
    a = loop.dispatch(10.0)
    loop.clock.mark()
    b = loop.dispatch(10.0, host=FAST_HOST)   # handed over while a runs
    loop.read(a)                              # dispatched before the mark
    assert loop.clock.stats()["device_clock_ticks"] == 0
    loop.read(b)
    s = loop.clock.stats()
    assert s["device_clock_ticks"] == 1
    # the sums run from the end of the tick the device was still running
    assert s["device_busy_ms"] == pytest.approx(10.0)
    assert s["device_clock_span_ms"] == pytest.approx(10.0)


def the_alternating_loop_starves_by_every_host_millisecond():
    loop = Loop()
    host = (("stream", 0.4), ("record", 0.1)) + FAST_HOST
    for i in range(5):
        rec = loop.dispatch(8.0, host=host if i else None)
        (tick, starved, unasked, err), blocked = loop.read(rec)
        assert blocked and tick == pytest.approx(8.0)
        # every host millisecond up to the call, and the call's own
        # millisecond as what is not known of the start
        assert starved == pytest.approx(2.5 if i else 0.0)
        assert err == pytest.approx(1.0 if i else 0.0)
    s = loop.clock.stats()
    assert s["device_starved_pct"] == pytest.approx(100 * 10.0 / 50.0)
    assert s["device_clock_exact_pct"] == 20.0


def a_multi_step_window():
    loop = Loop()
    loop.ahead(4, FAST_HOST, tick_ms=12.0, program="multi")
    s = loop.clock.stats()
    # device_tick_ms / k is what a token of the window cost
    assert s["device_multi_tick_ms"] == pytest.approx(12.0)
    assert s["device_decode_tick_ms"] is None
    loop.check_each_tick()


def the_speculative_order_reads_before_it_plans():
    loop = Loop()
    rec = loop.dispatch(6.0, "spec")
    for _ in range(5):
        loop.phase("stream", 0.5)   # the deferred emission
        loop.phase("ctrl", 0.1)
        (tick, _, _, err), blocked = loop.read(rec)
        assert blocked and tick == pytest.approx(6.0)
        rec = loop.dispatch(6.0, "spec", host=(
            ("stream", 0.3), ("admit", 0.2), ("plan", 1.0),
            ("upload", 0.2), ("dispatch", 0.8)))
    (_, starved, unasked, err), _ = loop.read(rec)
    assert (starved, unasked, err) == (
        pytest.approx(1.7), 0.0, pytest.approx(0.8))
    assert loop.clock.stats()["device_spec_tick_ms"] == pytest.approx(6.0)


def the_three_sums_add_up_to_the_elapsed_time():
    loop = Loop()
    rng = np.random.default_rng(7)
    pending = deque()
    first_dispatch = None
    for i in range(200):
        if rng.random() < 0.1:
            loop.phase("idle", 2.0)
        host = [(n, float(rng.uniform(0.05, 3.0))) for n, _ in FAST_HOST]
        pending.append(loop.dispatch(
            float(rng.uniform(1.0, 12.0)),
            "mixed" if rng.random() < 0.3 else "decode", host))
        if first_dispatch is None:
            first_dispatch = pending[0].dispatching_t
        while len(pending) > 1:
            loop.read(pending.popleft())
            loop.phase("stream", float(rng.uniform(0.1, 4.0)))
            loop.phase("record", 0.2)
    loop.read(pending.popleft())
    loop.check_each_tick()
    s = loop.clock.stats()
    # to the moment the last tick was known to have ended (the read
    # that found it ready came a phase later)
    elapsed = (loop.said[-1][0].ready_hi - first_dispatch) * 1e3
    assert 0.0 <= (loop.time.t - first_dispatch) * 1e3 - elapsed <= 4.2
    total = (s["device_busy_ms"] + s["device_starved_ms"]
             + s["device_unasked_ms"])
    assert s["device_clock_span_ms"] == pytest.approx(elapsed)
    assert abs(total - elapsed) <= s["device_clock_err_ms"]
    assert abs(total - elapsed) <= 3.0  # half of one phase, in fact
    assert 0.0 < s["device_clock_exact_pct"] < 100.0
    assert s["device_starved_ms"] > 0 and s["device_unasked_ms"] > 0
    # against the truth, over the run
    true_busy = sum(t for t, _, _ in loop.true.values())
    assert abs(s["device_busy_ms"] - true_busy) <= s["device_clock_err_ms"]


def two_ticks_that_end_between_two_boundaries():
    loop = Loop()
    a = loop.dispatch(1.0)
    b = loop.dispatch(1.0, host=(("plan", 0.2), ("dispatch", 0.2)))
    loop.phase("stream", 10.0)      # both end under this one phase
    loop.read(a)
    (tick, starved, unasked, err), blocked = loop.read(b)
    assert not blocked
    assert tick >= 0.0 and starved == 0.0
    assert b.ready_hi >= a.ready_hi
    loop.check_each_tick()


CLOCK_CASES = [
    back_to_back_blocked_reads,
    host_late_by_3ms_seen_at_the_plan_boundary,
    a_call_that_returns_late_hands_over_where_it_begins,
    a_doze_before_the_tick_is_unasked_not_starved,
    the_first_tick_after_the_mark,
    a_mark_with_a_tick_in_flight,
    the_alternating_loop_starves_by_every_host_millisecond,
    a_multi_step_window,
    the_speculative_order_reads_before_it_plans,
    the_three_sums_add_up_to_the_elapsed_time,
    two_ticks_that_end_between_two_boundaries,
]


@pytest.mark.parametrize("case", CLOCK_CASES, ids=lambda f: f.__name__)
def test_clock_under_a_made_up_clock(case):
    case()


def test_a_probe_is_made_only_while_a_tick_is_unread_and_not_seen_ready():
    loop = Loop()
    calls = []

    class Counted(Toks):
        def is_ready(self):
            calls.append(loop.time.t)
            return super().is_ready()

    loop.phase("ctrl", 1.0)             # nothing unread: nothing asked
    assert calls == []
    rec = loop.dispatch(2.0)
    rec.toks = Counted(loop.time, rec.toks.ready_at)
    loop.phase("ctrl", 1.0)
    loop.phase("admit", 1.5)            # seen ready here
    assert len(calls) == 2
    loop.phase("plan", 1.0)
    loop.phase("upload", 1.0)
    assert len(calls) == 2              # seen ready: asked no more
    loop.read(rec)
    assert len(calls) == 2              # nor by the read


# -- the engine's surfaces ----------------------------------------------------


KW = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2,
          max_len=64, dtype=jnp.float32, attention="dense",
          pos_emb="rope", num_kv_heads=2)

PATHS = {
    "pipelined": dict(prefill_chunk=4),
    "decode_only": dict(prefill_chunk=None),
    "multi_step": dict(prefill_chunk=4, multi_step_k=4),
    "spec": dict(prefill_chunk=4, draft="ngram", spec_k=3),
    "paged": dict(prefill_chunk=4, paged=True, block_size=8),
    "sync_mixed": dict(prefill_chunk=4, pipeline=False),
    "sync_decode_only": dict(prefill_chunk=None, pipeline=False),
    "sync_multi_step": dict(prefill_chunk=4, multi_step_k=4,
                            pipeline=False),
}
PROGRAMS = {"pipelined": {"decode", "mixed"}, "decode_only": {"decode"},
            "multi_step": {"decode", "mixed", "multi"}, "spec": {"spec"},
            "paged": {"decode", "mixed"}, "sync_mixed": {"decode", "mixed"},
            "sync_decode_only": {"decode"},
            "sync_multi_step": {"decode", "mixed", "multi"}}
NEW_FIELDS = ("device_tick_ms", "device_starved_ms", "device_unasked_ms",
              "device_clock_err_ms")


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


def _submit(eng, n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [eng.submit(
        rng.integers(0, KW["vocab_size"], size=int(rng.integers(5, 13)))
        .astype(np.int32), max_new_tokens=int(rng.integers(6, 17)), seed=i)
        for i in range(n)]


def _ticks(eng):
    return [s for s in eng.flight.snapshots() if s["kind"] == "tick"]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_flight_tick_carries_the_clock_fields(lm, path):
    eng = _engine(lm, **PATHS[path])
    _submit(eng)
    eng.drain()
    ticks = _ticks(eng)
    assert len(ticks) >= 12
    for t in ticks:
        assert t["program"] in PROGRAMS[path], t["program"]
        assert t["program"] == (
            "spec" if "draft_tokens" in t else "multi" if "multi_k" in t
            else "mixed" if t["prefill_tokens"] else "decode")
        for f in NEW_FIELDS:
            assert t[f] >= 0.0, (f, t[f])
        assert not (t["device_starved_ms"] and t["device_unasked_ms"])
        # the old fields keep what they held
        assert t["device_ms"] == t["dispatch_ms"] + t["device_wait_ms"]
    seen = {t["program"] for t in ticks}
    # (a multi-step engine's all-decode ticks are windows)
    assert seen == PROGRAMS[path] - (
        {"decode"} if "multi" in seen else set())
    assert ticks[0]["device_starved_ms"] == 0.0  # nothing ran before it
    s = eng.stats()
    assert s["device_clock_ticks"] == len(ticks) == eng.ticks
    total = (s["device_busy_ms"] + s["device_starved_ms"]
             + s["device_unasked_ms"])
    assert total == pytest.approx(sum(
        t["device_tick_ms"] + t["device_starved_ms"]
        + t["device_unasked_ms"] for t in ticks))
    assert abs(total - s["device_clock_span_ms"]) <= (
        s["device_clock_err_ms"] + 1e-6)
    assert 0.0 <= s["device_clock_exact_pct"] <= 100.0
    for program in ("decode", "mixed", "multi", "spec"):
        mean = s[f"device_{program}_tick_ms"]
        own = [t["device_tick_ms"] for t in ticks if t["program"] == program]
        assert (mean is None) == (not own)
        if own:
            assert mean == pytest.approx(sum(own) / len(own))
    if not eng.pipeline:
        # the alternating loop: every read blocks, and the host's time
        # between two ticks is the device's to wait
        assert s["device_clock_exact_pct"] == 100.0 or \
            jax.default_backend() == "cpu"
        assert s["device_starved_ms"] > 0.0


def test_mark_steady_zeroes_the_sums_and_leaves_the_flight_ring(lm):
    eng = _engine(lm, prefill_chunk=4)
    _submit(eng, 3)
    eng.drain()
    before = len(_ticks(eng))
    assert eng.stats()["device_busy_ms"] > 0.0
    eng.mark_steady()
    s = eng.stats()
    assert s["device_clock_ticks"] == 0
    assert s["device_busy_ms"] == s["device_starved_ms"] == 0.0
    assert s["device_unasked_ms"] == s["device_clock_err_ms"] == 0.0
    assert s["device_starved_pct"] is None
    assert s["device_decode_tick_ms"] is None
    assert s["device_clock_span_ms"] == 0.0
    assert len(_ticks(eng)) == before
    _submit(eng, 2, seed=1)
    eng.drain()
    after = _ticks(eng)[before:]
    s = eng.stats()
    assert s["device_clock_ticks"] == len(after)
    # the gap before the first dispatch after the mark is on its record
    # (nothing ran since the drain) and not in the sums
    assert after[0]["device_starved_ms"] > 0.0
    assert s["device_starved_ms"] == pytest.approx(
        sum(t["device_starved_ms"] for t in after[1:]))


def test_a_doze_in_serve_forever_is_unasked(lm):
    import threading

    eng = _engine(lm, prefill_chunk=4)
    stop = threading.Event()
    th = threading.Thread(target=eng.serve_forever, args=(stop,))
    th.start()
    try:
        for seed in range(2):
            for r in _submit(eng, 2, seed=seed):
                r.stream.tokens(timeout=60)
            # the loop dozes until the next request comes
            while eng.stats()["active_slots"]:
                pass
            stop.wait(0.02)
    finally:
        stop.set()
        th.join()
    ticks = _ticks(eng)
    assert sum(t["device_unasked_ms"] > 0 for t in ticks) >= 1
    assert all(t["idle_ms"] > 0 for t in ticks if t["device_unasked_ms"])
    assert eng.stats()["device_unasked_pct"] > 0.0


# -- what the stale device_ms fed ---------------------------------------------


class _Late:
    """A tick's tokens under the made-up device: ready ``tick_ms`` after
    the tick starts; reading them passes the made-up time to their end,
    as a blocking read does."""

    def __init__(self, toks, time, ready_at):
        self.toks, self.time, self.ready_at = toks, time, ready_at

    def is_ready(self):
        return self.time.t >= self.ready_at

    def __array__(self, *a, **kw):
        self.time.t = max(self.time.t, self.ready_at)
        return np.asarray(self.toks)


class _MadeUpDevice(_DeviceClock):
    """The engine's clock with the host's every look at it costing
    ``look_ms`` and every tick taking ``TICK_MS`` of a device that runs
    one at a time."""

    TICK_MS = 7.5

    def __init__(self, look_ms):
        self.time = Time()
        self.look_ms = look_ms
        self.free_at = 0.0
        super().__init__(now=self._look)

    def _look(self):
        self.time.pass_ms(self.look_ms)
        return self.time.t

    def dispatched(self, rec):
        super().dispatched(rec)
        self.free_at = max(rec.dispatching_t, self.free_at) + \
            self.TICK_MS / 1e3
        rec.toks = _Late(rec.toks, self.time, self.free_at)


def _with_made_up_device(eng, look_ms):
    clock = _MadeUpDevice(look_ms)
    eng._clock = clock
    eng._phase.boundary = clock.boundary
    return clock


@pytest.mark.parametrize("look_ms,blocks", [(0.25, True), (1.0, False)],
                         ids=["read_blocks", "read_does_not_block"])
def test_a_tick_reports_its_device_time_not_the_overlaps_residue(
        lm, look_ms, blocks):
    """``serving_token_ms``, ``serving_decode_tokens_per_sec``, the
    ``token_ms`` of ``stats()`` and of the per-tick log and every
    request's ``device_ms_accum`` take the clock's ``device_tick_ms``: a
    tick whose read did not block still reports 7.5 ms of the made-up
    device, where ``dispatch_ms + wait_ms`` has only what the overlap
    left."""
    from distkeras_tpu.utils.metrics import MetricsWriter

    writer = MetricsWriter()
    model, params = lm
    eng = ServingEngine(model, params, slots=3, prefill_chunk=4,
                        registry=telemetry.MetricRegistry(),
                        tracer=telemetry.Tracer(), metrics=writer)
    _with_made_up_device(eng, look_ms)
    reqs = _submit(eng)
    eng.drain()
    ticks = _ticks(eng)
    steady = ticks[2:-1]
    for t in steady:
        assert abs(t["device_tick_ms"] - 7.5) <= \
            t["device_clock_err_ms"] + 1e-6
        assert (t["device_clock_err_ms"] == 0.0) == blocks
        if blocks:
            assert t["device_tick_ms"] == pytest.approx(7.5)
            assert t["device_starved_ms"] == 0.0
    if not blocks:
        # the host is the slower side: the device waits for it
        assert sum(t["device_starved_ms"] for t in steady) > 0.0
        assert eng.stats()["device_clock_exact_pct"] < 50.0
    said = [t["device_tick_ms"] for t in ticks]
    hist = eng._m_tick_ms.value
    assert hist["count"] == len(ticks)
    assert hist["sum"] == pytest.approx(sum(said))
    # ... and not what the old formula held (the real machine's time)
    assert hist["sum"] != pytest.approx(sum(t["device_ms"] for t in ticks))
    assert eng._m_decode_tps.value == pytest.approx(
        ticks[-1]["emitted"] / (said[-1] / 1e3), abs=1e-3)
    logged = [r["token_ms"] for r in writer.records if "token_ms" in r]
    assert logged == [round(ms, 3) for ms in said]
    assert eng.stats()["token_ms"]["p50"] == pytest.approx(
        float(np.percentile(logged, 50)))
    # each tick's device time is split over the rows it left live
    accum = sum(r.device_ms_accum for r in reqs)
    assert 0.5 * sum(said) < accum <= sum(said) + 1e-6


def test_a_multi_step_windows_timestamps_are_its_device_time_over_k(
        lm, monkeypatch):
    eng = _engine(lm, prefill_chunk=4, multi_step_k=4)
    _with_made_up_device(eng, 1.0)
    seen = []
    stream_row = eng._stream_row

    def spy(s, st, toks_row, now, defer=None, times=None):
        if times is not None and len(times) > 1:
            seen.append((eng.ticks, np.diff(times)))
        return stream_row(s, st, toks_row, now, defer, times=times)

    monkeypatch.setattr(eng, "_stream_row", spy)
    _submit(eng)
    eng.drain()
    by_tick = {t["tick"]: t for t in _ticks(eng)}
    assert len(seen) >= 3
    for tick, gaps in seen:
        t = by_tick[tick]
        assert t["program"] == "multi"
        assert gaps == pytest.approx(
            t["device_tick_ms"] / 1e3 / t["multi_k"])
        assert t["device_tick_ms"] > 1.0


# -- nothing grows with the ticks ---------------------------------------------


def _sizes(root, limit=6):
    """The length of every list, deque, dict and set reachable from
    ``root`` through attributes and containers of this package's
    objects."""
    out, seen, stack = {}, set(), [("eng", root, 0)]
    while stack:
        path, obj, depth = stack.pop()
        if id(obj) in seen or depth > limit:
            continue
        seen.add(id(obj))
        if isinstance(obj, (list, deque, dict, set, tuple)):
            # (a deque with a bound fills up and stays there)
            if not isinstance(obj, tuple) and \
                    getattr(obj, "maxlen", None) is None:
                out[path] = len(obj)
            items = obj.values() if isinstance(obj, dict) else obj
            if len(obj) <= 64:
                for i, v in enumerate(items):
                    stack.append((f"{path}[{i}]", v, depth + 1))
        elif type(obj).__module__.startswith("distkeras_tpu") and \
                hasattr(obj, "__dict__"):
            for k, v in vars(obj).items():
                stack.append((f"{path}.{k}", v, depth + 1))
    return out


def test_an_engine_handed_no_writer_holds_nothing_that_grows(
        lm, monkeypatch):
    monkeypatch.setattr(ServingEngine, "STATS_RECENT", 64)
    model, params = lm
    eng = ServingEngine(model, params, slots=3, prefill_chunk=4,
                        flight_capacity=64,
                        registry=telemetry.MetricRegistry(),
                        tracer=telemetry.Tracer(capacity=64))
    assert eng.metrics is None
    rng = np.random.default_rng(0)

    def run_to(ticks):
        n = 0
        while eng.ticks < ticks:
            while eng.scheduler.depth() < 4:
                eng.submit(rng.integers(0, 64, size=6).astype(np.int32),
                           max_new_tokens=50, seed=n)
                n += 1
            eng.step()
        eng.drain()

    run_to(300)
    early, stats_early = _sizes(eng), eng.stats()
    run_to(10_000)
    late, stats_late = _sizes(eng), eng.stats()
    assert eng.ticks >= 10_000
    grown = {k: (early.get(k), v) for k, v in late.items()
             if v > early.get(k, 0) + 8}
    assert not grown, grown
    assert sum(late.values()) <= sum(early.values()) + 64
    # the two keys keep their shape, over the latest observations
    for s in (stats_early, stats_late):
        assert sorted(s["ttft_ms"]) == sorted(s["token_ms"]) == [
            "p50", "p90", "p99"]
    assert len(eng._token_ms_recent) == len(eng._ttft_recent) == 64
    assert stats_late["device_clock_ticks"] == eng.ticks


def test_stats_keys_before_any_tick(lm):
    s = _engine(lm, prefill_chunk=4).stats()
    assert s["ttft_ms"] is None and s["token_ms"] is None
    assert s["device_clock_ticks"] == 0 and s["device_busy_ms"] == 0.0


# -- report --flight ----------------------------------------------------------


def test_report_flight_prints_the_device_tick_line(tmp_path, capsys):
    path = tmp_path / "flight.jsonl"
    ticks = []
    for i in range(20):
        mixed = i % 4 == 0
        ticks.append({
            "kind": "tick", "tick": i + 1, "t": 0.01 * i,
            "tick_ms": 9.0, "plan_ms": 1.0, "device_ms": 7.0,
            "stream_ms": 1.0, "occupancy": 2, "queue_depth": 0,
            "decode_tokens": 2, "prefill_tokens": 4 if mixed else 0,
            "program": "mixed" if mixed else "decode",
            "device_tick_ms": 30.0 if mixed else 8.0,
            "device_starved_ms": 2.0 if i == 5 else 0.0,
            "device_unasked_ms": 4.0 if i == 9 else 0.0,
            "device_clock_err_ms": 0.5 if i in (5, 6) else 0.0})
    with open(path, "w") as fh:
        fh.write(json.dumps({"kind": "flight_meta", "reason": "manual",
                             "recorded": 20}) + "\n")
        for t in ticks:
            fh.write(json.dumps(t) + "\n")
    telemetry_report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    whole = 5 * 30.0 + 15 * 8.0 + 6.0
    assert (f"device tick: decode p50 8.00 p95 8.00, mixed p50 30.00 "
            f"p95 30.00; starved {100 * 2.0 / whole:.1f} %, "
            f"unasked {100 * 4.0 / whole:.1f} %, exact 90.0 %") in out


def test_report_flight_of_a_recorded_engine_dump(lm, tmp_path, capsys):
    eng = _engine(lm, prefill_chunk=4)
    _submit(eng)
    eng.drain()
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    telemetry_report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("device tick: "))
    assert "decode p50" in line and "mixed p50" in line
    assert "starved" in line and "unasked" in line and "exact" in line
    # a dump from before the clock renders without the line
    old = tmp_path / "old.jsonl"
    with open(path) as src, open(old, "w") as dst:
        for ln in src:
            rec = json.loads(ln)
            for f in NEW_FIELDS + ("program",):
                rec.pop(f, None)
            dst.write(json.dumps(rec) + "\n")
    telemetry_report.main(["--flight", str(old)])
    assert "device tick:" not in capsys.readouterr().out


def test_the_module_keeps_no_writer_by_default():
    assert "MetricsWriter()" not in open(engine_mod.__file__).read()
