"""Continuous-batching serving engine: slot-refill parity with solo
generate(), same-tick EOS slot refill, queue backpressure/deadlines, the
generate() eos early-exit, and a localhost TCP smoke test."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import generate
from distkeras_tpu.serving import (
    FIFOScheduler,
    LMServer,
    QueueFullError,
    ServingClient,
    ServingEngine,
)

KW = dict(vocab_size=64, d_model=32, num_heads=2, num_layers=2,
          max_len=48, dtype=jnp.float32, attention="dense")


def _model_and_params(seed=0, **over):
    kw = dict(KW)
    kw.update(over)
    model = get_model("transformer_lm", **kw)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _solo(model, params, prompt, **cfg):
    """The reference stream: one B=1 generate() call, prompt stripped,
    truncated after the first eos (the engine stops emitting there)."""
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


def test_slot_refill_parity():
    """Every request served through the pooled continuously-batched cache
    emits exactly the tokens of a solo generate() call with the same
    seed/params — greedy and sampled alike, across slot refills."""
    model, params = _model_and_params()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (5, 8, 5, 8, 5)]
    cfgs = [
        dict(max_new_tokens=6),
        dict(max_new_tokens=9),
        dict(max_new_tokens=4, temperature=1.0, seed=7),
        dict(max_new_tokens=7, temperature=0.8, seed=3, top_k=8),
        dict(max_new_tokens=5, temperature=0.9, seed=11, top_p=0.9),
    ]
    eng = ServingEngine(model, params, slots=2)
    reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
    eng.drain()
    for p, c, r in zip(prompts, cfgs, reqs):
        assert r.stream.tokens(timeout=10) == _solo(model, params, p, **c)
        assert r.stream.finish_reason == "length"
    assert eng.requests_completed == 5
    # 2 slots over 5 requests: the pool was actually shared
    assert eng.stats()["mean_occupancy"] > 1.0


def test_parity_with_eos_gqa_int8_rope():
    """Parity again on the serving-realistic model config — rope + GQA +
    int8 KV cache — including an eos stop mid-stream."""
    model, params = _model_and_params(
        num_heads=4, num_kv_heads=2, cache_dtype="int8", pos_emb="rope",
        d_model=64,
    )
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, size=6).astype(np.int32)
               for _ in range(3)]
    # pick an eos that actually occurs: the 3rd greedily-decoded token
    probe = _solo(model, params, prompts[0], max_new_tokens=8)
    eos = probe[2]
    cfgs = [
        dict(max_new_tokens=8, eos_id=eos),
        dict(max_new_tokens=6),
        dict(max_new_tokens=5, temperature=1.0, seed=5, eos_id=eos),
    ]
    eng = ServingEngine(model, params, slots=2)
    reqs = [eng.submit(p, **c) for p, c in zip(prompts, cfgs)]
    eng.drain()
    for p, c, r in zip(prompts, cfgs, reqs):
        assert r.stream.tokens(timeout=10) == _solo(model, params, p, **c)
    assert reqs[0].stream.finish_reason == "eos"


@pytest.mark.parametrize("pipeline", [False, True])
def test_eos_frees_slot_same_tick(pipeline):
    """When a request samples its eos, its slot is refilled from the
    queue in the same step() call — the replacement's prompt chunk rides
    the very next tick, so the tick count for two back-to-back requests
    is the sum of their stream lengths plus exactly one prefill-chunk
    tick each (both prompts fit one default chunk), with no idle tick
    between. That is the strictly alternating loop (``pipeline=False``).
    The loop that runs a tick ahead reads the eos behind the next
    tick's dispatch: that tick's token for the row is dropped, the slot
    refills on the step after, and the eos costs one tick more. The
    replacement finishes by length, which the plan knows: no tick is
    spent on it."""
    model, params = _model_and_params()
    rng = np.random.default_rng(2)
    p1, p2 = (rng.integers(0, 64, size=6).astype(np.int32)
              for _ in range(2))
    probe = _solo(model, params, p1, max_new_tokens=10)
    eos = probe[3]  # req1 stops after 4 emitted tokens
    want1 = _solo(model, params, p1, max_new_tokens=10, eos_id=eos)
    want2 = _solo(model, params, p2, max_new_tokens=5)
    assert len(want1) == 4

    eng = ServingEngine(model, params, slots=1, pipeline=pipeline)
    r1 = eng.submit(p1, max_new_tokens=10, eos_id=eos)
    r2 = eng.submit(p2, max_new_tokens=5)
    saw_refill_tick = None
    while eng.step():
        if saw_refill_tick is None and r1.done_t is not None:
            # the step that completed r1 must already have admitted r2
            # (a tick ahead: the slot is free, the next step admits)
            saw_refill_tick = eng.ticks
            assert eng.slot_requests == ([None] if pipeline else [r2.rid])
    # r1: 1 chunk tick + 4 decode ticks, eos on the 5th
    assert saw_refill_tick == 1 + len(want1)
    assert r1.stream.tokens(timeout=10) == want1
    assert r2.stream.tokens(timeout=10) == want2
    # no idle ticks: every tick either fed a prompt chunk or emitted a
    # token for exactly one request (and, a tick ahead, the one whose
    # token for the finished row was dropped)
    assert eng.ticks == (1 + len(want1)) + (1 + len(want2)) + pipeline
    assert eng.stats()["overrun_tokens"] == int(pipeline)


def test_queue_backpressure_and_deadline():
    model, params = _model_and_params()
    sched = FIFOScheduler(max_queue_depth=2, tick_token_budget=64)
    eng = ServingEngine(model, params, slots=1, scheduler=sched)
    p = np.zeros(4, np.int32)
    eng.submit(p, max_new_tokens=2)
    eng.submit(p, max_new_tokens=2)
    with pytest.raises(QueueFullError):
        eng.submit(p, max_new_tokens=2)
    # deadline already passed when the engine gets to it: expired, not
    # decoded — and the expiry frees queue room
    r_dead = None
    # drain the two live ones first so the queue has room again
    eng.drain()
    r_dead = eng.submit(p, max_new_tokens=2, deadline_s=0.0)
    time.sleep(0.01)
    eng.drain()
    assert r_dead.stream.tokens(timeout=10) == []
    assert r_dead.stream.finish_reason == "expired"


def test_expired_request_leaves_finish_span():
    """Satellite: a queued-deadline expiry is finished by the SCHEDULER
    with a full span chain (queued → finish reason=expired), so expired
    requests show in trace dumps instead of vanishing."""
    from distkeras_tpu import telemetry

    tracer = telemetry.Tracer()
    model, params = _model_and_params()
    eng = ServingEngine(model, params, slots=1, tracer=tracer,
                        registry=telemetry.MetricRegistry())
    p = np.zeros(4, np.int32)
    r = eng.submit(p, max_new_tokens=2, deadline_s=0.0)
    time.sleep(0.01)
    eng.drain()
    assert r.stream.tokens(timeout=10) == []
    spans = {s["span"]: s for s in tracer.dump(trace=r.trace_id)}
    assert set(spans) == {"queued", "finish"}
    assert spans["finish"]["reason"] == "expired"
    # and the finish-reason counter saw it
    assert eng.registry.counter(
        "serving_requests_total",
        labelnames=("reason",)).labels(reason="expired").value == 1


def test_client_request_timeout_names_request():
    """Satellite: ServingClient's constructor-level request_timeout is
    inherited by _call/result, and a stalled wait raises TimeoutError
    naming the op/request instead of a bare queue.Empty."""
    model, params = _model_and_params()
    eng = ServingEngine(model, params, slots=1)
    server = LMServer(eng).start()
    try:
        client = ServingClient("127.0.0.1", server.port,
                               request_timeout=0.05)
        assert client.request_timeout == 0.05
        # no request with this id ever streams: result() must time out
        # with the rid in the message
        with pytest.raises(TimeoutError, match="request 12345"):
            client.result(12345)
        # per-call override still wins
        with pytest.raises(TimeoutError, match="request 12345"):
            client.result(12345, timeout=0.01)
        # a live request still works under the short default
        client2 = ServingClient("127.0.0.1", server.port,
                                request_timeout=30.0)
        p = np.arange(1, 6, dtype=np.int32)
        rid = client2.generate(p, max_new_tokens=3)
        toks, reason = client2.result(rid)
        assert toks == _solo(model, params, p, max_new_tokens=3)
        assert reason == "length"
        client.close()
        client2.close()
    finally:
        server.stop()


def test_a_reply_that_comes_after_its_call_gave_up_is_not_the_next_calls():
    """Acks carry no id: the reply to a call that timed out (a profile's
    export held every thread for longer than the wait, PR 41) used to be
    taken for the next call's, and ``flight()`` then found a generate
    ack. A peer that answers its first frame late, then at once."""
    import socket

    from distkeras_tpu.networking import recv_msg, send_msg

    listener = socket.create_server(("127.0.0.1", 0))
    late = threading.Event()

    def peer():
        conn, _ = listener.accept()
        with conn:
            recv_msg(conn)
            late.wait(10)
            send_msg(conn, {"ok": 1, "id": 7, "trace": None})
            while recv_msg(conn) is not None:
                send_msg(conn, {"ok": 1, "stats": {"mine": 1}})

    t = threading.Thread(target=peer, daemon=True)
    t.start()
    client = ServingClient("127.0.0.1", listener.getsockname()[1],
                           request_timeout=0.2)
    try:
        with pytest.raises(TimeoutError, match="'generate'"):
            client.generate([1, 2], 3)
        late.set()
        assert client.stats() == {"mine": 1}
        assert client.stats() == {"mine": 1}
    finally:
        client.close()
        listener.close()
        t.join(5)


def test_submit_validation():
    model, params = _model_and_params()
    eng = ServingEngine(model, params, slots=1)
    with pytest.raises(ValueError):  # overflows the per-slot cache
        eng.submit(np.zeros(40, np.int32), max_new_tokens=20)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(0, np.int32), max_new_tokens=4)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=0)


def test_generate_eos_early_exit():
    """Satellite: with eos_id set, generate()'s decode loop is a
    while_loop that stops once all rows are done — same eos-padded
    output, fewer decode steps."""
    model, params = _model_and_params()
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, 64, size=(1, 6)), jnp.int32)
    full = np.asarray(generate(model, params, prompt, 12))
    eos = int(full[0, 6 + 3])  # greedy row emits this at step 4
    done_at = list(full[0, 6:]).index(eos) + 1  # 4, unless it repeats
    out, steps = generate(model, params, prompt, 12, eos_id=eos,
                          return_steps=True)
    out = np.asarray(out)
    # early exit: the loop ran only to the step that finished the row
    assert steps == done_at < 12
    np.testing.assert_array_equal(
        out[0, : 6 + done_at], full[0, : 6 + done_at]
    )
    assert (out[0, 6 + done_at:] == eos).all()  # eos padding kept
    # no eos: the scan path reports the full step count
    _, steps_full = generate(model, params, prompt, 12, return_steps=True)
    assert steps_full == 12


def test_server_tcp_smoke():
    """Localhost end-to-end: submit over TCP, stream tokens back, check
    parity and the stats op, then shut down cleanly."""
    model, params = _model_and_params()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 64, size=5).astype(np.int32)
               for _ in range(3)]
    eng = ServingEngine(model, params, slots=2)
    server = LMServer(eng).start()
    try:
        client = ServingClient("127.0.0.1", server.port)
        rids = [client.generate(p, max_new_tokens=5) for p in prompts]
        for p, rid in zip(prompts, rids):
            toks, reason = client.result(rid, timeout=60)
            assert toks == _solo(model, params, p, max_new_tokens=5)
            assert reason == "length"
        stats = client.stats()
        assert stats["requests_completed"] == 3
        assert stats["tokens_generated"] == 15
        client.close()
    finally:
        server.stop()


def test_server_stats_under_concurrent_inflight_requests():
    """The stats/metrics ops answer correctly while requests are mid
    stream: stats frames interleave with token frames on the same
    connection without corrupting either, and the final counters agree
    with what was streamed."""
    from distkeras_tpu import telemetry

    model, params = _model_and_params()
    reg = telemetry.MetricRegistry()
    eng = ServingEngine(model, params, slots=2, registry=reg,
                        tracer=telemetry.Tracer())
    server = LMServer(eng).start()
    try:
        client = ServingClient("127.0.0.1", server.port)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 64, size=5).astype(np.int32)
                   for _ in range(4)]
        rids = [client.generate(p, max_new_tokens=12) for p in prompts]
        # hammer stats from a side thread while tokens stream
        polled, errors = [], []

        def poll():
            try:
                for _ in range(20):
                    polled.append(client.stats())
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        t = threading.Thread(target=poll)
        t.start()
        results = {rid: client.result(rid, timeout=60) for rid in rids}
        t.join(timeout=30)
        assert not errors
        assert len(polled) == 20
        # monotone progress visible through the op
        done_counts = [s["requests_completed"] for s in polled]
        assert done_counts == sorted(done_counts)
        for p, rid in zip(prompts, rids):
            toks, reason = results[rid]
            assert toks == _solo(model, params, p, max_new_tokens=12)
            assert reason == "length"
        final = client.stats()
        assert final["requests_completed"] == 4
        assert final["tokens_generated"] == 48
        # registry snapshot over the wire agrees
        metrics = client.metrics()
        series = metrics["serving_tokens_total"]["series"]
        assert series and series[0]["value"] == 48
        client.close()
    finally:
        server.stop()


def test_trace_id_roundtrip_via_client():
    """Satellite: the generate ack carries the trace id allocated at
    admission; trace_dump filtered to it returns the complete span chain
    (queued/prefill/decode/finish + the connection's stream span) with
    slot ids and token counts."""
    from distkeras_tpu import telemetry

    model, params = _model_and_params()
    eng = ServingEngine(model, params, slots=2,
                        registry=telemetry.MetricRegistry(),
                        tracer=telemetry.Tracer())
    server = LMServer(eng).start()
    try:
        client = ServingClient("127.0.0.1", server.port)
        p = np.arange(1, 7, dtype=np.int32)
        rid = client.generate(p, max_new_tokens=5)
        tid = client.trace_of(rid)
        assert tid is not None
        # stream path (not result()): tokens arrive as emitted
        toks = list(client.stream(rid))
        assert toks == _solo(model, params, p, max_new_tokens=5)
        # the engine records finish before the done frame is sent, so
        # the chain is complete the moment the stream ends; the stream
        # span itself is written by the pump thread right after done
        deadline = time.monotonic() + 5.0
        spans = {}
        while time.monotonic() < deadline:
            spans = {s["span"]: s for s in client.trace_dump(trace=tid)}
            if "stream" in spans:
                break
            time.sleep(0.01)
        assert set(spans) == {"queued", "prefill", "decode", "stream",
                              "finish"}
        assert spans["prefill"]["prompt_tokens"] == 6
        assert spans["decode"]["tokens"] == 5
        assert spans["stream"]["tokens"] == 5
        assert spans["finish"]["reason"] == "length"
        assert spans["finish"]["slot"] == spans["decode"]["slot"]
        assert all(s["trace"] == tid for s in spans.values())
        client.close()
    finally:
        server.stop()


def test_server_rejects_bad_requests():
    model, params = _model_and_params()
    eng = ServingEngine(model, params, slots=1)
    server = LMServer(eng).start()
    try:
        client = ServingClient("127.0.0.1", server.port)
        with pytest.raises(RuntimeError, match="max_len"):
            client.generate(list(range(40)), max_new_tokens=20)
        # typed unknown-op rejection: the terminal dispatch arm answers
        # {"error": "unknown_op", "op": ...} and the client raises the
        # typed error (still a RuntimeError for untyped callers),
        # echoing the rejected op — and the connection survives
        from distkeras_tpu.serving import UnknownOpError
        with pytest.raises(UnknownOpError, match="nope") as ei:
            client._call({"op": "nope"})
        assert ei.value.op == "nope"
        assert isinstance(ei.value, RuntimeError)
        assert "active_slots" in client.stats()  # conn still alive
        client.close()
    finally:
        server.stop()


def test_client_close_flips_flags_under_streams_lock():
    """Regression (lock-discipline fix): close() must mark the
    connection closed under _streams_lock — the same discipline as the
    reader thread's shutdown sweep — so _stream_q can never race a
    half-closed connection. Asserted via a counting probe lock."""
    model, params = _model_and_params()
    eng = ServingEngine(model, params, slots=1)
    server = LMServer(eng).start()
    try:
        client = ServingClient("127.0.0.1", server.port)
        real = client._streams_lock
        acquired = []

        class ProbeLock:
            def __enter__(self):
                acquired.append(True)
                return real.__enter__()

            def __exit__(self, *exc):
                return real.__exit__(*exc)

        client._streams_lock = ProbeLock()
        try:
            client.close()
        finally:
            client._streams_lock = real
        assert acquired, "close() must flip _closed under _streams_lock"
        assert client.closed and client.close_reason == "closed by client"
        client.close()  # still idempotent through the locked path
    finally:
        server.stop()


def test_lockorder_detector_is_armed_in_this_suite():
    """Meta-test: the conftest fixture must actually install the
    lock-order detector for this module (and engines/clients built
    here allocate tracked locks), otherwise the suite's 'no cycle'
    guarantee is vacuous."""
    import threading as _threading

    from distkeras_tpu.analysis import lockorder as _lo

    assert _threading.Lock is not _lo._REAL_LOCK, (
        "conftest _lock_order_guard did not install the detector"
    )
    probe = _threading.Lock()  # allocated from tests/: tracked
    assert type(probe).__name__ == "_TrackedLock"
    with probe:
        pass


@pytest.mark.parametrize("case", [
    "queued_before", "emitted_after", "ended_before", "iterated_as_before"])
def test_a_stream_forwarded_to_one_consumer(case):
    """``TokenStream.forward(sink)``: what the engine queued before the
    consumer came reaches the sink first and in order, what it emits
    after goes straight to the sink on the emitting thread, the end
    sets ``finish_reason`` either way; a stream nobody forwards is
    iterated as it always was."""
    from distkeras_tpu.serving.scheduler import TokenStream

    s, got = TokenStream(), []
    sink = lambda kind, val: got.append(  # noqa: E731
        (kind, val, threading.current_thread().name))
    me = threading.current_thread().name
    if case == "queued_before":
        for t in (5, 6, 7):
            s._put(t)
        s.forward(sink)
        assert got == [("tok", t, me) for t in (5, 6, 7)]
        assert s.finish_reason is None and s._q.empty()
    elif case == "emitted_after":
        s._put(5)
        s.forward(sink)
        t = threading.Thread(target=lambda: (s._put(6), s._finish("eos")),
                             name="engine-loop")
        t.start()
        t.join()
        assert got == [("tok", 5, me), ("tok", 6, "engine-loop"),
                       ("end", "eos", "engine-loop")]
        assert s.finish_reason == "eos" and s._q.empty()
    elif case == "ended_before":
        s._put(5)
        s._finish("length")
        s.forward(sink)
        assert [g[:2] for g in got] == [("tok", 5), ("end", "length")]
        assert s.finish_reason == "length"
    else:
        s._put(5)
        s._finish("length")
        assert list(s) == [5] and s.finish_reason == "length"
        s2 = TokenStream()
        s2._put(9)
        s2._finish("eos")
        assert s2.tokens(timeout=1.0) == [9] and s2.finish_reason == "eos"


def _pump_threads():
    return [t for t in threading.enumerate()
            if getattr(getattr(t, "_target", None), "__name__", "") == "_pump"]


@pytest.mark.parametrize("case", ["many_requests", "client_goes_away"])
def test_one_thread_a_connection_forwards_its_streams(case):
    """Every request of a connection reaches the client through ONE
    sender thread, whatever their number (a thread a request, each
    taking the send lock for a frame, stopped delivering at 2 400
    tokens a second on the chip's machine: PR 43): the ack first, then
    the request's tokens in order, then ``done`` with their count and
    the stream span already recorded; a client that goes away
    mid-stream leaves the engine running, the span marked aborted and
    no thread behind."""
    from distkeras_tpu import networking as net, telemetry

    model, params = _model_and_params()
    eng = ServingEngine(model, params, slots=3,
                        registry=telemetry.MetricRegistry(),
                        tracer=telemetry.Tracer())
    server = LMServer(eng).start()
    before = len(_pump_threads())
    sock = net.connect("127.0.0.1", server.port)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, size=n).astype(np.int32)
                   for n in (5, 7, 4, 6, 5, 8, 3)]
        new = 12 if case == "many_requests" else 40
        acks, toks, done = {}, {}, {}
        reader = net.MsgReader(sock)
        for p in prompts:
            net.send_msg(sock, {"op": "generate", "prompt": p.tolist(),
                                "max_new_tokens": new})
        while len(done) < len(prompts):
            for msg in reader.recv_msgs():
                if "ok" in msg:
                    acks[msg["id"]] = msg["trace"]
                    toks[msg["id"]] = []
                elif "t" in msg:
                    # never before its ack, never after its done
                    assert msg["id"] in acks and msg["id"] not in done
                    toks[msg["id"]].append(msg["t"])
                else:
                    done[msg["id"]] = msg
                    spans = [s for s in eng.tracer.dump(
                        trace=acks[msg["id"]]) if s["span"] == "stream"]
                    assert [s["tokens"] for s in spans] == [msg["n"]]
            if any(toks.values()):
                assert len(_pump_threads()) == before + 1
                if case == "client_goes_away":
                    break
        if case == "many_requests":
            rids = sorted(acks)  # rids count up in the order submitted
            for rid, p in zip(rids, prompts):
                assert toks[rid] == _solo(model, params, p,
                                          max_new_tokens=new)
                assert done[rid]["n"] == new
                assert done[rid]["reason"] == "length"
            return
        sock.close()
        deadline = time.monotonic() + 30.0
        aborted = []
        while time.monotonic() < deadline:
            aborted = [s for tid in acks.values()
                       for s in eng.tracer.dump(trace=tid)
                       if s["span"] == "stream" and s.get("aborted")]
            if len(_pump_threads()) == before and aborted:
                break
            time.sleep(0.02)
        assert aborted and len(_pump_threads()) == before
        # the engine finished them all; their tokens were dropped
        assert eng.stats()["requests_completed"] >= len(prompts) - len(done)
    finally:
        sock.close()
        server.stop()
