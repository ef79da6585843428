"""Flight recorder, SLO watchdog, runtime introspection (PR 5).

Acceptance coverage: a crash inside ``ServingEngine.step()`` and a
simulated stall each produce a postmortem dump that ``report --flight``
renders; the recompile counter reads zero in steady state; the flight
recorder's self-measured overhead stays a small fraction of tick time.
"""

import glob
import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.telemetry import (
    FlightRecorder,
    SloMonitor,
    SloRule,
    StallWatchdog,
    default_serving_rules,
)
from distkeras_tpu.telemetry import report as telemetry_report
from distkeras_tpu.telemetry.runtime import (
    MemoryWatermarks,
    RecompileCounter,
    host_rss_bytes,
)

KW = dict(vocab_size=64, d_model=32, num_heads=2, num_layers=2,
          max_len=48, dtype=jnp.float32, attention="dense")


def _model_and_params(seed=0):
    from distkeras_tpu.models import get_model

    model = get_model("transformer_lm", **KW)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _engine(tmp_path, **kw):
    from distkeras_tpu.serving import ServingEngine

    model, params = _model_and_params()
    return ServingEngine(
        model, params, registry=telemetry.MetricRegistry(),
        tracer=telemetry.Tracer(), postmortem_dir=str(tmp_path),
        **{"slots": 2, **kw},
    )


# -- FlightRecorder unit ----------------------------------------------------


def test_flight_ring_bound_and_dump(tmp_path):
    fl = FlightRecorder(capacity=3, postmortem_dir=str(tmp_path))
    for i in range(5):
        fl.record({"kind": "tick", "tick": i, "tick_ms": float(i)})
    assert len(fl) == 3 and fl.dropped == 2
    snaps = fl.snapshots()
    assert [s["tick"] for s in snaps] == [2, 3, 4]  # oldest aged out
    assert [s["tick"] for s in fl.snapshots(last=1)] == [4]
    path = tmp_path / "dump.jsonl"
    n = fl.dump(str(path), reason="manual", note="x")
    assert n == 3
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert lines[0]["kind"] == "flight_meta"
    assert lines[0]["reason"] == "manual" and lines[0]["note"] == "x"
    assert lines[0]["dropped"] == 2
    assert [r["tick"] for r in lines[1:]] == [2, 3, 4]
    fl.clear()
    assert len(fl) == 0 and fl.dropped == 0


def test_flight_postmortem_naming_and_fallback(tmp_path):
    fl = FlightRecorder(postmortem_dir=str(tmp_path))
    fl.record({"kind": "tick", "tick": 1})
    p1 = fl.dump_postmortem("crash", error="boom")
    p2 = fl.dump_postmortem("crash")
    assert p1 != p2  # sequence-numbered: dumps never clobber
    assert p1.startswith(str(tmp_path))
    assert telemetry.POSTMORTEM_PREFIX in p1
    meta = json.loads(open(p1).readline())
    assert meta["reason"] == "crash" and meta["error"] == "boom"
    # unwritable primary dir falls back to /tmp rather than raising
    fl2 = FlightRecorder(postmortem_dir=str(tmp_path / "nope" / "deeper"))
    p3 = fl2.dump_postmortem("stall")
    assert p3.startswith("/tmp/")
    import os

    os.unlink(p3)


# -- runtime introspection --------------------------------------------------


def test_recompile_counter_and_marks():
    rc = RecompileCounter()
    assert rc.total() == 0 and rc.counts() == {}
    rc.note("f")
    rc.note("f")
    rc.note("g")
    assert rc.total() == 3 and rc.counts() == {"f": 2, "g": 1}
    mark = rc.mark()
    assert rc.since(mark) == {}
    rc.note("g")
    assert rc.since(mark) == {"g": 1}


def test_host_rss_and_watermarks():
    rss = host_rss_bytes()
    assert rss is not None and rss > 10 * 1024 * 1024  # linux CI: >10MB
    wm = MemoryWatermarks()
    wm.sample_host()
    assert wm.rss_peak_bytes >= rss // 2
    wm.sample_device(None)
    assert wm.device_supported is False
    assert "device_mb" not in wm.summary()  # unsupported backend: omitted
    wm2 = MemoryWatermarks()
    wm2.sample_device({"bytes_in_use": 100, "peak_bytes_in_use": 250})
    wm2.sample_device({"bytes_in_use": 50})
    s = wm2.summary()
    assert wm2.device_bytes == 50 and wm2.device_peak_bytes == 250
    assert s["device_peak_mb"] == round(250 / 2**20, 1)


def test_engine_steady_state_recompiles_zero(tmp_path):
    """The acceptance criterion the bench smoke also asserts: after a
    warmup request has traced every shape, further same-shape requests
    trace nothing."""
    eng = _engine(tmp_path)
    r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    eng.drain()
    r.stream.tokens(timeout=10)
    assert eng.stats()["recompiles"]  # warmup did trace
    eng.mark_steady()
    for _ in range(3):
        r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        eng.drain()
        r.stream.tokens(timeout=10)
    assert eng.recompiles_since_mark() == {}
    assert eng.stats()["recompiles_since_mark"] == {}


# -- engine flight integration ----------------------------------------------


def test_engine_records_tick_snapshots(tmp_path):
    eng = _engine(tmp_path)
    reqs = [eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
            for _ in range(3)]
    eng.drain()
    for r in reqs:
        r.stream.tokens(timeout=10)
    snaps = eng.flight.snapshots()
    assert len(snaps) == eng.ticks
    for s in snaps:
        assert s["kind"] == "tick"
        assert s["tick_ms"] >= s["device_ms"] > 0
        assert {"plan_ms", "stream_ms", "occupancy", "queue_depth",
                "budget_limit", "decode_tokens", "prefill_tokens",
                "emitted", "slots", "recompiles"} <= set(s)
        assert len(s["slots"]) == eng.slots
    # ticks are monotonically numbered and the first sampled memory
    assert [s["tick"] for s in snaps] == list(range(1, eng.ticks + 1))
    assert "mem" in snaps[0] and snaps[0]["mem"]["rss_mb"] > 0
    # everything JSON-clean (the msgpack/HTTP surfaces send it as-is)
    json.dumps(snaps)
    st = eng.stats()
    assert st["flight"]["recorded"] == eng.ticks
    assert 0.0 <= st["flight"]["overhead_frac"] < 0.5
    assert st["memory"]["rss_mb"] > 0


def test_engine_flight_disabled(tmp_path):
    eng = _engine(tmp_path, flight=None)
    assert eng.flight is None
    r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    eng.drain()
    r.stream.tokens(timeout=10)
    assert "flight" not in eng.stats()


def test_paged_engine_snapshot_blocks(tmp_path):
    eng = _engine(tmp_path, paged=True, block_size=8)
    r = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    eng.drain()
    r.stream.tokens(timeout=10)
    snaps = eng.flight.snapshots()
    assert all("blocks" in s for s in snaps)
    busy = [s for s in snaps if s["occupancy"] > 0]
    assert busy and all(s["blocks"]["in_use"] > 0 for s in busy)
    # the sampled tick carries the refcount decomposition too
    assert {"live", "cached"} <= set(snaps[0]["blocks"])


def test_crash_in_step_dumps_postmortem_and_renders(tmp_path, capsys):
    """Acceptance: an exception inside step() produces a postmortem that
    report --flight renders (nonzero ticks, the error in the header)."""
    eng = _engine(tmp_path)
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=8)
    for _ in range(3):
        eng.step()

    def boom():
        raise RuntimeError("injected device fault")

    eng._plan_dispatch_mixed = boom  # both loops plan a tick through it
    with pytest.raises(RuntimeError, match="injected device fault"):
        eng.step()
    dumps = glob.glob(str(tmp_path / "distkeras-postmortem-*-crash-*"))
    assert len(dumps) == 1
    assert eng.registry.counter(
        "serving_engine_crashes_total").value == 1
    capsys.readouterr()  # drop the engine's stderr notice
    telemetry_report.main(["--flight", dumps[0]])
    out = capsys.readouterr().out
    assert "reason=crash" in out
    assert "RuntimeError: injected device fault" in out
    assert "phase share" in out and "slowest ticks" in out


def test_stall_watchdog_fires_postmortem_and_renders(tmp_path, capsys):
    """Acceptance: a simulated stall (work pending, step() never called)
    fires the watchdog exactly once per episode and the dump renders."""
    eng = _engine(tmp_path, slots=1)
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    eng.step()  # one real tick so the dump has content
    wd = eng.watchdog(timeout_s=5.0)
    assert not wd.check(now=100.0)  # first observation arms the mark
    assert not wd.check(now=104.0)  # within timeout
    assert wd.check(now=106.0)      # fired
    assert wd.stalled and not wd.check(now=200.0)  # once per episode
    assert eng.registry.counter("slo_stalls_total").value == 1
    dumps = glob.glob(str(tmp_path / "distkeras-postmortem-*-stall-*"))
    assert len(dumps) == 1 and wd.last_dump == dumps[0]
    telemetry_report.main(["--flight", dumps[0]])
    out = capsys.readouterr().out
    assert "reason=stall" in out and "stuck_s=" in out
    spans = {s["span"] for s in eng.tracer.dump()}
    assert "slo.stall" in spans
    # progress resumes -> episode resets -> a new stall can fire
    eng.step()
    assert not wd.check(now=300.0)
    assert not wd.stalled
    assert {"slo.stall_recovered"} <= {s["span"] for s in eng.tracer.dump()}


def test_watchdog_idle_engine_never_fires(tmp_path):
    eng = _engine(tmp_path)  # no requests: not busy
    wd = eng.watchdog(timeout_s=0.01)
    assert not wd.check(now=0.0)
    assert not wd.check(now=100.0)
    assert eng.registry.counter("slo_stalls_total").value == 0


def test_watchdog_thread_lifecycle(tmp_path):
    eng = _engine(tmp_path, slots=1)
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    wd = eng.watchdog(timeout_s=0.05, interval_s=0.01).start()
    assert wd.start() is wd  # idempotent
    import time

    t_end = time.monotonic() + 10
    while not wd.stalled and time.monotonic() < t_end:
        time.sleep(0.01)
    wd.stop()
    assert wd.stalled and wd.last_dump


# -- SloMonitor -------------------------------------------------------------


def test_slo_rule_validation():
    with pytest.raises(ValueError):
        SloRule("r", "m", kind="p75")
    with pytest.raises(ValueError):
        SloRule("r", "m", windows=())
    with pytest.raises(ValueError):
        SloRule("r", "m", burn_threshold=0.0)
    with pytest.raises(ValueError):
        SloMonitor([SloRule("dup", "m"), SloRule("dup", "m2")],
                   registry=telemetry.MetricRegistry())


def test_slo_gauge_rule_fires_and_resolves():
    reg, tr = telemetry.MetricRegistry(), telemetry.Tracer()
    g = reg.gauge("serving_queue_depth", "q")
    mon = SloMonitor(
        [SloRule("qd", "serving_queue_depth", "gauge", 4.0,
                 windows=(2.0, 6.0), burn_threshold=0.5)],
        registry=reg, tracer=tr,
    )
    t = 100.0
    g.set(1)
    for _ in range(8):
        mon.poll(now=t)
        t += 1.0
    assert not mon.poll(now=t)[0]["firing"]
    g.set(10)
    # must breach BOTH windows: the long (6 s) window needs >= 50%
    # breaching samples, so the alert is delayed past the short window
    fired = []
    for i in range(8):
        t += 1.0
        fired.append(mon.poll(now=t)[0]["firing"])
    assert not fired[0] and True in fired  # delayed, then fired
    a = [x for x in mon.alerts() if x["rule"] == "qd"][0]
    assert a["firing"] and a["since_s"] >= 0
    assert a["value"] == 10.0 and a["threshold"] == 4.0
    assert reg.counter("slo_alerts_total", labelnames=("rule",)) \
        .labels(rule="qd").value == 1
    assert reg.gauge("slo_alert_active", labelnames=("rule",)) \
        .labels(rule="qd").value == 1
    g.set(0)
    for _ in range(12):
        t += 1.0
        mon.poll(now=t)
    assert not mon.alerts()[0]["firing"]
    assert reg.gauge("slo_alert_active", labelnames=("rule",)) \
        .labels(rule="qd").value == 0
    spans = [s["span"] for s in tr.dump()]
    assert spans.count("slo.alert") == 1
    assert spans.count("slo.resolve") == 1


def test_slo_percentile_and_rate_rules():
    reg = telemetry.MetricRegistry()
    h = reg.histogram("serving_itl_ms", buckets=(10.0, 100.0, 1000.0))
    c = reg.counter("serving_requests_total", labelnames=("reason",))
    mon = SloMonitor(
        [SloRule("itl", "serving_itl_ms", "p99", 50.0, windows=(2.0, 4.0)),
         SloRule("exp", "serving_requests_total", "rate", 0.5,
                 labels=(("reason", "expired"),), windows=(2.0, 4.0))],
        registry=reg, tracer=telemetry.Tracer(),
    )
    t = 0.0
    for _ in range(6):
        h.observe(500.0)                    # p99 ~ beyond 100ms
        c.labels(reason="expired").inc(2)   # 2/s
        t += 1.0
        out = {a["rule"]: a for a in mon.poll(now=t)}
    assert out["itl"]["firing"] and out["itl"]["value"] > 50.0
    assert out["exp"]["firing"] and out["exp"]["value"] == pytest.approx(2.0)


def test_slo_unregistered_metric_is_inert():
    mon = SloMonitor([SloRule("ghost", "no_such_metric", "gauge", 1.0)],
                     registry=telemetry.MetricRegistry(),
                     tracer=telemetry.Tracer())
    for t in range(200):
        out = mon.poll(now=float(t))
    assert not out[0]["firing"] and out[0]["value"] is None


def test_default_serving_rules_cover_issue_objectives():
    names = {r.name for r in default_serving_rules()}
    assert names == {"itl_p99_ms", "ttft_p99_ms", "queue_depth",
                     "expiry_rate"}


def test_slo_monitor_thread_lifecycle():
    reg = telemetry.MetricRegistry()
    reg.gauge("serving_queue_depth", "q").set(100)
    mon = SloMonitor(
        [SloRule("qd", "serving_queue_depth", "gauge", 1.0,
                 windows=(0.01, 0.02))],
        registry=reg, tracer=telemetry.Tracer(), interval_s=0.01,
    ).start()
    import time

    t_end = time.monotonic() + 10
    while time.monotonic() < t_end:
        if any(a["firing"] for a in mon.alerts()):
            break
        time.sleep(0.01)
    mon.stop()
    assert any(a["firing"] for a in mon.alerts())


# -- serving surfaces: msgpack ops + HTTP endpoints -------------------------


def test_server_flight_and_alerts_ops(tmp_path):
    from distkeras_tpu.serving import LMServer, ServingClient

    eng = _engine(tmp_path)
    mon = SloMonitor(default_serving_rules(), registry=eng.registry,
                     tracer=eng.tracer, interval_s=0.05)
    srv = LMServer(eng, slo=mon, watchdog_timeout_s=60.0).start()
    try:
        cl = ServingClient("127.0.0.1", srv.port)
        rid = cl.generate(list(range(1, 6)), max_new_tokens=4)
        toks, reason = cl.result(rid, timeout=60)
        assert len(toks) == 4
        fl = cl.flight()
        assert fl["meta"]["kind"] == "flight_meta"
        assert len(fl["ticks"]) >= 4
        assert len(cl.flight(last=2)["ticks"]) == 2
        alerts = cl.alerts()
        assert {a["rule"] for a in alerts} == {
            "itl_p99_ms", "ttft_p99_ms", "queue_depth", "expiry_rate"}
        cl.close()
    finally:
        srv.stop()


def test_server_flight_disabled_is_an_error(tmp_path):
    from distkeras_tpu.serving import LMServer, ServingClient

    eng = _engine(tmp_path, flight=None)
    srv = LMServer(eng).start()
    try:
        cl = ServingClient("127.0.0.1", srv.port)
        with pytest.raises(RuntimeError, match="flight recorder disabled"):
            cl.flight()
        assert cl.alerts() == []  # no monitor: empty, not an error
        cl.close()
    finally:
        srv.stop()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode()


def test_http_flight_and_alerts_endpoints(tmp_path):
    eng = _engine(tmp_path)
    r = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    eng.drain()
    r.stream.tokens(timeout=10)
    mon = SloMonitor(default_serving_rules(), registry=eng.registry,
                     tracer=eng.tracer)
    mon.poll()
    http = telemetry.TelemetryServer(
        registry=eng.registry, tracer=eng.tracer,
        flight=eng.flight, slo=mon,
    ).start()
    try:
        code, text = _get(f"http://127.0.0.1:{http.port}/flight")
        body = json.loads(text)
        assert code == 200 and len(body["ticks"]) == eng.ticks
        code, text = _get(f"http://127.0.0.1:{http.port}/flight?last=1")
        assert len(json.loads(text)["ticks"]) == 1
        code, text = _get(f"http://127.0.0.1:{http.port}/alerts")
        assert code == 200 and len(json.loads(text)) == 4
        # the new gauges are scrapeable as Prometheus text
        code, text = _get(f"http://127.0.0.1:{http.port}/metrics")
        assert "jax_recompiles" in text
        assert "process_rss_bytes" in text
        assert "serving_queue_oldest_wait_s" in text
        assert "slo_alert_active" in text
    finally:
        http.stop()


def test_http_flight_404_when_unwired():
    http = telemetry.TelemetryServer(
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
    ).start()
    try:
        for route in ("/flight", "/alerts"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://127.0.0.1:{http.port}{route}")
            assert ei.value.code == 404
    finally:
        http.stop()


# -- report --flight renderer ----------------------------------------------


def test_report_flight_renders_manual_dump(tmp_path, capsys):
    eng = _engine(tmp_path)
    reqs = [eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
            for _ in range(2)]
    eng.drain()
    for r in reqs:
        r.stream.tokens(timeout=10)
    path = tmp_path / "flight.jsonl"
    eng.flight.dump(str(path), reason="manual")
    telemetry_report.main(["--flight", str(path)])
    out = capsys.readouterr().out
    assert "reason=manual" in out
    assert "phase share" in out and "device" in out
    assert "tick_ms: p50" in out and "slowest ticks:" in out
    # the engine thread's whole period by phase
    assert "loop_ms: p50" in out and "upload" in out and "record" in out
    # what the attend copied in of the cache (the dense attend off the
    # chip: all of it)
    assert "kv_fetched/cache: p50 1.000  max 1.000  (needed p50 0." in out
    assert "memory at last sample" in out
    # --last truncates the timeline but not the summary
    telemetry_report.main(["--flight", str(path), "--last", "2"])
    out2 = capsys.readouterr().out
    assert out2.count("\n") < out.count("\n")
    assert f"{eng.ticks} ticks" in out2


def test_report_flight_rejects_trace_jsonl(tmp_path):
    path = tmp_path / "trace.jsonl"
    tr = telemetry.Tracer(path=str(path))
    tr.record(1, "queued", 0.0, 1.0)
    tr.close()
    with pytest.raises(SystemExit) as ei:
        telemetry_report.main(["--flight", str(path)])
    assert ei.value.code == 2


def test_meta_counts_read_under_one_lock_hold(tmp_path):
    """Regression (lock-discipline fix): meta() snapshots recorded and
    dropped under ONE lock hold. A deterministic torn-read probe: the
    ring holds 2 of 4 snapshots; the probe lock injects 3 more records
    the moment meta() first releases the lock. A consistent snapshot is
    (2, 0) [before the injection] or (4, 1) [after]; the pre-fix code
    (locked len(), then an unlocked `self.dropped` read) returns the
    impossible (2, 1)."""
    fl = FlightRecorder(capacity=4, postmortem_dir=str(tmp_path))
    fl.record({"kind": "tick", "tick": 0})
    fl.record({"kind": "tick", "tick": 1})

    real = fl._lock

    class ProbeLock:
        def __init__(self):
            self.injected = False

        def __enter__(self):
            return real.__enter__()

        def __exit__(self, *exc):
            out = real.__exit__(*exc)
            if not self.injected:
                self.injected = True
                fl._lock = real  # the injection records normally
                for i in range(3):
                    fl.record({"kind": "tick", "tick": 2 + i})
                fl._lock = self
            return out

    fl._lock = ProbeLock()
    try:
        m = fl.meta("scrape")
    finally:
        fl._lock = real
    assert (m["recorded"], m["dropped"]) in ((2, 0), (4, 1)), (
        f"torn recorded/dropped pair: {m['recorded']}, {m['dropped']}"
    )
