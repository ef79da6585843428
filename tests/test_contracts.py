"""Seeded-violation tests for the cross-boundary contract passes
(wire-contract, metric-contract, span-contract, host-sync-hazard):
each pass proves it catches a violation planted in a copy of the REAL
server.py / router.py / engine.py against the real trace.py partition
— same discipline as the seeded engine tests in test_analysis — plus
the protocol extraction/rendering round-trip and its CLI drift check,
and the real-tree landing state (clean modulo the justified
baseline)."""

import os
import textwrap

import pytest

import distkeras_tpu
from distkeras_tpu.analysis import Baseline, analyze, split_by_baseline
from distkeras_tpu.analysis.__main__ import main as analysis_main
from distkeras_tpu.analysis.core import iter_source_files
from distkeras_tpu.analysis.hostsync import HostSyncHazardPass
from distkeras_tpu.analysis.metrics_contract import MetricContractPass
from distkeras_tpu.analysis.spans import SpanContractPass
from distkeras_tpu.analysis.wire import (
    WireContractPass,
    extract_protocol,
    render_protocol_md,
)

PKG = os.path.dirname(os.path.abspath(distkeras_tpu.__file__))
REPO_ROOT = os.path.dirname(PKG)
SERVER = os.path.join(PKG, "serving", "server.py")
ROUTER = os.path.join(PKG, "serving", "router.py")
ENGINE = os.path.join(PKG, "serving", "engine.py")
TRACE = os.path.join(PKG, "telemetry", "trace.py")


def _mutate(tmp_path, src_path, old, new, name=None):
    """Copy a real module with one seeded edit; the anchor must exist
    so a refactor that moves it fails loudly here, not silently."""
    text = open(src_path).read()
    seeded = text.replace(old, new, 1)
    assert seeded != text, f"anchor not found in {src_path}: {old!r}"
    p = tmp_path / (name or os.path.basename(src_path))
    p.write_text(seeded)
    return str(p)


def _copy(tmp_path, src_path, name=None):
    p = tmp_path / (name or os.path.basename(src_path))
    p.write_text(open(src_path).read())
    return str(p)


def _keys(findings):
    return {f.key for f in findings}


# -- wire-contract -----------------------------------------------------------


def test_wire_real_tree_clean():
    findings = analyze([SERVER, ROUTER], passes=[WireContractPass()])
    assert findings == [], [f.render() for f in findings]


def test_wire_dropped_router_arm_is_unproxied(tmp_path):
    """Drop the router's trace_dump arm (the exact drift PR 8's
    wire-compatibility claim forbids): the pass pins Router._handle."""
    s = _copy(tmp_path, SERVER)
    r = _mutate(tmp_path, ROUTER,
                'elif op == "trace_dump":',
                'elif op == "trace_dump_disabled":')
    findings = analyze([s, r], passes=[WireContractPass()])
    hits = [f for f in findings if f.key == "unproxied-op.trace_dump"]
    assert hits and hits[0].path.endswith("router.py")
    assert "Router._handle" in hits[0].message


def test_wire_dropped_server_arm(tmp_path):
    """Drop LMServer's alerts arm: the client op becomes unhandled,
    the renamed arm unreachable, and the docstring op table stale."""
    s = _mutate(tmp_path, SERVER,
                'elif op == "alerts":', 'elif op == "alerts_gone":')
    keys = _keys(analyze([s], passes=[WireContractPass()]))
    assert "unhandled-op.alerts" in keys
    assert "unreachable-op.alerts_gone" in keys
    assert "doc-drift.stale.alerts" in keys
    assert "doc-drift.missing.alerts_gone" in keys


def test_wire_handler_reads_unsent_field(tmp_path):
    s = _mutate(
        tmp_path, SERVER,
        '{"ok": 1, "stats": self.engine.stats()}',
        '{"ok": 1, "stats": self.engine.stats(), "v": msg["verbose"]}')
    findings = analyze([s], passes=[WireContractPass()])
    hits = [f for f in findings
            if f.key == "unsent-field.stats.verbose"]
    assert hits and "LMServer._handle" in hits[0].message


def test_wire_client_reads_unset_reply_key(tmp_path):
    s = _mutate(tmp_path, SERVER,
                '{"ok": 1, "stats": self.engine.stats()}',
                '{"ok": 1, "stat": self.engine.stats()}')
    keys = _keys(analyze([s], passes=[WireContractPass()]))
    assert "unset-reply.LMServer.stats.stats" in keys


def test_wire_untyped_unknown_op_arm_flagged(tmp_path):
    """Degrade the typed terminal arm back to a free-form message: the
    handled op set is open-ended again and the pass says so."""
    s = _mutate(tmp_path, SERVER,
                '"ok": 0, "error": "unknown_op",\n'
                '                            "op": str(op),',
                '"ok": 0, "error": "unknown op!",\n'
                '                            "op": str(op),')
    keys = _keys(analyze([s], passes=[WireContractPass()]))
    assert "missing-unknown-op-arm.LMServer" in keys


def test_wire_suppression_comment_applies(tmp_path):
    """Project-pass findings honor the standard line suppression."""
    s = _mutate(tmp_path, SERVER,
                'elif op == "alerts":',
                'elif op == "alerts_gone":  # analysis: wire-ok')
    keys = _keys(analyze([s], passes=[WireContractPass()]))
    assert "unreachable-op.alerts_gone" not in keys
    assert "unhandled-op.alerts" in keys  # the client side still fires


# -- protocol extraction / rendering -----------------------------------------


def test_protocol_extraction_matches_dispatch():
    proto = extract_protocol(iter_source_files([SERVER, ROUTER]))
    ops = set(proto.server.arms)
    assert ops == {"generate", "stats", "metrics", "trace_dump",
                   "chrome_trace", "flight", "alerts", "drain",
                   "reconfigure", "export_kv", "import_kv",
                   "push_weights", "timeseries", "events"}
    assert set(proto.router.arms) == ops
    assert set(proto.client.ops) == ops
    assert proto.server.has_unknown_arm and proto.router.has_unknown_arm
    gen = proto.server.arms["generate"]
    assert gen.fields["prompt"][0] == "required"
    assert gen.fields["temperature"][0] == "optional"
    assert {"id", "trace"} <= gen.reply_keys
    assert proto.client.ops["generate"].wildcard  # **kw widening
    assert {"t", "done", "id", "reason"} <= set(proto.client.stream_reads)


def test_protocol_render_deterministic_and_checked_in():
    """The committed docs/PROTOCOL.md must round-trip: regenerate ->
    byte-identical (the CI lint job runs exactly this check)."""
    proto = extract_protocol(iter_source_files([SERVER, ROUTER]))
    text = render_protocol_md(proto)
    assert text == render_protocol_md(proto)
    on_disk = os.path.join(REPO_ROOT, "docs", "PROTOCOL.md")
    if os.path.isfile(on_disk):  # absent in an installed-package run
        assert open(on_disk).read() == text, (
            "docs/PROTOCOL.md drifted — regenerate with: python -m "
            "distkeras_tpu.analysis protocol --out docs/PROTOCOL.md"
        )


def test_protocol_cli_out_and_check(tmp_path, capsys):
    out = str(tmp_path / "PROTOCOL.md")
    assert analysis_main(["protocol", SERVER, ROUTER,
                          "--out", out]) == 0
    assert analysis_main(["protocol", SERVER, ROUTER,
                          "--check", out]) == 0
    with open(out, "a") as fh:
        fh.write("drifted\n")
    assert analysis_main(["protocol", SERVER, ROUTER,
                          "--check", out]) == 1
    assert "drift" in capsys.readouterr().out
    # unusable scan set: one-line error, exit 2 (report contract)
    empty = tmp_path / "empty"
    empty.mkdir()
    assert analysis_main(["protocol", str(empty)]) == 2


# -- metric-contract ---------------------------------------------------------


def test_metric_real_tree_clean():
    findings = analyze([PKG], passes=[MetricContractPass()])
    assert findings == [], [f.render() for f in findings]


def test_metric_label_rename_at_one_site(tmp_path):
    """Rename one label key at one use site of a real router family:
    the pass pins the site and names the family."""
    r = _mutate(tmp_path, ROUTER,
                "decision=decision).inc()",
                "why=decision).inc()")
    findings = analyze([r], passes=[MetricContractPass()])
    hits = [f for f in findings if f.key.startswith(
        "label-mismatch.router_requests_routed_total")]
    assert hits and "router_requests_routed_total" in hits[0].message
    assert hits[0].path.endswith("router.py")


def test_metric_declared_never_written(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""
        class M:
            def __init__(self, registry):
                self._m_live = registry.counter("live_total", "h")
                self._m_dead = registry.counter("dead_total", "h")

            def go(self):
                self._m_live.inc()
    """))
    keys = _keys(analyze([str(p)], passes=[MetricContractPass()]))
    assert keys == {"never-written.dead_total"}


def test_metric_unknown_family_reference(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""
        class M:
            def __init__(self, registry):
                self.registry = registry
                self._m = registry.counter("real_total", "h")

            def go(self):
                self._m.inc()
                rules = [SloRule("r", "ghost_slo_ms", "p99", 1.0)]
                return self.registry.get("ghost_total"), rules
    """))
    keys = _keys(analyze([str(p)], passes=[MetricContractPass()]))
    assert keys == {"unknown-family.ghost_total",
                    "unknown-family.ghost_slo_ms"}


def test_metric_kind_and_labelset_conflicts(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""
        def a(reg):
            reg.counter("x_total", "h").inc()

        def b(reg):
            reg.gauge("x_total", "h").set(1)

        def c(reg):
            m = reg.counter("y_total", "h", labelnames=("a",))
            m.labels(b="1").inc()
    """))
    keys = _keys(analyze([str(p)], passes=[MetricContractPass()]))
    assert "kind-mismatch.x_total" in keys
    assert "label-mismatch.y_total.b" in keys


# -- span-contract -----------------------------------------------------------


def test_span_real_tree_only_baselined_findings():
    """The landing state: the only span-contract findings on the real
    tree are the three justified baseline entries (training-side PS
    spans and the SLO stall incident span)."""
    findings = analyze([PKG], passes=[SpanContractPass()])
    bl = Baseline.load(os.path.join(REPO_ROOT, "analysis-baseline.txt"))
    new, accepted = split_by_baseline(findings, bl)
    assert new == [], [f.render() for f in new]
    assert {f.key for f in accepted} == {
        "unattributed-span.ps.*", "unattributed-span.ps.rpc.*",
        "unattributed-span.slo.stall",
    }


def test_span_renamed_decode_span_falls_out(tmp_path):
    """Rename the engine's decode span: critical_path() would silently
    shunt all decode time into the residual phase — the pass pins the
    record site in the engine copy."""
    e = _mutate(tmp_path, ENGINE,
                'req.trace_id, "decode", decode_t0, decode_ms,',
                'req.trace_id, "decode2", decode_t0, decode_ms,')
    findings = analyze([e, TRACE], passes=[SpanContractPass()])
    hits = [f for f in findings if f.key == "unattributed-span.decode2"]
    assert hits and hits[0].path.endswith("engine.py")


def test_span_unknown_phase_label_value(tmp_path):
    e = _mutate(tmp_path, ENGINE,
                '("queue", "prefill", "decode", "device")}',
                '("queue", "prefill", "decode", "gpu")}')
    keys = _keys(analyze([e, TRACE], passes=[SpanContractPass()]))
    assert "unknown-phase.gpu" in keys


def test_span_markers_and_partition_names_exempt(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""
        def go(tracer, tid, t0, ms):
            tracer.record(tid, "my.marker", t0, 0.0, detail=1)  # zero
            tracer.record(tid, "decode", t0, ms)                # known
            tracer.record(tid, "router.stream", t0, ms)         # known
    """))
    assert analyze([str(p), TRACE], passes=[SpanContractPass()]) == []


def test_span_no_partition_in_scan_set_is_silent(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("def go(tracer, tid, t0, ms):\n"
                 "    tracer.record(tid, 'mystery', t0, ms)\n")
    assert analyze([str(p)], passes=[SpanContractPass()]) == []


# -- host-sync-hazard --------------------------------------------------------


def test_hostsync_real_engine_clean():
    findings = analyze([ENGINE], passes=[HostSyncHazardPass()])
    assert findings == [], [f.render() for f in findings]


def test_hostsync_hoisted_readback_into_plan_body(tmp_path):
    """Hoist the reconcile-side np.asarray readback into the plan body
    (the exact regression that silently serializes the pipeline): the
    pass pins _plan_dispatch_mixed."""
    e = _mutate(
        tmp_path, ENGINE,
        "            if self.host is not None:\n"
        "                self._issue_restores()\n"
        "            S = self.slots\n",
        "            if self.host is not None:\n"
        "                self._issue_restores()\n"
        "            _peek = np.asarray(self._last_logits)\n"
        "            S = self.slots\n")
    findings = analyze([e], passes=[HostSyncHazardPass()])
    hits = [f for f in findings
            if f.key == "_plan_dispatch_mixed:_plan_dispatch_mixed"
                        ".np.asarray"]
    assert hits and "_plan_dispatch_mixed" in hits[0].message


def test_hostsync_tainted_int_cast_in_plan_body(tmp_path):
    """int() of a value produced by the dispatched tick is a
    one-element sync; int() of host state (lengths, numpy lookups like
    the n-gram drafter's) stays legal — the real engine is clean."""
    anchor = ("        rec = _InflightTick(\n"
              "            toks=toks, rows=rows, tick=tick_no, "
              "plan_ms=plan.ms,\n")
    e = _mutate(tmp_path, ENGINE, anchor,
                "        _first = int(toks[0])\n" + anchor)
    findings = analyze([e], passes=[HostSyncHazardPass()])
    # the one dispatch site, reached from every plan root
    keys = {f.key for f in findings}
    assert {f"_plan_dispatch_{kind}:_dispatch.int"
            for kind in ("decode", "mixed", "multi", "spec")} <= keys, [
        f.render() for f in findings]


def test_hostsync_hazard_in_reached_helper(tmp_path):
    """A sync inside a helper the plan path calls is attributed to the
    plan root that reaches it."""
    e = _mutate(
        tmp_path, ENGINE,
        "        prev_host, prev_dev = self._packed_prev",
        "        packed.item()\n"
        "        prev_host, prev_dev = self._packed_prev")
    findings = analyze([e], passes=[HostSyncHazardPass()])
    keys = _keys(findings)
    # _upload is reached from every packed plan path
    assert any(k.endswith(":_upload.item") for k in keys), keys
    hit = next(f for f in findings if f.key.endswith(":_upload.item"))
    assert "reached from" in hit.message


def test_hostsync_suppression_comment(tmp_path):
    e = _mutate(
        tmp_path, ENGINE,
        "            if self.host is not None:\n"
        "                self._issue_restores()\n"
        "            S = self.slots\n",
        "            if self.host is not None:\n"
        "                self._issue_restores()\n"
        "            _peek = np.asarray(self._rngs)"
        "  # analysis: host-sync-ok\n"
        "            S = self.slots\n")
    assert analyze([e], passes=[HostSyncHazardPass()]) == []


# -- the four passes are wired into the default suite ------------------------


def test_contract_passes_registered_and_gating():
    from distkeras_tpu.analysis import default_passes

    rules = {p.rule for p in default_passes()}
    assert {"wire-contract", "metric-contract", "span-contract",
            "host-sync-hazard"} <= rules
