"""Self-tests for the static analysis suite and the lock-order
detector: every pass proves it catches its seeded bad fixture and
stays quiet on the good twin, suppression comments and the baseline
round-trip work, the CLI honors the report exit-code contract, and a
smoke run over the installed package comes back clean against the
checked-in baseline — which is what makes the analyzer a tier-1 gate,
not just a tool."""

import json
import os
import textwrap
import threading

import pytest

from distkeras_tpu.analysis import (
    AnalysisError,
    Baseline,
    analyze,
    default_passes,
    split_by_baseline,
)
from distkeras_tpu.analysis.__main__ import main as analysis_main
from distkeras_tpu.analysis.lockorder import (
    LockOrderDetector,
    LockOrderError,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, name, code):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    return str(p)


def _rules(findings):
    return sorted({f.rule for f in findings})


# -- lock discipline ---------------------------------------------------------


LOCK_BAD = """
    import threading

    class Ring:
        def __init__(self):
            self._lock = threading.Lock()
            self._buf = []

        def push(self, x):
            with self._lock:
                self._buf.append(x)

        def peek(self):
            return list(self._buf)
"""

LOCK_GOOD = """
    import threading

    class Ring:
        def __init__(self):
            self._lock = threading.Lock()
            self._buf = []   # init is exempt: not shared yet

        def push(self, x):
            with self._lock:
                self._buf.append(x)

        def peek(self):
            with self._lock:
                return list(self._buf)

        def _peek_locked(self):
            return list(self._buf)   # *_locked convention is exempt
"""


def test_lock_pass_flags_unguarded_read(tmp_path):
    findings = analyze([_write(tmp_path, "m.py", LOCK_BAD)])
    assert [f.rule for f in findings] == ["lock-discipline"]
    assert findings[0].key == "Ring._buf@peek"


def test_lock_pass_good_fixture_clean(tmp_path):
    assert analyze([_write(tmp_path, "m.py", LOCK_GOOD)]) == []


def test_lock_pass_counts_mutator_calls_and_augassign(tmp_path):
    code = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                self._q = []

            def locked_inc(self):
                with self._lock:
                    self.n += 1

            def bad_inc(self):
                self.n += 1

            def bad_push(self, x):
                self._q.append(x)

            def locked_push(self, x):
                with self._lock:
                    self._q.append(x)
    """
    keys = {f.key for f in analyze([_write(tmp_path, "m.py", code)])}
    assert keys == {"C.n@bad_inc", "C._q@bad_push"}


def test_lock_pass_suppression_comment(tmp_path):
    code = LOCK_BAD.replace(
        "return list(self._buf)",
        "return list(self._buf)  # analysis: unguarded-ok",
    )
    assert analyze([_write(tmp_path, "m.py", code)]) == []


def test_lock_pass_suppression_on_line_above(tmp_path):
    code = LOCK_BAD.replace(
        "return list(self._buf)",
        "# analysis: unguarded-ok (snapshot read)\n"
        "            return list(self._buf)",
    )
    assert analyze([_write(tmp_path, "m.py", code)]) == []


def test_lock_pass_nested_def_does_not_inherit_lock(tmp_path):
    code = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._buf = []

            def start(self):
                with self._lock:
                    self._buf.append(0)

                    def loop():
                        self._buf.append(1)  # runs later, other thread
                    return loop
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert [f.key for f in findings] == ["C._buf@start"]


# -- donation safety ---------------------------------------------------------


DONATE_BAD = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def advance(buf, x):
        return buf + x

    def use(buf, x):
        out = advance(buf, x)
        return out + buf.sum()
"""

DONATE_GOOD = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def advance(buf, x):
        return buf + x

    def use(buf, x):
        buf = advance(buf, x)
        return buf.sum()
"""


def test_donation_pass_flags_use_after_donate(tmp_path):
    findings = analyze([_write(tmp_path, "m.py", DONATE_BAD)])
    assert [f.rule for f in findings] == ["donation-safety"]
    assert findings[0].key == "use.buf"


def test_donation_pass_rebind_is_clean(tmp_path):
    assert analyze([_write(tmp_path, "m.py", DONATE_GOOD)]) == []


def test_donation_pass_tracks_factory_returned_functions(tmp_path):
    # the engine's real shape: an lru-cached factory returns a body
    # compiled with donate=...; call sites bind it to a local
    code = """
        import functools

        def _compile(body, ctx, in_kinds, out_kinds, donate):
            return body

        def _tick_fn(dm):
            @functools.partial(_compile, ctx=None, in_kinds="pc",
                               out_kinds="c", donate=(1,))
            def tick(params, cache):
                return cache
            return tick

        def bad(dm, params, cache):
            tick = _tick_fn(dm)
            new_cache = tick(params, cache)
            return cache.sum()

        def good(dm, params, cache):
            tick = _tick_fn(dm)
            cache = tick(params, cache)
            return cache.sum()
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert [f.key for f in findings] == ["bad.cache"]


def test_donation_pass_self_attr_rebind_clean(tmp_path):
    code = """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def tick(cache, logits, x):
            return cache, logits

        class Engine:
            def step(self, x):
                self._cache, self._logits = tick(
                    self._cache, self._logits, x)
                return self._logits
    """
    assert analyze([_write(tmp_path, "m.py", code)]) == []


# -- rng discipline ----------------------------------------------------------


def test_rng_pass_flags_reuse(tmp_path):
    code = """
        import jax

        def sample(rng):
            a = jax.random.uniform(rng, (3,))
            b = jax.random.normal(rng, (3,))
            return a + b
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert [f.rule for f in findings] == ["rng-discipline"]
    assert findings[0].key == "sample.rng"


def test_rng_pass_split_chain_clean(tmp_path):
    code = """
        import jax

        def sample(rng):
            rng, sub = jax.random.split(rng)
            a = jax.random.uniform(sub, (3,))
            rng, sub = jax.random.split(rng)
            return a + jax.random.uniform(sub, (3,))
    """
    assert analyze([_write(tmp_path, "m.py", code)]) == []


def test_rng_pass_branch_alternatives_clean(tmp_path):
    code = """
        import jax

        def sample(key, flag):
            if flag:
                return jax.random.uniform(key, (2,))
            else:
                return jax.random.normal(key, (2,))
    """
    assert analyze([_write(tmp_path, "m.py", code)]) == []


def test_rng_pass_consume_then_split_flagged(tmp_path):
    # the subtle one: the draw uses rng, then split(rng) consumes the
    # SAME key again before the rebind lands
    code = """
        import jax

        def sample(rng):
            u = jax.random.uniform(rng, (3,))
            rng, sub = jax.random.split(rng)
            return u, sub
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert [f.key for f in findings] == ["sample.rng"]


# -- recompile hazards -------------------------------------------------------


def test_recompile_pass_flags_list_into_lru_cache(tmp_path):
    code = """
        import functools

        @functools.lru_cache(maxsize=8)
        def builder(cfgs):
            return cfgs

        def call():
            return builder([1, 2, 3])
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert [f.rule for f in findings] == ["recompile-hazard"]


def test_recompile_pass_flags_static_argnums(tmp_path):
    code = """
        import jax

        def run(x):
            f = jax.jit(lambda a, s: a, static_argnums=(1,))
            return f(x, [4, 4])
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert [f.rule for f in findings] == ["recompile-hazard"]


def test_recompile_pass_flags_fstring_and_variable_hazard(tmp_path):
    code = """
        import functools

        @functools.lru_cache(maxsize=8)
        def builder(tag):
            return tag

        def call(n):
            cfg = [n]
            builder(f"cfg-{n}")
            return builder(cfg)
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert len(findings) == 2
    assert _rules(findings) == ["recompile-hazard"]


def test_recompile_pass_tuple_args_clean(tmp_path):
    code = """
        import functools

        @functools.lru_cache(maxsize=8)
        def builder(cfgs, ctx):
            return cfgs

        def call(xs, mesh):
            cfgs = tuple((x, None) for x in xs)
            return builder(cfgs, (mesh, "model"))
    """
    assert analyze([_write(tmp_path, "m.py", code)]) == []


# -- import hygiene ----------------------------------------------------------


def test_import_pass_stdlib_only_layer(tmp_path):
    _write(tmp_path, "distkeras_tpu/telemetry/mod.py", """
        import json
        import numpy as np
        from distkeras_tpu.telemetry.trace import Tracer
    """)
    findings = analyze([str(tmp_path / "distkeras_tpu")])
    assert [f.rule for f in findings] == ["import-hygiene"]
    assert findings[0].key == "third-party.numpy"


def test_import_pass_tests_import_forbidden(tmp_path):
    _write(tmp_path, "distkeras_tpu/mod.py", """
        import tests.helpers
    """)
    findings = analyze([str(tmp_path / "distkeras_tpu")])
    assert [f.key for f in findings] == ["tests-import.tests.helpers"]


def test_import_pass_third_party_fine_outside_layer(tmp_path):
    _write(tmp_path, "distkeras_tpu/other.py", """
        import numpy as np
        import jax
    """)
    assert analyze([str(tmp_path / "distkeras_tpu")]) == []


# -- baseline ----------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    src = _write(tmp_path, "m.py", LOCK_BAD)
    findings = analyze([src])
    assert len(findings) == 1
    bl_path = str(tmp_path / "baseline.txt")

    # add: write, reload, finding is accepted
    Baseline(path=bl_path).write(bl_path, findings)
    bl = Baseline.load(bl_path)
    new, accepted = split_by_baseline(findings, bl)
    assert new == [] and len(accepted) == 1
    assert bl.entries[findings[0].fingerprint()] == "TODO: justify"

    # justify: edits survive a rewrite of the same findings
    bl.entries[findings[0].fingerprint()] = "snapshot read, documented"
    bl.write(bl_path, findings)
    bl2 = Baseline.load(bl_path)
    assert (bl2.entries[findings[0].fingerprint()]
            == "snapshot read, documented")

    # remove: the code is fixed, the entry goes stale, a rewrite from
    # the (now empty) findings drops it
    fixed = analyze([_write(tmp_path, "m.py", LOCK_GOOD)])
    assert fixed == []
    assert bl2.stale(fixed) == [findings[0].fingerprint()]
    bl2.write(bl_path, fixed)
    assert Baseline.load(bl_path).entries == {}


def test_baseline_rejects_malformed(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text("rule-without-tabs\n")
    with pytest.raises(AnalysisError):
        Baseline.load(str(p))


# -- CLI ---------------------------------------------------------------------


def test_cli_strict_exit_codes(tmp_path, capsys):
    """The add -> justify -> pass round trip: --write-baseline stamps
    new entries "TODO: justify", and --strict refuses to accept them
    until a human replaces the marker — the ledger cannot rot."""
    src = _write(tmp_path, "m.py", LOCK_BAD)
    assert analysis_main([src, "--no-baseline"]) == 0  # warn only
    assert analysis_main([src, "--no-baseline", "--strict"]) == 1
    bl = str(tmp_path / "bl.txt")
    assert analysis_main([src, "--baseline", bl,
                          "--write-baseline"]) == 0
    # baselined, but unjustified: strict still fails, naming the entry
    assert analysis_main([src, "--baseline", bl, "--strict"]) == 1
    out = capsys.readouterr().out
    assert "unjustified baseline entry" in out
    # justify it: strict passes
    text = open(bl).read()
    assert "TODO: justify" in text
    with open(bl, "w") as fh:
        fh.write(text.replace("TODO: justify",
                              "monitor read, racy by design"))
    assert analysis_main([src, "--baseline", bl, "--strict"]) == 0
    # regeneration preserves the justification, so strict keeps passing
    assert analysis_main([src, "--baseline", bl,
                          "--write-baseline"]) == 0
    assert analysis_main([src, "--baseline", bl, "--strict"]) == 0
    capsys.readouterr()


def test_cli_report_json(tmp_path, capsys):
    src = _write(tmp_path, "m.py", LOCK_BAD)
    assert analysis_main(["report", src, "--no-baseline",
                          "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["new"] == 1
    assert payload["findings"][0]["rule"] == "lock-discipline"


def test_cli_report_bad_input_exits_2(tmp_path, capsys):
    assert analysis_main(["report", str(tmp_path / "nope.py")]) == 2
    err = capsys.readouterr().err
    assert err.startswith("error:") and len(err.strip().splitlines()) == 1


def test_cli_report_syntax_error_exits_2(tmp_path, capsys):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    assert analysis_main(["report", str(bad)]) == 2
    err = capsys.readouterr().err
    assert "cannot parse" in err and "Traceback" not in err


# -- the real gate -----------------------------------------------------------


def test_analyzer_clean_on_installed_package():
    """The tier-1 gate: every pass over the real package, checked
    against the repo baseline — any unbaselined finding fails here
    before CI's lint job ever runs."""
    import distkeras_tpu

    pkg = os.path.dirname(os.path.abspath(distkeras_tpu.__file__))
    findings = analyze([pkg])
    bl_path = os.path.join(REPO_ROOT, "analysis-baseline.txt")
    baseline = (Baseline.load(bl_path) if os.path.isfile(bl_path)
                else None)
    new, accepted = split_by_baseline(findings, baseline)
    assert new == [], "unbaselined findings:\n" + "\n".join(
        f.render() for f in new)
    # the baseline must stay justified, not a dumping ground
    if baseline:
        assert all(j and not j.startswith("TODO")
                   for j in baseline.entries.values())


def test_every_pass_has_distinct_rule_and_suppression():
    passes = default_passes()
    assert len({p.rule for p in passes}) == len(passes) == 9
    assert len({p.suppression for p in passes}) == len(passes)


def test_report_rule_filter(tmp_path, capsys):
    """``report --rule`` inspects one pass's findings in isolation."""
    src = _write(tmp_path, "m.py", LOCK_BAD + """

        import jax

        def reuse(rng):
            a = jax.random.uniform(rng)
            b = jax.random.normal(rng)
            return a, b
    """)
    assert analysis_main(["report", src, "--no-baseline",
                          "--json"]) == 0
    rules = {f["rule"] for f in
             json.loads(capsys.readouterr().out)["findings"]}
    assert {"lock-discipline", "rng-discipline"} <= rules
    assert analysis_main(["report", src, "--no-baseline", "--json",
                          "--rule", "rng-discipline"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["findings"]
    assert {f["rule"] for f in payload["findings"]} == {"rng-discipline"}


# -- dynamic lock-order detector ---------------------------------------------


def _tracked_pair():
    """Two locks allocated from THIS file (under tests/, so the
    installed detector tracks them), at distinct sites."""
    a = threading.Lock()
    b = threading.Lock()
    return a, b


def test_lockorder_fires_on_deliberate_inversion():
    det = LockOrderDetector()
    det.install()
    try:
        a, b = _tracked_pair()
        with a:
            with b:
                pass
        with b:
            with a:
                pass
    finally:
        det.uninstall()
    assert len(det.cycles) == 1
    with pytest.raises(LockOrderError) as ei:
        det.assert_no_cycles()
    assert "inversion" in str(ei.value)


def test_lockorder_consistent_order_is_clean():
    det = LockOrderDetector()
    det.install()
    try:
        a, b = _tracked_pair()
        for _ in range(3):
            with a:
                with b:
                    pass
    finally:
        det.uninstall()
    assert det.cycles == []
    det.assert_no_cycles()


def test_lockorder_same_site_pair_inversion_fires():
    code = "import threading\n\ndef make():\n    return [threading.Lock() for _ in range(2)]\n"
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), "_lockorder_fixture.py")
    with open(path, "w") as fh:
        fh.write(code)
    try:
        det = LockOrderDetector()
        det.install()
        try:
            spec = importlib.util.spec_from_file_location(
                "tests._lockorder_fixture", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            a, b = mod.make()  # one allocation site, two instances
            with a:
                with b:
                    pass
            with b:
                with a:
                    pass
        finally:
            det.uninstall()
        assert len(det.cycles) == 1
    finally:
        os.remove(path)


def test_lockorder_three_lock_cycle():
    det = LockOrderDetector()
    det.install()
    try:
        a, b = _tracked_pair()
        c = threading.Lock()
        with a:
            with b:
                pass
        with b:
            with c:
                pass
        with c:
            with a:
                pass
    finally:
        det.uninstall()
    assert len(det.cycles) == 1
    assert len(det.cycles[0]["cycle"]) == 4  # a -> b -> c -> a


def test_lockorder_uninstall_restores_and_silences():
    real = threading.Lock
    det = LockOrderDetector()
    det.install()
    a, b = _tracked_pair()
    assert threading.Lock is not real
    det.uninstall()
    assert threading.Lock is real
    # wrappers handed out keep working but report nothing
    with b:
        with a:
            pass
    with a:
        with b:
            pass
    assert det.cycles == []


def test_lockorder_stdlib_allocations_untracked():
    import queue

    det = LockOrderDetector()
    det.install()
    try:
        q = queue.Queue()  # allocates its mutex from queue.py
        assert type(q.mutex).__name__ != "_TrackedLock"
        q.put(1)
        assert q.get() == 1
    finally:
        det.uninstall()
    assert det.edge_count() == 0


def test_lockorder_cross_thread_inversion_detected():
    """The real shape: each thread's ordering is locally fine; only
    the union of the two is cyclic."""
    det = LockOrderDetector()
    det.install()
    try:
        a, b = _tracked_pair()
        with a:
            with b:
                pass

        def other():
            with b:
                with a:
                    pass

        t = threading.Thread(target=other)
        t.start()
        t.join()
    finally:
        det.uninstall()
    assert len(det.cycles) == 1
    assert det.cycles[0]["thread"] != "MainThread"


def test_donation_pass_catches_seeded_engine_violation(tmp_path):
    """The pass against the REAL engine: discover every donating tick
    factory in serving/engine.py, then seed a broken rebind (the cache
    donated but bound to a fresh name, the stale attr read after) and
    assert the pass pins the exact function."""
    from distkeras_tpu.analysis.core import SourceFile
    from distkeras_tpu.analysis.donation import _module_donators

    eng_path = os.path.join(REPO_ROOT, "distkeras_tpu", "serving",
                            "engine.py")
    text = open(eng_path).read()
    src = SourceFile(eng_path, "engine.py", text)
    direct, factories = _module_donators(src.tree)
    # every compiled serving body donates; the discovery must see them
    assert set(direct) == {"_reset_slot_cursors", "_seed_slot",
                           "_enter_slot", "_copy_block"}
    assert {"_tick_fn", "_mixed_tick_fn", "_multi_tick_fn",
            "_spec_verify_fn", "_draft_feed_fn"} <= set(factories)
    assert all(v for v in factories.values())

    seeded = text.replace(
        """            (self._cache, self._last_logits, toks, *acc,
             self._rngs) = tick(
                self._params_only, self._cache, self._last_logits,
                self._rngs, *operands,
            )""",
        """            (new_cache, self._last_logits, toks, *acc,
             self._rngs) = tick(
                self._params_only, self._cache, self._last_logits,
                self._rngs, *operands,
            )
            stale = self._cache""",
        1,
    )
    assert seeded != text, "engine call-site shape changed; update seed"
    p = tmp_path / "engine_seeded.py"
    p.write_text(seeded)
    findings = analyze([str(p)])
    assert any(f.rule == "donation-safety"
               and f.key == "_dispatch.self._cache"
               for f in findings), [f.render() for f in findings]


def test_donation_pass_catches_seeded_inflight_handoff(tmp_path):
    """The in-flight handoff rule against the REAL engine: seed a
    pre-donation capture of the cache into the _InflightTick record
    (which the pipelined loop parks on self._pending) and assert the
    pass pins it."""
    eng_path = os.path.join(REPO_ROOT, "distkeras_tpu", "serving",
                            "engine.py")
    text = open(eng_path).read()
    site = ('        with self._phase("dispatch", tick=tick_no, **span) '
            'as dispatch:')
    seeded = text.replace(
        site,
        """        leak = _InflightTick(toks=self._cache, rows=rows,
                             tick=tick_no, plan_ms=0.0, upload_ms=0.0,
                             dispatch_ms=0.0, n_dec=n_dec,
                             fed_tokens=fed_tokens, chunk=chunk)
        self._pending.append(leak)
""" + site,
        1,
    )
    assert seeded != text, "engine dispatch shape changed; update seed"
    p = tmp_path / "engine_handoff_seeded.py"
    p.write_text(seeded)
    findings = analyze([str(p)])
    assert any(f.rule == "donation-safety"
               and f.key == "_dispatch.self._cache:handoff"
               for f in findings), [f.render() for f in findings]


def test_donation_handoff_fixture_good_and_bad(tmp_path):
    """Unit fixtures for the handoff rule: capturing a tick OUTPUT into
    an escaping record is fine; capturing a donated INPUT is not."""
    bad = """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def tick(buf, x):
            return buf + x, x

        class Engine:
            def step(self, x):
                rec = dict(held=self.buf)
                self.pending.append(rec)
                self.buf, toks = tick(self.buf, x)
                return toks
    """
    good = """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def tick(buf, x):
            return buf + x, x

        class Engine:
            def step(self, x):
                self.buf, toks = tick(self.buf, x)
                rec = dict(held=toks)
                self.pending.append(rec)
                return toks
    """
    import textwrap

    pb = tmp_path / "bad_handoff.py"
    pb.write_text(textwrap.dedent(bad))
    pg = tmp_path / "good_handoff.py"
    pg.write_text(textwrap.dedent(good))
    findings = analyze([str(pb)])
    assert any(f.rule == "donation-safety" and f.key.endswith(":handoff")
               for f in findings), [f.render() for f in findings]
    assert not [f for f in analyze([str(pg)])
                if f.key.endswith(":handoff")]


def test_rng_pass_catches_seeded_engine_violation(tmp_path):
    """Seed a key reuse into the ticks' real sampler (the per-slot sub
    key drawn twice) and assert the pass pins it."""
    eng_path = os.path.join(REPO_ROOT, "distkeras_tpu", "serving",
                            "engine.py")
    text = open(eng_path).read()
    seeded = text.replace(
        """            rng, sub = jax.random.split(rngs[s])
            toks.append(
                sample_tokens(logits[s][None], sub, temp,
                              top_k, top_p)[0]
            )
""",
        """            rng, sub = jax.random.split(rngs[s])
            toks.append(
                sample_tokens(logits[s][None], sub, temp,
                              top_k, top_p)[0]
            )
            extra = jax.random.uniform(sub, ())
""",
        1,
    )
    assert seeded != text, "engine tick shape changed; update seed"
    p = tmp_path / "engine_rng_seeded.py"
    p.write_text(seeded)
    findings = analyze([str(p)])
    assert any(f.rule == "rng-discipline" and f.key.endswith(".sub")
               for f in findings), [f.render() for f in findings]


def test_donation_pass_handles_donate_argnames(tmp_path):
    code = """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnames=("buf",))
        def advance(buf, x):
            return buf + x

        def bad(buf, x):
            out = advance(buf, x)
            return buf.sum()

        def good(buf, x):
            buf = advance(buf, x)
            return buf.sum()
    """
    findings = analyze([_write(tmp_path, "m.py", code)])
    assert [f.key for f in findings] == ["bad.buf"]
