"""Multi-replica serving fabric: prefix-affinity routing determinism,
load-aware spill, kill-one-replica failover with zero lost streams,
graceful drain, typed overload signaling, fleet stats/metrics
aggregation, wire compatibility of a plain ServingClient against the
router, and the routing-policy unit invariants (consistent-hash
stability, affinity-index eviction, metric-snapshot merging)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import generate
from distkeras_tpu.serving import (
    DISCONNECTED,
    DrainingError,
    FIFOScheduler,
    LMServer,
    OverloadedError,
    Router,
    ServingClient,
    ServingConnectionError,
    ServingEngine,
    merge_metric_snapshots,
)
from distkeras_tpu.serving.fleet import Replica
from distkeras_tpu.serving.router import PrefixAffinityIndex, _HashRing

# identical to test_serving/test_paged KW, so every slot-engine tick
# shape is already traced when this file runs inside the full suite
KW = dict(vocab_size=64, d_model=32, num_heads=2, num_layers=2,
          max_len=48, dtype=jnp.float32, attention="dense")
BS = 8  # paged block size AND router affinity chunk size


@pytest.fixture(scope="module")
def model_and_params():
    model = get_model("transformer_lm", **KW)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, params


def _solo(model, params, prompt, max_new):
    out = generate(model, params, jnp.asarray(prompt)[None], max_new)
    return np.asarray(out)[0, len(prompt):].tolist()


def _server(model, params, slots=2, paged=False, scheduler=None):
    eng = ServingEngine(
        model, params, slots=slots,
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
        scheduler=scheduler,
        **(dict(paged=True, block_size=BS) if paged else {}),
    )
    return LMServer(eng).start()


def _fleet(model, params, n=3, paged=False, slots=2, **router_kw):
    """N in-process replicas + a router fronting them (fast probe
    cadence for tests). Caller stops both."""
    servers = [_server(model, params, slots=slots, paged=paged)
               for _ in range(n)]
    kw = dict(block_size=BS, poll_interval=0.05, down_after=1,
              backoff_base=0.05, probe_timeout=2.0,
              registry=telemetry.MetricRegistry(),
              tracer=telemetry.Tracer())
    kw.update(router_kw)
    router = Router(
        [("127.0.0.1", s.port, f"r{i}") for i, s in enumerate(servers)],
        **kw,
    ).start()
    return servers, router


def _stop(servers, router, clients=()):
    for c in clients:
        c.close()
    router.stop()
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# wire compatibility + routing
# ---------------------------------------------------------------------------

def test_router_wire_compat_and_parity(model_and_params):
    """A plain ServingClient pointed at the router works unchanged:
    generate acks with rid+trace, tokens stream with parity to solo
    generate(), stats/metrics/alerts/trace_dump answer, unknown ops
    error without dropping the connection."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=3)
    client = ServingClient("127.0.0.1", router.port)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=6).astype(np.int32)
                   for _ in range(5)]
        rids = [client.generate(p, max_new_tokens=5) for p in prompts]
        assert len(set(rids)) == 5
        for p, rid in zip(prompts, rids):
            toks, reason = client.result(rid, timeout=60)
            assert toks == _solo(model, params, p, 5)
            assert reason == "length"
            assert client.trace_of(rid) is not None
        router.manager.probe_all()  # fresh load view for the sums
        st = client.stats()
        assert st["requests_completed"] == 5
        assert st["tokens_generated"] == 25
        assert st["replicas_routable"] == 3
        assert st["router"]["routed"] == 5
        assert st["router"]["failed"] == 0
        merged = client.metrics()
        assert "serving_tokens_total" in merged
        assert "router_requests_routed_total" in merged
        assert client.alerts() == []  # replicas have no SLO monitors
        # the routing spans are dumpable by the acked trace id
        spans = {s["span"]
                 for s in client.trace_dump(trace=client.trace_of(rids[0]))}
        assert {"router.route", "router.stream"} <= spans
        # typed unknown-op rejection across the router hop: same
        # {"error": "unknown_op", "op": ...} terminal arm as a direct
        # LMServer, surfaced as the same typed client error
        from distkeras_tpu.serving import UnknownOpError
        with pytest.raises(UnknownOpError, match="nope") as ei:
            client._call({"op": "nope"})
        assert ei.value.op == "nope"
        # still alive after the error reply
        assert client.stats()["router"]["routed"] == 5
    finally:
        _stop(servers, router, [client])


def test_affinity_same_prefix_same_replica(model_and_params):
    """Affinity determinism: requests sharing a prompt prefix all land
    on the replica that served the first one — its radix cache keeps
    paying off — and the router's routed counter records the affine
    decisions."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=3, paged=True)
    client = ServingClient("127.0.0.1", router.port)
    try:
        rng = np.random.default_rng(1)
        system = rng.integers(0, 64, size=2 * BS).astype(np.int32)
        n = 6
        for i in range(n):
            tail = rng.integers(0, 64, size=4).astype(np.int32)
            p = np.concatenate([system, tail])
            rid = client.generate(p, max_new_tokens=4)
            toks, _ = client.result(rid, timeout=60)
            assert toks == _solo(model, params, p, 4)
        router.manager.probe_all()
        st = client.stats()
        served = {name: rep.get("stats", {}).get("requests_completed", 0)
                  for name, rep in st["replicas"].items()}
        # every request on ONE replica, the other two untouched
        assert sorted(served.values()) == [0, 0, n], served
        # decisions: first is hash placement, the rest affine
        fam = router.registry.get("router_requests_routed_total")
        by_decision = {}
        for s in fam.snapshot()["series"]:
            d = s["labels"]["decision"]
            by_decision[d] = by_decision.get(d, 0) + s["value"]
        assert by_decision.get("affine", 0) == n - 1
        # and the winning replica actually prefix-hit in its KV cache
        winner = max(served, key=served.get)
        assert st["replicas"][winner]["stats"]["prefix_hit_fraction"] > 0.5
    finally:
        _stop(servers, router, [client])


def test_spill_under_induced_saturation(model_and_params):
    """Load-aware spill: when the affine replica's polled stats report
    queue saturation, a same-prefix request is diverted to the
    least-loaded peer instead of queueing behind the wall."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=2, slots=1,
                             spill_queue_depth=2)
    client = ServingClient("127.0.0.1", router.port)
    try:
        rng = np.random.default_rng(2)
        system = rng.integers(0, 64, size=2 * BS).astype(np.int32)
        p0 = np.concatenate(
            [system, rng.integers(0, 64, size=2).astype(np.int32)])
        rid = client.generate(p0, max_new_tokens=4)
        client.result(rid, timeout=60)
        router.manager.probe_all()
        st = client.stats()
        owner = max(
            st["replicas"],
            key=lambda r: st["replicas"][r].get("stats", {}).get(
                "requests_completed", 0),
        )
        # saturate the owner directly (slots=1: one active, rest queue)
        direct = ServingClient(
            "127.0.0.1", servers[int(owner[1:])].port)
        busy = [direct.generate(
            rng.integers(0, 64, size=6).astype(np.int32),
            max_new_tokens=24) for _ in range(4)]
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            router.manager.probe_all()
            if (router.manager.get(owner).last_stats.get("queue_depth", 0)
                    >= 2):
                break
        # same-prefix request now spills to the idle peer
        p1 = np.concatenate(
            [system, rng.integers(0, 64, size=2).astype(np.int32)])
        rid = client.generate(p1, max_new_tokens=4)
        toks, reason = client.result(rid, timeout=60)
        assert toks == _solo(model, params, p1, 4)
        assert reason == "length"
        assert router.registry.counter(
            "router_requests_spilled_total").value >= 1
        router.manager.probe_all()
        st = client.stats()
        other = next(n for n in st["replicas"] if n != owner)
        assert st["replicas"][other]["stats"]["requests_completed"] >= 1
        for b in busy:
            direct.result(b, timeout=120)
        direct.close()
    finally:
        _stop(servers, router, [client])


def test_failover_zero_lost_streams(model_and_params):
    """Kill the busiest replica mid-stream: every accepted stream still
    completes with bit-parity (replay-with-skip on survivors re-derives
    the identical seeded stream), unstarted requests are requeued, and
    nothing is reported failed."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=3)
    client = ServingClient("127.0.0.1", router.port)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, size=6).astype(np.int32)
                   for _ in range(6)]
        rids = [client.generate(p, max_new_tokens=40) for p in prompts]
        # wait until tokens are actually streaming, then kill the
        # replica carrying the most in-flight requests
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            by = router.stats()["router"]["inflight_by_replica"]
            if by and max(by.values()) >= 2:
                break
            time.sleep(0.01)
        victim = max(by, key=by.get)
        servers[int(victim[1:])].stop()  # closes live conns = crash
        for p, rid in zip(prompts, rids):
            toks, reason = client.result(rid, timeout=120)
            assert toks == _solo(model, params, p, 40)
            assert reason == "length"
        st = client.stats()
        assert st["router"]["failed"] == 0
        assert st["router"]["failed_over"] >= 1
        assert st["router"]["failovers"] >= 1
        assert st["replicas"][victim]["state"] == "down"
    finally:
        _stop(servers, router, [client])


def test_failover_requeues_unstarted_requests(model_and_params):
    """A queued-but-unstarted request on the dead replica (zero tokens
    delivered) is requeued, not replayed — visible in the failed-over
    counter's kind label — and completes with parity."""
    model, params = model_and_params
    # one slot per replica so extra requests sit queued server-side
    servers, router = _fleet(model, params, n=2, slots=1,
                             spill_queue_depth=1000)
    client = ServingClient("127.0.0.1", router.port)
    try:
        rng = np.random.default_rng(4)
        system = rng.integers(0, 64, size=2 * BS).astype(np.int32)
        prompts = [np.concatenate(
            [system, rng.integers(0, 64, size=2).astype(np.int32)])
            for _ in range(3)]
        # same prefix -> all three ride the SAME replica (affinity, and
        # spill is disabled via the huge threshold): one decoding, two
        # queued behind it
        rids = [client.generate(p, max_new_tokens=30) for p in prompts]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            by = router.stats()["router"]["inflight_by_replica"]
            # (empty until the router has placed the first of them)
            if by and max(by.values()) == 3:
                break
            time.sleep(0.01)
        victim = max(by, key=by.get)
        # kill on observed PROGRESS, not a fixed sleep: consume the
        # first stream until its second token, then stop the victim
        # while that stream is provably mid-flight and the others sit
        # queued behind the single slot (a fixed sleep races warm
        # engines — three 30-token streams can finish inside it)
        frames0 = client.frames(rids[0], timeout=120)
        toks0 = []
        for kind, val in frames0:
            if kind == "tok":
                toks0.append(val)
            if len(toks0) >= 2:
                break
        servers[int(victim[1:])].stop()
        for kind, val in frames0:
            if kind == "tok":
                toks0.append(val)
            else:
                reason0 = val
        assert toks0 == _solo(model, params, prompts[0], 30)
        assert reason0 == "length"
        for p, rid in zip(prompts[1:], rids[1:]):
            toks, reason = client.result(rid, timeout=120)
            assert toks == _solo(model, params, p, 30)
            assert reason == "length"
        fam = router.registry.get("router_requests_failed_over_total")
        kinds = {s["labels"]["kind"]: s["value"]
                 for s in fam.snapshot()["series"]}
        assert kinds.get("requeued", 0) >= 1, kinds
        assert client.stats()["router"]["failed"] == 0
    finally:
        _stop(servers, router, [client])


# ---------------------------------------------------------------------------
# drain
# ---------------------------------------------------------------------------

def test_lmserver_drain_semantics(model_and_params):
    """Engine-level graceful drain over the wire: the drain op closes
    admissions (typed DrainingError on new generates), in-flight
    streams finish, and stats reports draining -> drained."""
    model, params = model_and_params
    servers = [_server(model, params)]
    client = ServingClient("127.0.0.1", servers[0].port)
    try:
        p = np.arange(1, 7, dtype=np.int32)
        rid = client.generate(p, max_new_tokens=20)
        reply = client.drain()
        assert set(reply) == {"active", "queued"}
        with pytest.raises(DrainingError, match="draining"):
            client.generate(p, max_new_tokens=4)
        toks, reason = client.result(rid, timeout=60)
        assert toks == _solo(model, params, p, 20)
        assert reason == "length"
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = client.stats()
            if st["drained"]:
                break
            time.sleep(0.02)
        assert st["draining"] and st["drained"]
        # engine-level API agrees
        assert servers[0].engine.draining and servers[0].engine.drained
    finally:
        client.close()
        servers[0].stop()


def test_router_drain_and_replica_drain(model_and_params):
    """Router drain closes ROUTER admissions (typed error to clients,
    in-flight finishes); draining one replica via the op routes all new
    traffic to the survivors."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=2)
    client = ServingClient("127.0.0.1", router.port)
    try:
        rng = np.random.default_rng(5)
        # drain replica r0 through the public client API (the wire
        # field the wire-contract pass tracks): everything new must
        # land on r1
        reply = client.drain(replica="r0")
        assert reply == {"active": 0, "queued": 0}
        for _ in range(4):
            p = rng.integers(0, 64, size=6).astype(np.int32)
            rid = client.generate(p, max_new_tokens=4)
            toks, _ = client.result(rid, timeout=60)
            assert toks == _solo(model, params, p, 4)
        router.manager.probe_all()
        st = client.stats()
        assert st["replicas"]["r0"]["state"] == "draining"
        assert st["replicas"]["r0"].get(
            "stats", {}).get("requests_completed", 0) == 0
        assert st["replicas"]["r1"]["stats"]["requests_completed"] == 4
        # now drain the router itself: one in-flight rides through,
        # new submits are refused with the typed error
        p = rng.integers(0, 64, size=6).astype(np.int32)
        rid = client.generate(p, max_new_tokens=20)
        assert client._call({"op": "drain"})["draining"] == 1
        with pytest.raises(DrainingError):
            client.generate(p, max_new_tokens=4)
        toks, reason = client.result(rid, timeout=60)
        assert toks == _solo(model, params, p, 20)
        assert reason == "length"
        # the client holds its last token a moment before the router's
        # pump has struck the request off its in-flight count (seen once
        # under six workers, PR 31): drained is where the count settles
        deadline = time.monotonic() + 5.0
        st = client.stats()
        while not st["router"]["drained"] and time.monotonic() < deadline:
            time.sleep(0.05)
            st = client.stats()
        assert st["router"]["draining"] and st["router"]["drained"]
    finally:
        _stop(servers, router, [client])


def test_drain_forgets_affinity_placements(model_and_params):
    """Regression: a *drained* replica's affinity placements must be
    forgotten (previously only death forgot them), both when the drain
    is admin-issued through the router and when the probe loop detects
    an engine that began draining on its own — otherwise the radix
    index keeps steering every same-prefix request at a replica that
    refuses it."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=2, paged=True)
    client = ServingClient("127.0.0.1", router.port)
    try:
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, 64, size=4 * BS).astype(np.int32)
        rid = client.generate(prompt, max_new_tokens=2)
        client.result(rid, timeout=60)
        with router._route_lock:
            owner, hit = router.index.lookup(prompt)
        assert owner in ("r0", "r1") and hit > 0
        # leg 1: admin drain through the wire op — placements must be
        # gone IMMEDIATELY, not at the next poll
        client.drain(replica=owner)
        with router._route_lock:
            owner2, _ = router.index.lookup(prompt)
        assert owner2 is None
        # traffic re-places on the survivor
        rid = client.generate(prompt, max_new_tokens=2)
        toks, _ = client.result(rid, timeout=60)
        assert toks == _solo(model, params, prompt, 2)
        survivor = "r1" if owner == "r0" else "r0"
        with router._route_lock:
            owner3, _ = router.index.lookup(prompt)
        assert owner3 == survivor
        # leg 2: the survivor's ENGINE begins draining on its own (a
        # deploy agent drained it behind the router's back) — the
        # probe loop must detect the transition and forget
        idx = int(survivor[1:])
        servers[idx].engine.begin_drain()
        router.manager.probe_all()
        assert router.manager.get(survivor).state == "draining"
        with router._route_lock:
            owner4, _ = router.index.lookup(prompt)
        assert owner4 is None
    finally:
        _stop(servers, router, [client])


# ---------------------------------------------------------------------------
# typed overload + connection robustness (satellites)
# ---------------------------------------------------------------------------

def test_overloaded_typed_error_end_to_end(model_and_params):
    """QueueFullError at the server boundary surfaces as the structured
    overloaded reply and a typed OverloadedError carrying queue_depth —
    distinguishable from hard failures by routers and users."""
    model, params = model_and_params
    sched = FIFOScheduler(max_queue_depth=1, tick_token_budget=64,
                          registry=telemetry.MetricRegistry(),
                          tracer=telemetry.Tracer())
    servers = [_server(model, params, slots=1, scheduler=sched)]
    client = ServingClient("127.0.0.1", servers[0].port)
    try:
        p = np.arange(1, 7, dtype=np.int32)
        rids, err = [], None
        try:
            for _ in range(10):
                rids.append(client.generate(p, max_new_tokens=24))
        except OverloadedError as e:
            err = e
        assert err is not None
        assert err.queue_depth == 1
        assert isinstance(err, RuntimeError)  # untyped callers still catch
        for rid in rids:  # the accepted ones still complete
            toks, _ = client.result(rid, timeout=120)
            assert toks == _solo(model, params, p, 24)
    finally:
        client.close()
        servers[0].stop()


def test_client_connection_robustness(model_and_params):
    """Typed connection errors name host:port; a socket dying
    mid-stream delivers the terminal DISCONNECTED frame instead of
    hanging consumers; close() is idempotent; post-mortem calls fail
    fast with the typed error."""
    model, params = model_and_params
    with pytest.raises(ServingConnectionError, match="127.0.0.1:1"):
        ServingClient("127.0.0.1", 1)
    server = _server(model, params)
    client = ServingClient("127.0.0.1", server.port)
    p = np.arange(1, 7, dtype=np.int32)
    rid = client.generate(p, max_new_tokens=40)
    got, reason = [], None
    for kind, val in client.frames(rid, timeout=30):
        if kind == "end":
            reason = val
            break
        got.append(val)
        if len(got) == 2:
            server.stop()  # kill the server mid-stream
    assert reason == DISCONNECTED
    assert len(got) < 40
    # parity on what WAS delivered before the cut
    assert got == _solo(model, params, p, 40)[: len(got)]
    # late consumer on a dead connection: immediate terminal frame
    assert client.result(999, timeout=5) == ([], DISCONNECTED)
    with pytest.raises(ServingConnectionError,
                       match=f"127.0.0.1:{server.port}"):
        client.stats()
    client.close()
    client.close()  # idempotent


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def test_aggregated_stats_and_metrics_vs_per_replica_sums(
        model_and_params):
    """Fleet stats are exactly the per-replica sums, and the merged
    metrics snapshot's counter values equal the sum of each replica's
    own registry series."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=3)
    client = ServingClient("127.0.0.1", router.port)
    directs = [ServingClient("127.0.0.1", s.port) for s in servers]
    try:
        rng = np.random.default_rng(6)
        for _ in range(7):
            p = rng.integers(0, 64, size=6).astype(np.int32)
            rid = client.generate(p, max_new_tokens=5)
            client.result(rid, timeout=60)
        router.manager.probe_all()
        agg = client.stats()
        per = [d.stats() for d in directs]
        for key in ("requests_completed", "tokens_generated", "ticks"):
            assert agg[key] == sum(s[key] for s in per), key
        assert agg["requests_completed"] == 7
        merged = client.metrics()

        def tokens_total(metrics):
            series = metrics["serving_tokens_total"]["series"]
            # a replica that served nothing has the family declared but
            # no series yet
            return series[0]["value"] if series else 0

        want = sum(tokens_total(d.metrics()) for d in directs)
        assert tokens_total(merged) == want == 35
    finally:
        _stop(servers, router, [client] + directs)


def test_merge_metric_snapshots_unit():
    """Counters/gauges sum by label key, histograms merge
    bucket-by-bucket, series unions are kept, and type-skewed families
    keep the first replica's view."""
    a = telemetry.MetricRegistry()
    b = telemetry.MetricRegistry()
    a.counter("c", labelnames=("x",)).labels(x="1").inc(3)
    b.counter("c", labelnames=("x",)).labels(x="1").inc(4)
    b.counter("c", labelnames=("x",)).labels(x="2").inc(5)
    a.gauge("g").set(2)
    b.gauge("g").set(8)
    a.histogram("h", buckets=(1.0, 10.0)).observe(0.5)
    b.histogram("h", buckets=(1.0, 10.0)).observe(5.0)
    b.histogram("h", buckets=(1.0, 10.0)).observe(100.0)
    b.gauge("c_skew").set(1)
    a.counter("c_skew").inc()
    m = merge_metric_snapshots([a.collect(), b.collect()])
    series = {tuple(sorted(s["labels"].items())): s["value"]
              for s in m["c"]["series"]}
    assert series[(("x", "1"),)] == 7
    assert series[(("x", "2"),)] == 5
    assert m["g"]["series"][0]["value"] == 10
    h = m["h"]["series"][0]
    assert h["count"] == 3
    assert h["sum"] == pytest.approx(105.5)
    assert h["buckets"]["1.0"] == 1
    assert h["buckets"]["10.0"] == 1
    assert h["buckets"]["+Inf"] == 1
    assert m["c_skew"]["type"] == "counter"  # first snapshot wins


# ---------------------------------------------------------------------------
# policy units
# ---------------------------------------------------------------------------

def test_hash_ring_stability():
    """Removing a replica from the alive set only remaps the keys that
    pointed at it — everything else stays put (the property that keeps
    cold-prefix placement cache-friendly across failures)."""
    names = [f"r{i}" for i in range(4)]
    ring = _HashRing(names)
    keys = [f"key-{i}".encode() for i in range(200)]
    full = {k: ring.lookup(k, set(names)) for k in keys}
    assert len(set(full.values())) == 4  # all replicas get keyspace
    alive = set(names) - {"r2"}
    for k in keys:
        now = ring.lookup(k, alive)
        if full[k] != "r2":
            assert now == full[k]
        else:
            assert now in alive


def test_prefix_affinity_index_unit():
    """Affinity lookup follows the deepest owned chunk, first placement
    wins under overlap, forget() retires one owner's chunks, and the
    node cap evicts LRU."""
    idx = PrefixAffinityIndex(block_size=4, max_nodes=8)
    t1 = list(range(12))          # 3 chunks
    idx.place(t1, "rA")
    owner, hit = idx.lookup(t1 + [99])
    assert owner == "rA" and hit == 12
    # longer prompt sharing 2 chunks, extended by another replica:
    # shared chunks keep rA, the extension belongs to rB
    t2 = t1[:8] + [7, 7, 7, 7]
    idx.place(t2, "rB")
    assert idx.lookup(t1 + [99])[0] == "rA"
    owner2, hit2 = idx.lookup(t2 + [99])
    assert owner2 == "rB" and hit2 == 12
    # short prompts (< one chunk) never produce affinity
    assert idx.lookup([1, 2])[0] is None
    # forget rB: its extension chunk goes, rA's chain survives
    idx.forget("rB")
    assert idx.lookup(t2 + [99])[0] == "rA"
    assert idx.lookup(t1 + [99])[0] == "rA"
    # cap: placing many distinct prefixes stays bounded
    for i in range(20):
        idx.place([100 + i] * 4, "rC")
    assert len(idx) <= 8


def test_replica_recovery_after_restart(model_and_params):
    """A downed replica is re-probed under backoff and returns to
    rotation once a server listens on its address again — traffic
    flows to it without router restart."""
    model, params = model_and_params
    servers, router = _fleet(model, params, n=2)
    client = ServingClient("127.0.0.1", router.port)
    try:
        port0 = servers[0].port
        servers[0].stop()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.manager.get("r0").state == "down":
                break
            time.sleep(0.02)
        assert router.manager.get("r0").state == "down"
        # requests still served by the survivor
        p = np.arange(1, 7, dtype=np.int32)
        rid = client.generate(p, max_new_tokens=4)
        assert client.result(rid, timeout=60)[0] == _solo(
            model, params, p, 4)
        # resurrect on the SAME address; the probe loop's backoff
        # reconnect must bring it back to healthy
        servers[0] = _server_on(model, params, port0)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.manager.get("r0").state == "healthy":
                break
            time.sleep(0.02)
        assert router.manager.get("r0").state == "healthy"
        assert len(router.manager.routable()) == 2
    finally:
        _stop(servers, router, [client])


def _server_on(model, params, port):
    eng = ServingEngine(
        model, params, slots=2,
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
    )
    return LMServer(eng, port=port).start()


def test_router_rejects_unknown_policy_and_bad_replica(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="policy"):
        Router([("127.0.0.1", 1)], policy="lru")
    servers, router = _fleet(model, params, n=2)
    client = ServingClient("127.0.0.1", router.port)
    try:
        with pytest.raises(RuntimeError, match="no replica named"):
            client._call({"op": "drain", "replica": "nope"})
        with pytest.raises(RuntimeError, match="per replica"):
            client.flight()
    finally:
        _stop(servers, router, [client])


def test_replica_snapshot_reads_state_under_lock():
    """Regression (lock-discipline fix): snapshot() reads state and
    last_stats under the replica lock, so the probe thread's updates
    can't tear one snapshot across two states."""
    r = Replica("127.0.0.1", 1, name="r0")
    r.state = "healthy"
    r.last_stats = {"queue_depth": 3}
    real = r._lock
    acquired = []

    class ProbeLock:
        def __enter__(self):
            acquired.append(True)
            return real.__enter__()

        def __exit__(self, *exc):
            return real.__exit__(*exc)

    r._lock = ProbeLock()
    try:
        snap = r.snapshot()
    finally:
        r._lock = real
    assert acquired, "snapshot() must read state/last_stats under _lock"
    assert snap["state"] == "healthy"
    assert snap["stats"] == {"queue_depth": 3}
