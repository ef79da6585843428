"""LM serving example — continuous batching over TCP.

Starts an :class:`LMServer` (slot-pooled KV cache, FIFO admission) on a
tiny TransformerLM, submits a handful of prompts over the framed-msgpack
transport, and prints each request's tokens as they stream back. Every
stream is checked token-for-token against a solo ``generate()`` call —
the continuous-batching engine is the same math, just scheduled.

Telemetry: the server's engine publishes into the process-global
registry/tracer; ``--telemetry-port`` starts the HTTP scrape endpoint
(``/metrics`` Prometheus text, ``/metrics.json``, ``/traces``,
``/flight``, ``/alerts``), and the example always prints the first
request's span chain (queued → prefill → decode → stream → finish)
fetched over the TCP ``trace_dump`` op.

Flight recorder + SLO watchdog: the engine records one snapshot per tick
(budget split, phase-decomposed latency, slot states); the example
prints the last ticks fetched over the TCP ``flight`` op, attaches an
:class:`SloMonitor` with the default serving rules (queried over the
``alerts`` op), and arms the stall watchdog. ``--flight-dump PATH``
writes the ring as JSONL — render it with
``python -m distkeras_tpu.telemetry.report --flight PATH``.

``--paged`` serves through the block-paged KV cache with radix prefix
sharing instead of the contiguous slot slabs: prompts open with a shared
system prefix, so every request after the first skips most of its
prefill (the printed stats show the prefix-hit fraction and block
usage). Streams are bit-identical either way.

Prompts stream into their slots chunk-by-chunk inside the decode tick
(Sarathi-style chunked prefill; ``--prefill-chunk`` sets the chunk, 0
restores the legacy monolithic whole-prompt prefill dispatch) — a long
prompt never stalls the tokens already streaming.

``--tp N`` serves tensor-parallel: the jitted tick bodies run under
``shard_map`` on a 1-D ``model`` mesh over N devices — attention heads
and MLP hidden sharded, one psum per block, the KV cache split along its
head axis. Streams stay bit-identical to single-chip serving (the
parity check below covers it). Needs N local devices (real chips, or
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU).

``--draft ngram|model`` turns on speculative decoding: a drafter
proposes ``--spec-k`` tokens per decoding stream each tick (the
stream's own n-gram history, or a small draft TransformerLM) and the
flagship verifies the whole window in one fused dispatch, accepting a
prefix by rejection sampling. Greedy streams are bit-identical to the
non-speculative engine — the parity check below covers it — and the
printed stats show proposed/accepted draft tokens and the acceptance
rate.

``--replicas N`` serves through the multi-replica fabric: N in-process
``LMServer`` replicas fronted by the prefix-affinity ``Router``, which
speaks the same wire protocol (the client below connects to it
unchanged). Prompts share a system prefix, so affine routing lands
them all on the replica whose radix cache holds it — the printed fleet
stats show the per-replica request distribution, the fleet prefix-hit
fraction, and the router's routed/spilled/failed-over counters.
Streams stay bit-identical to solo ``generate()`` through the extra
hop.

Run: python examples/lm_serving.py [--prompts 4] [--max-new 16] [--slots 2]
     [--telemetry-port 9100] [--paged] [--prefill-chunk 16] [--tp 2]
     [--draft ngram] [--spec-k 4] [--replicas 3]
     [--flight-dump /tmp/flight.jsonl]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp

from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import generate
from distkeras_tpu.serving import LMServer, ServingClient, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--telemetry-port", type=int, default=None,
                    help="start the HTTP scrape endpoint on this port "
                         "(0 = ephemeral)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache + radix prefix sharing "
                         "(prompts share a system prefix; repeat "
                         "requests skip its prefill)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked mixed-tick prefill: prompts stream "
                         "into their slot this many tokens per decode "
                         "tick (0 = legacy monolithic prefill; default "
                         "64)")
    ap.add_argument("--flight-dump", default=None, metavar="PATH",
                    help="write the flight-recorder ring to this JSONL "
                         "when done (render: python -m "
                         "distkeras_tpu.telemetry.report --flight PATH)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel serving over this many "
                         "devices (1-D 'model' mesh; heads must "
                         "divide)")
    ap.add_argument("--draft", default=None,
                    choices=["ngram", "model"],
                    help="speculative decoding: 'ngram' proposes from "
                         "each stream's own history (no second model), "
                         "'model' runs a small draft TransformerLM; "
                         "the flagship verifies k proposals per tick "
                         "and streams stay bit-identical either way")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per row per tick "
                         "(default 4)")
    ap.add_argument("--multi-step-k", type=int, default=1,
                    help="device-resident multi-step decode: run k "
                         "decode steps per dispatch in all-decode "
                         "steady state (streams stay bit-identical "
                         "to k=1; watch tokens_per_dispatch in "
                         "stats())")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through the multi-replica fabric: this "
                         "many in-process LMServer replicas behind the "
                         "prefix-affinity Router (the client speaks "
                         "the same protocol to it)")
    args = ap.parse_args()

    model = get_model(
        "transformer_lm", vocab_size=args.vocab, d_model=64, num_heads=2,
        num_layers=2, max_len=args.prompt_len + args.max_new,
        dtype=jnp.float32, attention="dense",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    rng = np.random.default_rng(0)
    if args.paged:
        # shared system prefix (half the prompt): after the first
        # request finishes, every later prompt prefix-hits its blocks
        half = max(args.prompt_len // 2, 1)
        system = rng.integers(0, args.vocab, size=half).astype(np.int32)
        prompts = [
            np.concatenate([
                system,
                rng.integers(0, args.vocab,
                             size=args.prompt_len - half).astype(np.int32),
            ])
            for _ in range(args.prompts)
        ]
    else:
        prompts = [
            rng.integers(0, args.vocab,
                         size=args.prompt_len).astype(np.int32)
            for _ in range(args.prompts)
        ]

    engine_kw = {}
    if args.multi_step_k > 1:
        engine_kw["multi_step_k"] = args.multi_step_k
        print(f"multi-step decode: up to {args.multi_step_k} tokens "
              f"per dispatch in all-decode steady state")
    if args.prefill_chunk is not None:
        engine_kw["prefill_chunk"] = (None if args.prefill_chunk == 0
                                      else args.prefill_chunk)
    if args.paged:
        # largest small block size dividing max_len (paged mode needs
        # whole blocks); small blocks keep sharing visible on tiny
        # prompts
        max_len = args.prompt_len + args.max_new
        bs = next(b for b in (8, 4, 2, 1) if max_len % b == 0)
        engine_kw.update(paged=True, block_size=bs)
    if args.tp > 1:
        from distkeras_tpu.parallel.mesh import make_mesh

        engine_kw["mesh"] = make_mesh({"model": args.tp})
        print(f"tensor-parallel serving: tp={args.tp} over "
              f"{args.tp} of {len(jax.devices())} devices")
    if args.draft == "ngram":
        engine_kw.update(draft="ngram", spec_k=args.spec_k)
        print(f"speculative decoding: n-gram drafter, k={args.spec_k}")
    elif args.draft == "model":
        dmodel = get_model(
            "transformer_lm", vocab_size=args.vocab, d_model=32,
            num_heads=2, num_layers=1,
            max_len=args.prompt_len + args.max_new,
            dtype=jnp.float32, attention="dense",
        )
        dparams = dmodel.init(jax.random.PRNGKey(1),
                              jnp.zeros((1, 4), jnp.int32))
        engine_kw.update(draft=dmodel, draft_params=dparams,
                         spec_k=args.spec_k)
        print(f"speculative decoding: draft model "
              f"(d_model=32, 1 layer), k={args.spec_k} — untrained "
              f"drafts rarely survive verification, so expect a low "
              f"acceptance rate; the point here is that streams stay "
              f"bit-identical anyway")
    router = None
    servers = []
    if args.replicas > 1:
        # multi-replica fabric: N replicas (own registries, so the
        # fleet view below is a real aggregation) behind the Router
        from distkeras_tpu import telemetry as tel
        from distkeras_tpu.serving import Router

        for i in range(args.replicas):
            eng = ServingEngine(
                model, params, slots=args.slots,
                registry=tel.MetricRegistry(), tracer=tel.Tracer(),
                **engine_kw,
            )
            servers.append(LMServer(eng).start())
        engine = servers[0].engine
        router = Router(
            [("127.0.0.1", s.port, f"r{i}")
             for i, s in enumerate(servers)],
            block_size=engine_kw.get("block_size", 16),
            poll_interval=0.1,
            registry=tel.MetricRegistry(), tracer=tel.Tracer(),
        ).start()
        slo = None
        front_port = router.port
        print(f"fabric: {args.replicas} replicas behind the router "
              f"on port {front_port} (prefix-affine routing)")
    else:
        engine = ServingEngine(model, params, slots=args.slots,
                               **engine_kw)
        # SLO monitor (default serving rules) + stall watchdog: the
        # server starts/stops both; alerts served over the TCP op
        from distkeras_tpu.telemetry import (
            SloMonitor,
            default_serving_rules,
        )

        slo = SloMonitor(default_serving_rules(),
                         registry=engine.registry,
                         tracer=engine.tracer, interval_s=0.25)
        servers.append(LMServer(engine, slo=slo,
                                watchdog_timeout_s=30.0).start())
        front_port = servers[0].port
    telemetry_server = None
    if args.telemetry_port is not None:
        from distkeras_tpu.telemetry import TelemetryServer

        telemetry_server = TelemetryServer(
            registry=engine.registry, tracer=engine.tracer,
            flight=engine.flight, slo=slo,
            port=args.telemetry_port,
        ).start()
        print(f"telemetry: http://127.0.0.1:{telemetry_server.port}"
              f"/metrics (+ /metrics.json, /traces, /flight, /alerts)")
    client = ServingClient("127.0.0.1", front_port)
    try:
        rids = [client.generate(p, max_new_tokens=args.max_new)
                for p in prompts]
        total = 0
        for p, rid in zip(prompts, rids):
            toks = []
            for tok in client.stream(rid):  # arrives as the engine emits
                toks.append(tok)
            total += len(toks)
            solo = np.asarray(
                generate(model, params, jnp.asarray(p)[None], args.max_new)
            )[0, len(p):].tolist()
            tag = "parity OK" if toks == solo else "PARITY MISMATCH"
            print(f"request {rid}: {toks} ({tag})")
            assert toks == solo, (toks, solo)
        stats = client.stats()
        if router is not None:
            router.manager.probe_all()  # fresh per-replica counters
            stats = client.stats()
            served = {name: rep.get("stats", {}).get(
                "requests_completed", 0)
                for name, rep in stats["replicas"].items()}
            print(
                f"served {stats['requests_completed']} requests, "
                f"{total} tokens across {stats['replicas_routable']} "
                f"replicas (per replica: {served})"
            )
            r = stats["router"]
            print(
                f"router: {r['routed']:.0f} routed "
                f"({r['spilled']:.0f} spilled, "
                f"{r['failed_over']:.0f} failed over, "
                f"{r['failed']:.0f} failed), "
                f"affinity index {r['affinity_index_nodes']} nodes"
            )
        else:
            print(
                f"served {stats['requests_completed']} requests, "
                f"{total} tokens in {stats['ticks']} ticks "
                f"(mean occupancy {stats['mean_occupancy']}, "
                f"ttft p50 {stats['ttft_ms']['p50']:.1f}ms)"
            )
        if stats.get("pipeline"):
            dw = stats.get("device_wait_ms", {}).get("p50")
            print(
                f"pipeline: {stats.get('overrun_tokens', 0)} overrun "
                f"tokens dropped at reconciliation "
                f"({stats.get('overrun_pct', 0.0):.2f}% of the tokens "
                f"sampled), device-wait p50 "
                + (f"{dw:.2f}ms" if dw is not None else "n/a")
            )
        if args.multi_step_k > 1:
            tpd = stats.get("tokens_per_dispatch", {}).get("p50")
            print(
                f"multi-step: k={stats.get('multi_step_k')}, "
                f"{stats.get('dispatches', 0)} dispatches, "
                f"tokens/dispatch p50 "
                + (f"{tpd:.2f}" if tpd is not None else "n/a")
                + f", fallbacks {stats.get('multi_step_fallbacks', {})}"
            )
        if args.draft is not None:
            rate = (stats["accepted_tokens"] / stats["draft_tokens"]
                    if stats.get("draft_tokens") else 0.0)
            print(
                f"speculation: {stats['accepted_tokens']}"
                f"/{stats['draft_tokens']} draft tokens accepted "
                f"(rate {rate:.2f}, draft={args.draft}, "
                f"k={args.spec_k})"
            )
        if args.paged:
            print(
                f"paged cache: prefix hit fraction "
                f"{stats['prefix_hit_fraction']:.2f} "
                f"({stats['prefix_hit_tokens']}/{stats['prompt_tokens']} "
                f"prompt tokens served from cache), "
                f"{stats['blocks_in_use']} blocks in use"
            )
        # where did request 0 spend its time? — the span chain by trace id
        spans = client.trace_dump(trace=client.trace_of(rids[0]))
        for s in spans:
            attrs = {k: v for k, v in s.items()
                     if k not in ("trace", "span", "t0", "ms")}
            print(f"  trace {s['trace']} {s['span']:<8} {s['ms']:8.2f}ms "
                  + " ".join(f"{k}={v}" for k, v in attrs.items()))
        if router is None:
            # why was tick N slow? — the flight recorder's last ticks,
            # phase-decomposed (plan / device dispatch / stream fanout)
            fl = client.flight(last=3)
            print(f"flight recorder: {fl['meta']['recorded']} ticks "
                  f"retained; last {len(fl['ticks'])}:")
            for t in fl["ticks"]:
                print(f"  tick {t['tick']}: {t['tick_ms']:.2f}ms "
                      f"(plan {t['plan_ms']:.2f} / device "
                      f"{t['device_ms']:.2f} / stream "
                      f"{t['stream_ms']:.2f}), "
                      f"occ {t['occupancy']}, emitted {t['emitted']}")
        alerts = client.alerts()
        firing = [a["rule"] for a in alerts if a["firing"]]
        print(f"slo: {len(alerts)} rules, "
              + (f"FIRING: {firing}" if firing else "none firing"))
        if args.flight_dump:
            n = engine.flight.dump(args.flight_dump, reason="example")
            print(f"flight dump: {n} ticks -> {args.flight_dump} "
                  f"(render: python -m distkeras_tpu.telemetry.report "
                  f"--flight {args.flight_dump})")
    finally:
        client.close()
        if router is not None:
            router.stop()
        for s in servers:
            s.stop()
        if telemetry_server is not None:
            telemetry_server.stop()


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
