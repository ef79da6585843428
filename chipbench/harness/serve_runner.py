"""Traffic kinds ``closed_loop`` and ``open_loop``: a ``ServingEngine``
behind ``LMServer`` on a loopback port, driven through ``ServingClient``
from threads of this process. Every time is taken on the client's side.

``closed_loop``: ``clients`` callers, each sending its next request when
its last completed; the metric is the output tokens that reached the
clients inside the window. ``open_loop``: requests sent on a Poisson
schedule fixed by the seed whether or not earlier ones finished, each
timed from when it was due.
"""

from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

from chipbench.harness import device, spec, traffic
from chipbench.harness.check import Verdict, served_gaps

WARM_NEW = 4  # the warm-up request decodes, after its two chunks of prefill
REQUEST_TIMEOUT_S = 120.0


class Request:
    """What the client saw of one request."""

    __slots__ = ("index", "prompt", "out_len", "due", "sent", "times",
                 "tokens", "reason", "error", "ended")

    def __init__(self, index, prompt, out_len, due=None):
        self.index, self.prompt, self.out_len, self.due = (
            index, prompt, out_len, due)
        self.sent = None
        self.times, self.tokens = [], []
        self.reason = self.error = self.ended = None

    @property
    def ok(self) -> bool:
        return (self.error is None and self.reason == "length"
                and len(self.tokens) == self.out_len)

    def drive(self, client):
        """Send, then read the stream to its end, stamping each token."""
        import jax

        try:
            self.sent = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:client.generate"):
                rid = client.generate(self.prompt, self.out_len)
            for kind, value in client.frames(rid):
                if kind == "end":
                    self.reason = value
                    break
                self.times.append(time.perf_counter())
                self.tokens.append(value)
        except Exception as e:  # counted as failed, never hidden
            self.error = f"{type(e).__name__}: {e}"
        self.ended = time.perf_counter()


def start_system(cfg: dict, params, out_dir: str):
    """(engine, server, client) as the configuration states them."""
    import jax.numpy as jnp

    from distkeras_tpu import telemetry
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import LMServer, ServingClient, ServingEngine

    model = get_model(spec.model_name(cfg), **cfg["model"],
                      dtype=jnp.dtype(cfg["compute_dtype"]))
    engine = ServingEngine(
        model, params, registry=telemetry.MetricRegistry(),
        tracer=telemetry.Tracer(capacity=1 << 16),
        postmortem_dir=out_dir, **cfg["engine"])
    server = LMServer(engine).start()
    client = ServingClient("127.0.0.1", server.port, timeout=None,
                           request_timeout=REQUEST_TIMEOUT_S)
    return engine, server, client


def warm_prompt_len(engine) -> int:
    """A prompt that takes two prefill chunks, one whole and one of six
    tokens (70 at the engine's default chunk of 64): with the decode
    after it, both tick programs compile in set-up."""
    return (engine.prefill_chunk or 64) + 6


def make_request(cfg, sizes, seed, index, due=None) -> Request:
    p_len, o_len = sizes[index % len(sizes)]
    return Request(index, traffic.prompt_tokens(
        cfg["model"]["vocab_size"], p_len, seed, index), o_len, due)


def run(cell: dict, seed: int, seconds: float, trace: bool, ctx) -> dict:
    cfg, mix = cell["config_spec"], cell["traffic_spec"]
    params = spec.reference(cfg).make_params(cfg, seed)
    engine, server, client = start_system(cfg, params, ctx["out_dir"])
    trace_dir = os.path.join(ctx["out_dir"], f"trace.{cell['name']}")
    try:
        # warm the two tick programs on traffic of the mix's own shape
        # (other tokens), then declare the steady state
        for i in range(2):
            w = Request(-1 - i, traffic.prompt_tokens(
                cfg["model"]["vocab_size"], warm_prompt_len(engine), seed,
                -1 - i), WARM_NEW)
            w.drive(client)
            if not w.ok:
                raise RuntimeError(f"warm-up request failed: {w.reason} "
                                   f"{w.error}")
        engine.mark_steady()
        compile_mark = ctx["compile_log"].mark()
        drive = {"closed_loop": closed_loop, "open_loop": open_loop}[
            mix["kind"]]
        out = drive(cfg, mix, seed, seconds, client,
                    trace_dir if trace else None)
        out["compiles_in_window"] = ctx["compile_log"].since(compile_mark)
        out["recompiles"] = engine.recompiles_since_mark()
        out["engine_stats"] = engine.stats()
        out["flight"] = client.flight()
        out["tracer_spans"] = engine.tracer.dump()
        out["device"] = device.describe(ctx["devices"])
        out["trace_dir"] = trace_dir if trace else None
        # what the engine's own thread does in a gap, the trace cannot
        # say yet: the program has no spans on the profiler's clock
        out["default_span"] = "engine_thread_no_span"
    finally:
        client.close()
        server.stop()
    if out["compiles_in_window"]["backend_compiles"] or out["recompiles"]:
        raise RuntimeError(
            f"compiled inside the window: {out['compiles_in_window']} "
            f"{out['recompiles']}")
    out["setup_s"] = out.pop("window_start") - ctx["proc_start_perf"]
    # the engine's cache goes; the weights stay, they are the benchmark's
    del engine, server, client
    gc.collect()
    t0 = time.time()
    out["check_args"] = (cell, cfg, seed, params, out["finished"])
    out["verdict"] = check(*out["check_args"])
    out["check_s"] = time.time() - t0
    return out


def trace_window(trace_dir, mix: dict, window_start: float):
    """A thread that puts a few seconds of the window under
    ``jax.profiler`` (``None`` where the run is not traced)."""
    if trace_dir is None:
        return None

    def body():
        import jax

        time.sleep(max(0.0, window_start + mix["trace_after_s"]
                       - time.perf_counter()))
        jax.profiler.start_trace(trace_dir)
        time.sleep(mix["trace_s"])
        jax.profiler.stop_trace()

    t = threading.Thread(target=body, name="bench-trace")
    t.start()
    return t


def closed_loop(cfg, mix, seed, seconds, client, trace_dir) -> dict:
    clients = mix["clients"]
    sizes = traffic.request_sizes(mix, mix["size_pool"], seed)
    stop = threading.Event()
    started: list = []  # list.append is atomic; read after the window

    def caller(c: int):
        k = c
        while not stop.is_set():
            req = make_request(cfg, sizes, seed, k)
            started.append(req)
            req.drive(client)
            k += clients

    threads = [threading.Thread(target=caller, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(clients)]
    t_send = time.perf_counter()
    for t in threads:
        t.start()
    w0 = t_send + mix["ramp_s"]
    w1 = w0 + seconds
    tracer = trace_window(trace_dir, mix, w0)
    time.sleep(max(0.0, w1 - time.perf_counter()))
    stop.set()
    if tracer:
        tracer.join()
    every = list(started)
    # requests that ended inside the window; those cut by its end are
    # neither attempted nor failed, but their tokens count below
    ended = [r for r in every if r.ended is not None and w0 <= r.ended <= w1]
    # tokens that reached a client inside the window, whichever request
    # they belong to: all the work and all the time of the window
    tokens = sum(1 for r in every for ts in list(r.times) if w0 <= ts <= w1)
    return {"window_start": w0, "window_s": seconds,
            "serve_tok_s": tokens / seconds, "tokens_in_window": tokens,
            "attempted": len(ended), "failed": sum(not r.ok for r in ended),
            "finished": [r for r in ended if r.ok], "requests": ended}


def open_loop(cfg, mix, seed, seconds, client, trace_dir) -> dict:
    duration = mix["ramp_s"] + seconds
    due = traffic.poisson_schedule(mix["rate_rps"], duration, seed)
    sizes = traffic.request_sizes(mix, len(due), seed)
    t_send = time.perf_counter() + 0.05
    w0 = t_send + mix["ramp_s"]
    w1 = w0 + seconds
    tracer = trace_window(trace_dir, mix, w0)
    reqs, threads = [], []
    for i, d in enumerate(due):
        req = make_request(cfg, sizes, seed, i, due=t_send + float(d))
        delay = req.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=req.drive, args=(client,), daemon=True,
                             name=f"bench-req-{i}")
        t.start()
        reqs.append(req)
        threads.append(t)
    deadline = time.perf_counter() + REQUEST_TIMEOUT_S
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    if tracer:
        tracer.join()
    counted = [r for r in reqs if w0 <= r.due < w1]
    for r in counted:
        if r.reason is None and r.error is None:
            r.error = "no end of stream before the run's deadline"
    good = [r for r in counted if r.ok]
    ttft = [(r.times[0] - r.due) * 1e3 for r in good]
    gaps = [(b - a) * 1e3 for r in good
            for a, b in zip(r.times, r.times[1:])]
    late = [(r.sent - r.due) * 1e3 for r in counted if r.sent is not None]
    out = {"window_start": w0, "window_s": seconds,
           "attempted": len(counted),
           "failed": len(counted) - len(good), "finished": good,
           "requests": counted, "gen_late_ms": late, "ttft_ms": ttft,
           "ttft_samples": len(ttft), "itl_samples": len(gaps),
           "drain_s": time.perf_counter() - w1}
    if ttft and gaps:
        # an end-to-end metric's file picks its percentile by its name
        for p in (50, 90, 95, 99):
            out[f"ttft_p{p}_ms"] = traffic.percentile(ttft, p)
            out[f"itl_p{p}_ms"] = traffic.percentile(gaps, p)
    return out


def sample_order(finished, seed: int):
    """The finished requests in the order the check takes them: the
    longest first, the others shuffled by the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r.prompt) + len(r.tokens)))
    rest = order[1:]
    return [order[0]] + [rest[i] for i in
                         traffic.rng(seed, 5).permutation(len(rest))]


def padded_length(n: int) -> int:
    """The length the reference's sequence is padded to: the least power
    of two that holds it, 256 at the least, so that few lengths compile."""
    return max(256, 1 << (n - 1).bit_length())


def request_readings(cfg, variables, r, precision: str = "f32"):
    """``(gaps, margins)`` over the served tokens of one request under
    the float32 reference: the gap of each served token, or (for a lower
    ``precision``) of the token that precision itself puts first at the
    same position, and the reference's own margin there."""
    forward_logits = spec.reference(cfg).forward_logits
    seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
    at = np.arange(len(r.prompt) - 1, len(seq) - 1)
    pad = padded_length(len(seq))
    ref = forward_logits(cfg, variables, seq, at, "f32", pad)
    served = r.tokens
    if precision != "f32":
        low = forward_logits(cfg, variables, seq, at, precision, pad)
        served = np.asarray(low.argmax(axis=-1))
    return served_gaps(ref, served)


def sample_readings(cfg, variables, finished, seed: int, limits: dict,
                    precision: str = "f32"):
    """Gaps and margins of a sample of the finished requests, drawn by
    the seed with the longest in it: ``sample_requests`` of them, then
    more (up to ``sample_requests_max``) until the sample holds
    ``near_ties_wanted`` positions whose margin is under
    ``near_tie_margin``. Only there can rounding show in a greedy token,
    and a seed whose logits are far apart needs more requests to say as
    much."""
    gaps, margins, near = [], [], 0
    for i, r in enumerate(sample_order(finished, seed)[
            :limits["sample_requests_max"]]):
        enough = near >= limits["near_ties_wanted"]
        if i >= limits["sample_requests"] and enough:
            break
        gap, margin = request_readings(cfg, variables, r, precision)
        gaps.append(gap)
        margins.append(margin)
        near += int((margin < limits["near_tie_margin"]).sum())
    return gaps, margins


def check(cell, cfg, seed, variables, finished,
          precision: str = "f32") -> Verdict:
    """The widest gap catches a token that is not the model's (a wrong
    mask or slot moves it by whole units). Rounding shows in how often a
    served token lies far under the reference's best: a tail that the
    stated bfloat16 all but never reaches and a lower precision reaches
    many times over, counted against the near-ties the sample holds
    (no fewer than ``near_ties_wanted``, so that a thin sample cannot
    fail a sound run on one token)."""
    limits = cell["limits"]
    verdict = Verdict()
    gaps, margins = sample_readings(cfg, variables, finished, seed, limits,
                                    precision)
    verdict.hold("requests_missing_from_sample", float(not gaps), 0.0)
    if gaps:
        requests = len(gaps)
        gaps, margins = np.concatenate(gaps), np.concatenate(margins)
        verdict.hold("served_logit_gap.max", float(gaps.max()),
                     limits["served_logit_gap_max"])
        far = int((gaps > limits["far_off_gap"]).sum())
        near = int((margins < limits["near_tie_margin"]).sum())
        verdict.hold("served_far_off_per_near_tie",
                     far / max(near, limits["near_ties_wanted"]),
                     limits["served_far_off_per_near_tie"])
        verdict.rows[-1].update(
            requests=requests, tokens=int(gaps.size), far_off=far,
            near_ties=near, gap_mean=float(gaps.mean()),
            off_best_share=float((gaps > 0).mean()))
    return verdict
