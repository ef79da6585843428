#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process drives the two main paths once, through the entry points a
user calls, at the full width of the flagship ``transformer_lm`` (d_model
2048, 8 heads of 256, 8 layers, vocab 8192, T 2048; weights random, from
``--seed``):

- ``cnn``    ``SingleTrainer`` on ``cifar_cnn`` at batch 2048, a few windows;
- ``train``  ``LMTrainer`` on one chip: bf16, fused CE, a few optimizer
             steps, the Pallas causal-attention call in the compiled step;
- ``serve``  ``ServingEngine`` -> ``LMServer`` -> ``ServingClient`` (a thread
             of this process), the slot engine and then the paged engine,
             every kernel argument left at its default; greedy streams
             are held to solo ``Model.generate()``.

``--four-chips`` runs instead (and only) the sharded pair: ``LMTrainer`` on
``{"dp": 1, "sp": 2, "tp": 2}`` and the tensor-parallel engine on a
4-device mesh, each against its one-chip twin.

Every phase prints one JSON line. The last line of a passing run is
``{"ok": true, "device": {...}}``; any failure raises and the process
exits non-zero without it. Times printed here are smoke readings of one
short run, not benchmark results. It needs a TPU: run it through the
chip tool (see README, "Running on the chip").
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

FLAGSHIP = dict(vocab_size=8192, d_model=2048, num_heads=8, num_layers=8,
                max_len=2048)

# The repo's parity contracts (engine == generate(), tp == one chip) are
# bit-identity and were only ever checked on the CPU backend. They stay
# the target here. Where the chip's tiling or reduction order breaks one
# on seeded, nearly flat logits, the check is not dropped: along the
# reference's own tokens, the token the engine chose must be within this
# many logit units of the reference's best — and the first divergence is
# printed. Seeded logits have a spread of about one unit, bf16 rounding
# through eight layers moves one by a few hundredths, and a wrong mask or
# a wrong page moves it by whole units.
LOGIT_TOL = 0.25
# four chips vs one, same seed and data: the first step's loss (the same
# parameters on both sides, as tests/test_spmd.py compares it) to that
# test's tolerance is the target; beyond it the run still has to stay
# inside the second. Later steps are printed, not bounded: adam without
# warm-up on a fresh 437M model amplifies bf16 rounding (5 % by step 11
# on the chip, both runs falling alike).
LOSS_RTOL_TARGET = 1e-4
LOSS_RTOL_CHIP = 5e-3


@dataclass
class Sizes:
    """What the phases run at. The defaults are the real thing; the CPU
    rehearsal (tests/test_chip_smoke.py) passes tiny ones."""

    lm: dict = field(default_factory=lambda: dict(FLAGSHIP))
    seq_len: int = 2048
    # B=8 does not fit beside what LMTrainer keeps alive: the compiled
    # window step alone is 4.88 GiB of state + 10.67 GiB of temporaries
    # (memory_analysis for a described v5e), and train() holds a second
    # copy of the parameters (1.63 GiB). B=4 is 4.88 + 5.54 + 1.63 GiB.
    lm_batch: int = 4
    lm_steps_per_epoch: int = 4
    lm_epochs: int = 3
    cnn_batch: int = 2048
    cnn_steps_per_epoch: int = 4
    cnn_epochs: int = 3
    slots: int = 8
    # int8 pages need 32-row blocks for the paged kernel; the engine's
    # default 16 sends an int8 pool to the gathered attend
    block_size: int = 32
    prompt_lens: tuple = (24, 100, 150, 150, 24)
    shared_prefix: int = 128  # of the two 150-token prompts
    new_tokens: int = 24
    expect_kernels: bool = True


def emit(phase: str, **fields):
    import jax

    print(json.dumps({"phase": phase,
                      "device_kind": jax.devices()[0].device_kind,
                      **fields}), flush=True)


# -- compile accounting -------------------------------------------------------


class CompileLog:
    """Every trace / lowering / backend compile JAX reports, with the
    function's name and its span on the clock, plus persistent-cache
    hits and misses. Compile seconds are the length of the union of the
    spans: traces nest and the engine compiles on its own thread, so a
    sum of durations counts some seconds twice."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.spans = []  # (event, fun_name, start, end)
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **kw):
        if event in self._EVENTS:
            self.spans.append((event, kw.get("fun_name"), start, end))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def mark(self):
        return len(self.spans), dict(self.cache)

    def since(self, mark) -> dict:
        n, cache = mark
        new = self.spans[n:]
        total, reach = 0.0, float("-inf")
        for start, end in sorted((s, e) for _, _, s, e in new):
            total += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        # backend compiles by function. The one-op programs of eager
        # code (initialisers, casts) are only counted: a function is one
        # of those when none of its spans — tracing included, which a
        # cache hit does not shorten — reaches a quarter of a second
        def base(fn):
            return fn[4:-1] if fn and fn.startswith("jit(") else fn

        longest: dict = {}
        for _, fn, start, end in new:
            longest[base(fn)] = max(longest.get(base(fn), 0.0), end - start)
        by_fn, small = {}, 0
        for event, fn, _, _ in new:
            if event != self._EVENTS[2]:
                continue
            if longest[base(fn)] < 0.25:
                small += 1
            else:
                by_fn[fn] = by_fn.get(fn, 0) + 1
        def secs(event):  # summed, so nested traces count twice
            return round(sum(e - s for ev, _, s, e in new if ev == event), 3)

        return {
            "compile_s": round(total, 3),
            "trace_s": secs(self._EVENTS[0]),
            "lower_s": secs(self._EVENTS[1]),
            # XLA's compile, or the read of a cached executable
            "backend_s": secs(self._EVENTS[2]),
            "backend_compiles": by_fn,
            "small_backend_compiles": small,
            "cache_hits": self.cache["hits"] - cache["hits"],
            "cache_misses": self.cache["misses"] - cache["misses"],
        }


def timed(log: CompileLog, fn):
    """(result, fields): wall seconds of ``fn()`` split into compile and
    run, and what compiled."""
    mark, t0 = log.mark(), time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    c = log.since(mark)
    return out, {"wall_s": round(wall, 3),
                 "run_s": round(wall - c["compile_s"], 3), **c}


def device_bytes(key: str):
    """``memory_stats()[key]`` of every device, or None where the backend
    reports none (the CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    return [s[key] for s in stats] if all(stats) else None


def memory_fields():
    return {"peak_bytes_in_use": device_bytes("peak_bytes_in_use"),
            "bytes_in_use": device_bytes("bytes_in_use")}


def kernel_calls(compiled_text: str) -> dict:
    """{kernel name: count} of the Pallas custom calls in one compiled
    program's text (``%paged_attention.3 = ... custom_call_target=
    "tpu_custom_call"`` -> ``paged_attention``)."""
    out: dict = {}
    for m in re.finditer(
            r"%([\w.\-]+?)(?:\.\d+)? = [^\n]*?"
            r"custom_call_target=\"tpu_custom_call\"", compiled_text):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


def assert_falling(losses, what: str):
    import numpy as np

    assert len(losses) >= 2 and np.all(np.isfinite(losses)), (what, losses)
    assert losses[-1] < losses[0], f"{what}: loss did not fall: {losses}"


# -- cnn ----------------------------------------------------------------------


def phase_cnn(sz: Sizes, seed: int, log: CompileLog):
    import numpy as np

    from distkeras_tpu.data.dataset import PartitionedDataset
    from distkeras_tpu.models import get_model
    from distkeras_tpu.trainers import SingleTrainer

    rng = np.random.default_rng(seed)
    n = sz.cnn_batch * sz.cnn_steps_per_epoch
    labels = rng.integers(0, 10, size=n)
    # one mean image per class plus noise: learnable in a few steps
    means = rng.normal(size=(10, 32, 32, 3)).astype(np.float32)
    x = means[labels] + rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    ds = PartitionedDataset.from_arrays(
        {"features": x, "label": np.eye(10, dtype=np.float32)[labels]},
        num_partitions=1)
    trainer = SingleTrainer(
        get_model("cifar_cnn"), worker_optimizer="sgd", learning_rate=0.05,
        batch_size=sz.cnn_batch, num_epoch=sz.cnn_epochs, seed=seed)
    _, t = timed(log, lambda: trainer.train(ds))
    losses = [h["loss"] for h in trainer.history]
    assert_falling(losses, "cnn")
    steps = len(losses)
    emit("cnn", trainer="SingleTrainer", model="cifar_cnn",
         batch=sz.cnn_batch, steps=steps, windows=sz.cnn_epochs,
         loss_first=losses[0], loss_last=losses[-1],
         smoke_samples_per_s=round(steps * sz.cnn_batch / t["run_s"], 1),
         **t, **memory_fields())


# -- train --------------------------------------------------------------------


def lm_tokens(sz: Sizes, seed: int):
    """Seeded synthetic corpus with a skewed unigram distribution, so a
    few optimizer steps already move the loss well below ln(vocab)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    V = sz.lm["vocab_size"]
    p = 1.0 / np.arange(1, V + 1) ** 1.2
    n = sz.lm_batch * sz.lm_steps_per_epoch
    return rng.choice(V, size=(n, sz.seq_len), p=p / p.sum()).astype(np.int32)


def run_lm_trainer(sz: Sizes, seed: int, log: CompileLog, axes: dict):
    """LMTrainer over ``axes`` on the seeded corpus. Returns (loss
    history, timing fields, trainer)."""
    from distkeras_tpu.data.dataset import PartitionedDataset
    from distkeras_tpu.models import get_model
    from distkeras_tpu.trainers import LMTrainer

    sp, tp = axes.get("sp", 1), axes.get("tp", 1)
    model = get_model(
        "transformer_lm", **sz.lm,
        attention="ring" if sp > 1 else "standard", seq_axis="sp",
        tp_size=tp, tp_axis="tp")
    ds = PartitionedDataset.from_arrays(
        {"tokens": lm_tokens(sz, seed)}, num_partitions=1)
    trainer = LMTrainer(
        model, axes=axes, batch_size=sz.lm_batch, num_epoch=sz.lm_epochs,
        worker_optimizer="adam", learning_rate=3e-4, seed=seed)
    _, t = timed(log, lambda: trainer.train(ds))
    return [h["loss"] for h in trainer.history], t, trainer


def lm_step_lowered(trainer, sz: Sizes, axes: dict) -> str:
    """StableHLO text of the window step LMTrainer ran, rebuilt the way
    ``LMTrainer._train`` builds it (same model, optimizer, mesh, window
    shape). Traces and lowers only; nothing is compiled."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distkeras_tpu.parallel.mesh import make_mesh
    from distkeras_tpu.parallel.spmd import make_lm_train_step
    from distkeras_tpu.utils.losses import get_optimizer

    axes = dict(axes)
    axes.setdefault("sp", 1)
    if axes.get("tp", 1) == 1:
        axes.pop("tp", None)
    mesh = make_mesh(axes)
    tp, sp = axes.get("tp", 1), axes["sp"]
    optimizer = get_optimizer(trainer.worker_optimizer,
                              trainer.learning_rate)
    params = jax.eval_shape(lambda: trainer.params)
    step = make_lm_train_step(
        trainer.model, optimizer, mesh, tp_axis="tp" if tp > 1 else None,
        params_template=params if tp > 1 else None, window=True)
    toks = jax.ShapeDtypeStruct(
        (sz.lm_steps_per_epoch, sz.lm_batch, sz.seq_len), jnp.int32,
        sharding=NamedSharding(
            mesh, P(None, "dp", "sp") if sp > 1 else P(None, "dp")))
    return step.lower(params, jax.eval_shape(optimizer.init, params),
                      toks).as_text()


def phase_train(sz: Sizes, seed: int, log: CompileLog):
    import jax.numpy as jnp

    from distkeras_tpu.ops import pallas_attention

    axes = {"dp": 1}
    losses, t, trainer = run_lm_trainer(sz, seed, log, axes)
    assert_falling(losses, "train")
    steps = len(losses)
    assert steps == sz.lm_steps_per_epoch * sz.lm_epochs, steps
    # one window per epoch was dispatched; the step function may have
    # been compiled once
    step_compiles = {f: n for f, n in t["backend_compiles"].items()
                     if f and "window" in f}
    assert sz.lm_epochs >= 2 and sum(step_compiles.values()) == 1, (
        f"the window step compiled {step_compiles} times over "
        f"{sz.lm_epochs} dispatches")
    hd = sz.lm["d_model"] // sz.lm["num_heads"]
    block = (pallas_attention.choose_block(
        sz.seq_len, hd, itemsize=jnp.dtype(trainer.model.dtype).itemsize)
        if pallas_attention.preferred(sz.seq_len, hd) else None)
    n_calls = lm_step_lowered(trainer, sz, axes).count("tpu_custom_call")
    if sz.expect_kernels:
        assert block and n_calls, (
            "no Pallas attention call in the lowered train step "
            f"(block={block}, tpu_custom_call x{n_calls})")
    emit("train", trainer="LMTrainer", axes=axes, model=sz.lm,
         dtype=str(jnp.dtype(trainer.model.dtype)), seq_len=sz.seq_len,
         batch=sz.lm_batch, remat=trainer.model.remat, optimizer="adam",
         fit="batch 4, no remat: B=8 needs 4.88+10.67 GiB for the step "
             "plus 1.63 GiB LMTrainer keeps (memory_analysis, v5e)",
         loss="fused-ce",
         attention=f"pallas-causal{block}" if block else "blocked/dense",
         tpu_custom_calls_in_step=n_calls, steps=steps,
         windows=sz.lm_epochs, step_compiles=step_compiles, losses=losses,
         smoke_steps_per_s=round(steps / t["run_s"], 3),
         smoke_tokens_per_s=round(
             steps * sz.lm_batch * sz.seq_len / t["run_s"], 1),
         **t, **memory_fields())


# -- serve --------------------------------------------------------------------


def serve_model(sz: Sizes, seed: int, num_kv_heads: int = 2):
    """The flagship architecture as S2 names it for serving (GQA, int8
    KV cache), with seeded random weights."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models import get_model

    model = get_model("transformer_lm", **sz.lm, num_kv_heads=num_kv_heads,
                      cache_dtype="int8")
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 jnp.zeros((1, 8), jnp.int32))
    return model, params


def make_prompts(sz: Sizes, seed: int):
    """Seeded prompts of ``sz.prompt_lens``; the two longest share their
    first ``sz.shared_prefix`` tokens."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, sz.lm["vocab_size"], size=n).astype(np.int32)
               for n in sz.prompt_lens]
    longest = sorted(range(len(prompts)), key=lambda i: -len(prompts[i]))
    a, b = longest[0], longest[1]
    prompts[b][:sz.shared_prefix] = prompts[a][:sz.shared_prefix]
    # the sharer is submitted once its sibling has finished, so the
    # sibling's blocks are in the prefix index by then
    return prompts, {b: a}


def reference_streams(model, params, prompts, n_new: int):
    """Solo greedy ``Model.generate()`` per prompt — the parity target."""
    import numpy as np

    from distkeras_tpu.models.wrapper import Model

    solo = Model(model, params)
    return [np.asarray(solo.generate(p[None], max_new_tokens=n_new)
                       )[0, len(p):].tolist() for p in prompts]


def drive_client(port: int, prompts, after: dict, n_new: int):
    """Submit every prompt through ``ServingClient`` from a thread of
    this process and collect ``(tokens, finish_reason)`` per prompt."""
    from distkeras_tpu.serving import ServingClient

    box: dict = {}

    def run():
        try:
            # the first tick of each shape compiles: no socket deadline
            # (a silent minute would end the reader), a long request one
            client = ServingClient("127.0.0.1", port, timeout=None,
                                   request_timeout=900.0)
            try:
                rids = {i: client.generate(p, n_new)
                        for i, p in enumerate(prompts) if i not in after}
                out = {}
                for i in sorted(rids):
                    out[i] = client.result(rids[i])
                for i, sibling in after.items():
                    assert sibling in out
                    out[i] = client.result(client.generate(prompts[i], n_new))
                box["out"] = [out[i] for i in range(len(prompts))]
            finally:
                client.close()
        except BaseException as e:  # re-raised on the main thread
            box["err"] = e

    t = threading.Thread(target=run, name="smoke-client")
    t.start()
    t.join()
    if "err" in box:
        raise box["err"]
    return box["out"]


def greedy_gaps(model, params, prompt, stream):
    """How far each token of ``stream`` is below the best logit when the
    stream itself is fed through the decode twin in one pass (the same
    quantized cache math as ``generate()``): 0 everywhere for a stream
    that is greedy under this reference's logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dm = model.clone(decode=True, parent=None)
    seq = np.concatenate([prompt, np.asarray(stream, np.int32)])[None]
    cache = jax.eval_shape(dm.init, jax.random.PRNGKey(0),
                           jnp.asarray(seq))["cache"]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache)
    logits, _ = jax.jit(
        lambda p, c, x: dm.apply({"params": p, "cache": c}, x,
                                 mutable=["cache"])
    )(params["params"], cache, jnp.asarray(seq))
    # logits[t] chooses token t + 1
    rows = np.asarray(logits[0, len(prompt) - 1:-1], np.float32)
    chosen = rows[np.arange(len(stream)), np.asarray(stream)]
    return rows.max(axis=-1) - chosen


def check_streams(tag: str, model, params, prompts, refs, got):
    """Engine streams against the solo references: bit-identity is the
    target; a divergence has to be a near-tie under ``LOGIT_TOL``."""
    exact, divergences = 0, []
    for i, (prompt, ref, (toks, reason)) in enumerate(
            zip(prompts, refs, got)):
        assert reason == "length" and len(toks) == len(ref), (
            f"{tag}: request {i} ended {reason!r} after {len(toks)} of "
            f"{len(ref)} tokens")
        if list(toks) == list(ref):
            exact += 1
            continue
        at = next(j for j, (a, b) in enumerate(zip(toks, ref)) if a != b)
        gaps = greedy_gaps(model, params, prompt, toks)
        divergences.append({
            "request": i, "first_divergence_at": at,
            "engine_token": int(toks[at]), "reference_token": int(ref[at]),
            "logit_gap_there": float(gaps[at]),
            "max_logit_gap": float(gaps.max())})
        assert gaps.max() <= LOGIT_TOL, (
            f"{tag}: request {i} left the greedy path of the reference "
            f"by {gaps.max():.4f} logits (> {LOGIT_TOL}) — first "
            f"divergence {divergences[-1]}")
    return {"streams": len(got), "streams_bit_identical": exact,
            "divergences": divergences, "logit_tol": LOGIT_TOL}


def tick_kernels(engine) -> dict:
    """{tick function: {kernel: calls}} for the engine's compiled mixed
    and decode ticks, read from their compiled text. No stat of the
    engine names the attend that ran (``stats()["prefill_kernel"]``
    echoes the configured string), so this takes each tick's jitted
    function from the module's cached builders — the very objects
    ``_plan_dispatch_*`` call — and lowers it once more on the engine's
    own state arrays (nothing runs, nothing is donated): the same
    program as the dispatched one, so the compile is a cache hit."""
    import jax.numpy as jnp

    from distkeras_tpu.serving import engine as eng

    S, C = engine.slots, engine.prefill_chunk
    cfgs = (eng._IDLE_CFG,) * S  # every request here is greedy
    state = (engine._params_only, engine._cache, engine._last_logits,
             engine._rngs)

    def packed(n):  # the host control buffer, as _upload hands it over
        return jnp.zeros((n,), jnp.int32)

    layout = engine._layout
    # the layout's head of a control buffer: tables and seq lens per row
    # where the cache is paged, nothing where it is slots (whose plain
    # decode tick takes no buffer at all)
    head = S * (layout.max_blocks + 1) if layout.paged else 0
    ticks = {
        layout.tag("mixed_tick"): (
            eng._mixed_tick_fn(layout, cfgs, C, engine._ctx),
            (packed(head + S * (C + 2)),)),
        layout.tag("tick"): (
            eng._tick_fn(layout, cfgs, engine._ctx),
            (packed(head),) if head else ()),
    }
    return {name: kernel_calls(
        fn.lower(*state, *extra).compile().as_text())
        for name, (fn, extra) in ticks.items()}


def attend_label(kernels: dict) -> str:
    names = sorted(k for k in kernels
                   if k in ("paged_attention", "splash_prefill",
                            "slot_decode_attend"))
    return "+".join(names) if names else "dense"


def serve_once(tag: str, sz: Sizes, seed: int, log: CompileLog, model,
               params, prompts, after, refs, **engine_kw):
    """One engine behind ``LMServer`` on a loopback port: a warm-up
    round with the same shape of traffic (other tokens), then the
    checked round."""
    from distkeras_tpu.serving import LMServer, ServingEngine
    from distkeras_tpu.telemetry import recompiles

    before = recompiles.counts()
    engine = ServingEngine(model, params, slots=sz.slots, **engine_kw)
    server = LMServer(engine).start()
    try:
        warm_prompts, warm_after = make_prompts(sz, seed + 1)
        _, t_warm = timed(log, lambda: drive_client(
            server.port, warm_prompts, warm_after, sz.new_tokens))
        engine.mark_steady()
        got, t = timed(log, lambda: drive_client(
            server.port, prompts, after, sz.new_tokens))
        recompiled = engine.recompiles_since_mark()
        stats = engine.stats()
    finally:
        server.stop()
    assert recompiled == {}, f"{tag}: recompiled after warm-up: {recompiled}"
    traced = {f: n - before.get(f, 0)
              for f, n in recompiles.counts().items()
              if n - before.get(f, 0)}
    parity = check_streams(tag, model, params, prompts, refs, got)
    kernels = tick_kernels(engine)
    n_tok = sum(len(toks) for toks, _ in got)
    emit(tag, engine={"slots": sz.slots, **{
             k: dict(v.shape) if k == "mesh" else v
             for k, v in engine_kw.items()}},
         kv_heads=model.num_kv_heads, cache_dtype="int8",
         prefill_chunk=engine.prefill_chunk,
         paged_kernel=(engine._dm_paged.paged_kernel if engine.paged
                       else None),
         prefill_kernel=engine.prefill_kernel,
         requests=len(prompts), prompt_lens=[len(p) for p in prompts],
         new_tokens=n_tok, traced=traced,
         attend={f: attend_label(k) for f, k in kernels.items()},
         kernel_calls=kernels, recompiles_after_warmup=recompiled,
         prefix_hit_tokens=stats.get("prefix_hit_tokens"),
         ticks=stats.get("ticks"), warmup=t_warm,
         smoke_tokens_per_s=round(n_tok / t["run_s"], 1),
         **parity, **t, **memory_fields())
    return engine, kernels


def paged_gate_note(sz: Sizes, num_kv_heads: int) -> str:
    """What the paged kernel's gate says of this model's two tick shapes
    — printed, because the decode tick's answer is 'no' at G = 4."""
    from distkeras_tpu.ops import paged_attention as pa
    from distkeras_tpu.serving.scheduler import DEFAULT_PREFILL_CHUNK as C

    H = sz.lm["num_heads"]
    G, hd = H // num_kv_heads, sz.lm["d_model"] // H
    dec = pa.supports(1, G, hd, sz.block_size, 1, num_kv_heads)
    mix = pa.supports(C, G, hd, sz.block_size, 1, num_kv_heads)
    return (f"paged_attention.supports: mixed tick T={C} G={G} -> {mix}; "
            f"decode tick T=1 G={G} -> {dec} ((T*G) % 8 = {G % 8}: the "
            "gate sends it to the gathered attend)")


def phase_serve(sz: Sizes, seed: int, log: CompileLog):
    model, params = serve_model(sz, seed)
    prompts, after = make_prompts(sz, seed)
    refs, t_ref = timed(log, lambda: reference_streams(
        model, params, prompts, sz.new_tokens))
    emit("serve_reference", source="Model.generate() greedy, solo", **t_ref)
    # (a) the README quickstart's engine: slot cache, 64-token chunks
    _, k_slot = serve_once("serve_slot", sz, seed, log, model, params,
                           prompts, after, refs)
    # (b) paged pool + radix prefix cache
    emit("serve_gate", note=paged_gate_note(sz, model.num_kv_heads),
         block_size_note="block_size=32 passed: the default 16 sends an "
                         "int8 pool to the gather")
    _, k_paged = serve_once("serve_paged", sz, seed, log, model, params,
                            prompts, after, refs, paged=True,
                            prefix_cache=True, block_size=sz.block_size)
    if sz.expect_kernels:
        assert k_slot["serve.mixed_tick"].get("splash_prefill"), k_slot
        assert k_paged["serve.paged_mixed_tick"].get("paged_attention"), (
            k_paged)


# -- four chips ---------------------------------------------------------------


def shard_report(tree, expect: int, what: str) -> dict:
    """Every leaf of ``tree`` lives on ``expect`` devices."""
    import jax

    sizes = sorted({len(leaf.sharding.device_set)
                    for leaf in jax.tree.leaves(tree)})
    assert sizes == [expect], (
        f"{what}: leaves span {sizes} devices, expected {expect} each")
    return {"leaves": len(jax.tree.leaves(tree)), "devices_per_leaf": expect}


def assert_every_device_held(what: str, key: str):
    """Code that only ever saw one real device may have put everything
    on ``jax.devices()[0]``: every device must report bytes."""
    used = device_bytes(key)
    assert used is None or all(u > 0 for u in used), (
        f"{what}: a device held nothing: {key}={used}")
    return used


def phase_four_train(sz: Sizes, seed: int, log: CompileLog):
    import jax
    import numpy as np

    axes = {"dp": 1, "sp": 2, "tp": 2}
    losses4, t4, tr4 = run_lm_trainer(sz, seed, log, axes)
    assert_falling(losses4, "four_train")
    # the trainer hands back host arrays, so it is the peak that shows
    # what each device held while the sharded step ran (this phase runs
    # first in the process)
    used = assert_every_device_held("four_train", "peak_bytes_in_use")
    n_calls = lm_step_lowered(tr4, sz, axes).count("tpu_custom_call")
    if sz.expect_kernels:
        assert n_calls, "no Pallas call in the sharded train step"
    del tr4
    gc.collect()
    losses1, t1, _ = run_lm_trainer(sz, seed, log, {"dp": 1})
    rel = np.abs(np.asarray(losses4) - np.asarray(losses1)) / np.abs(
        np.asarray(losses1))
    assert_falling(losses1, "four_train one-chip twin")
    assert rel[0] <= LOSS_RTOL_CHIP, (
        f"sharded first loss left the one-chip run's by {rel[0]:.2e}: "
        f"{losses4} vs {losses1}")
    emit("four_train", trainer="LMTrainer", axes=axes, model=sz.lm,
         batch=sz.lm_batch, seq_len=sz.seq_len,
         attention="ring over sp (pallas_pair), Megatron over tp",
         tpu_custom_calls_in_step=n_calls, losses=losses4,
         one_chip_losses=losses1, first_loss_rel_diff=float(rel[0]),
         max_loss_rel_diff=float(rel.max()),
         meets_test_spmd_rtol=bool(rel[0] <= LOSS_RTOL_TARGET),
         loss_rtol_target=LOSS_RTOL_TARGET, loss_rtol_chip=LOSS_RTOL_CHIP,
         peak_bytes_after_sharded_run=used, sharded=t4, one_chip=t1,
         devices=len(jax.devices()), **memory_fields())


def phase_four_serve(sz: Sizes, seed: int, log: CompileLog):
    import jax

    from distkeras_tpu.parallel.mesh import make_mesh

    # the KV-head count must divide by the mesh: 4, not the 2 of `serve`
    model, params = serve_model(sz, seed, num_kv_heads=4)
    prompts, after = make_prompts(sz, seed)
    refs = reference_streams(model, params, prompts, sz.new_tokens)
    kw = dict(paged=True, prefix_cache=True, block_size=sz.block_size)
    one, _ = serve_once("four_serve_one_chip", sz, seed, log, model, params,
                        prompts, after, refs, **kw)
    del one
    gc.collect()
    mesh = make_mesh({"model": 4})
    tp, _ = serve_once("four_serve_tp", sz, seed, log, model, params,
                       prompts, after, refs, mesh=mesh, **kw)
    # heads are sharded, everything else replicated: every parameter and
    # cache leaf lives on all four devices, and each holds bytes
    report = {"params": shard_report(tp._params_only, 4, "tp params"),
              "cache": shard_report(tp._cache, 4, "tp cache")}
    used = assert_every_device_held("four_serve_tp", "bytes_in_use")
    emit("four_serve", mesh={"model": 4}, kv_heads=4,
         note="num_kv_heads=4 so the KV heads divide by the mesh",
         contract="tp streams == one-chip streams == generate() "
                  "(tests/test_tp_serving.py), each held by check_streams",
         shards=report, bytes_in_use=used, devices=len(jax.devices()))


# -- main ---------------------------------------------------------------------


def require_tpu(min_devices: int = 1):
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX's default backend is "
                 f"'{backend}'")
    if jax.device_count() < min_devices:
        sys.exit(f"chip_smoke: needs {min_devices} chips, JAX sees "
                 f"{jax.device_count()}")


def run(four_chips: bool, seed: int, sz: Optional[Sizes] = None):
    import jax

    from distkeras_tpu import networking
    from distkeras_tpu.utils import compile_cache

    t0 = time.perf_counter()
    sz = sz or Sizes()
    dev = jax.devices()[0]
    log = CompileLog()
    # native/*.so is built from the tracked sources on first use; here a
    # build that fails is an error, not a switch to the Python loops
    if not networking.native_transport_active():
        raise RuntimeError("native transport did not build: "
                           f"{networking.native_transport_error}")
    emit("start", four_chips=four_chips, seed=seed,
         compile_cache_dir=compile_cache.enable(), native_transport=True,
         devices=jax.device_count(), jax=jax.__version__)
    phases = ((phase_four_train, phase_four_serve) if four_chips
              else (phase_cnn, phase_train, phase_serve))
    for phase in phases:
        phase(sz, seed, log)
        gc.collect()
    emit("total", seconds=round(time.perf_counter() - t0, 1),
         **log.since((0, {"hits": 0, "misses": 0})))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step and the tp "
                         "engine, each against its one-chip twin")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    require_tpu(4 if args.four_chips else 1)
    run(args.four_chips, args.seed)


if __name__ == "__main__":
    main()
