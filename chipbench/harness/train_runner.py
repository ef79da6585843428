"""Traffic kind ``train_job``: one ``LMTrainer.train()`` call. Its first
epoch is set-up (it compiles and warms the window step), its further
epochs are the window; the clock is the trainer's own ``metrics_path``
rows, whose first row of an epoch is written right after that epoch's
losses reached the host."""

from __future__ import annotations

import gc
import json
import math
import os
import threading
import time

import numpy as np

from chipbench.harness import device, spec, traffic
from chipbench.harness.check import Verdict

CHECK_STEPS = 3  # the reference follows the first three optimizer steps


def build_trainer(cfg: dict, params, corpus, epochs: int, seed: int,
                  metrics_path: str):
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.data.dataset import PartitionedDataset
    from distkeras_tpu.models import get_model
    from distkeras_tpu.trainers import LMTrainer

    tr = cfg["trainer"]
    sched = tr["schedule"]
    model = get_model(spec.model_name(cfg), **cfg["model"],
                      dtype=jnp.dtype(cfg["compute_dtype"]),
                      remat=tr["remat"])
    optimizer = optax.adam(optax.linear_schedule(
        sched["init"], sched["peak"], sched["warmup_steps"]))
    trainer = LMTrainer(
        model, params=params, axes=dict(tr["axes"]),
        batch_size=tr["batch_size"], num_epoch=epochs,
        worker_optimizer=optimizer, seed=seed % (2 ** 31 - 1),
        metrics_path=metrics_path)
    dataset = PartitionedDataset.from_arrays({"tokens": corpus},
                                             num_partitions=1)
    return trainer, dataset


def epoch_stamps(rows, steps_per_epoch: int):
    """Seconds (on the metrics writer's clock) at which each epoch's
    device work had ended: the ``t`` of the epoch's first row."""
    steps = [r for r in rows if "step" in r]
    return [steps[i]["t"] for i in range(0, len(steps), steps_per_epoch)]


def run(cell: dict, seed: int, seconds: float, trace: bool, ctx) -> dict:
    """``ctx`` carries what the entry point set up: ``proc_start``
    (``time.time()`` at process start), ``out_dir``, ``compile_log``."""
    import jax

    cfg, job = cell["config_spec"], cell["traffic_spec"]
    B, T = cfg["trainer"]["batch_size"], job["seq_len"]
    S = job["steps_per_epoch"]
    tokens_per_epoch = S * B * T
    # LMTrainer takes its number of epochs before it starts and offers
    # no way to stop: the window's length is set from the rate this job
    # was sized at, and the rate is then taken over the stamps themselves
    epochs = 1 + max(2, math.ceil(
        seconds * job["nominal_tokens_per_s"] / tokens_per_epoch))
    params = spec.reference(cfg).make_params(cfg, seed)
    corpus = traffic.unigram_corpus(job, cfg["model"]["vocab_size"],
                                    S * B, seed)
    metrics_path = os.path.join(ctx["out_dir"], f"{cell['name']}.metrics.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)
    trainer, dataset = build_trainer(cfg, params, corpus, epochs, seed,
                                     metrics_path)
    del params  # the trainer holds them now
    marks: dict = {}
    trace_dir = os.path.join(ctx["out_dir"], f"trace.{cell['name']}")

    def watch():
        """Notes when set-up ended, and traces a few epochs of the
        window: ``train()`` has the main thread until it returns."""
        def rows_logged():
            w = trainer.metrics_writer
            return len(w.records) if w is not None else 0

        while rows_logged() < S and not marks.get("done"):
            time.sleep(0.01)
        marks["compile_mark"] = ctx["compile_log"].mark()
        if not trace:
            return
        first = 1 + job["trace_skip_epochs"]
        while rows_logged() < first * S and not marks.get("done"):
            time.sleep(0.01)
        jax.profiler.start_trace(trace_dir)
        marks["traced"] = True
        last = first + job["trace_epochs"]
        while rows_logged() < last * S and not marks.get("done"):
            time.sleep(0.01)
        jax.profiler.stop_trace()

    watcher = threading.Thread(target=watch, name="bench-watch")
    t_call = time.time()
    watcher.start()
    try:
        with jax.profiler.TraceAnnotation("bench:LMTrainer.train"):
            trainer.train(dataset)
    finally:
        marks["done"] = True
        watcher.join()
    in_window = ctx["compile_log"].since(marks["compile_mark"])
    device_peak = device.describe(ctx["devices"])
    with open(metrics_path) as f:
        rows = [json.loads(line) for line in f]
    stamps = epoch_stamps(rows, S)
    if len(stamps) != epochs:
        raise RuntimeError(f"{len(stamps)} epoch stamps for {epochs} epochs")
    window_s = stamps[-1] - stamps[0]
    losses = [h["loss"] for h in trainer.history]
    window_losses = losses[S:]
    result = {
        "setup_s": (t_call - ctx["proc_start"]) + stamps[0],
        "window_s": window_s,
        "train_tok_s": (epochs - 1) * tokens_per_epoch / window_s,
        # beside the rate, so that a whole run that reads slow says
        # whether every epoch was slow or one of them stalled
        "epoch_s_median": float(np.median(np.diff(stamps))),
        "epoch_s_max": float(np.max(np.diff(stamps))),
        "attempted": len(window_losses),
        "failed": int(sum(not math.isfinite(x) for x in window_losses)),
        "compiles_in_window": in_window, "device": device_peak,
        "losses_first": losses[:CHECK_STEPS], "loss_last": losses[-1],
        "epochs": epochs, "default_span": "bench:LMTrainer.train",
        "trace_dir": trace_dir if marks.get("traced")
        else None,
    }
    if in_window["backend_compiles"]:
        raise RuntimeError(f"compiled inside the window: {in_window}")
    # the program's state is gone with train()'s frame; the reference
    # makes the same weights again from the seed and follows three steps
    del trainer, dataset
    gc.collect()
    t0 = time.time()
    result["check_args"] = (cell, cfg, seed, corpus, losses)
    result["verdict"] = check(*result["check_args"])
    result["check_s"] = time.time() - t0
    return result


def check(cell: dict, cfg: dict, seed: int, corpus, losses,
          precision: str = "f32") -> Verdict:
    """The trainer's first losses against the reference's, step by step,
    and the last loss against the first. With a lower ``precision`` this
    is the control: the reference in that precision stands in the
    trainer's place for the first losses."""
    limits = cell["limits"]
    B = cfg["trainer"]["batch_size"]
    batches = np.asarray(corpus).reshape(-1, B, corpus.shape[1])[:CHECK_STEPS]

    reference = spec.reference(cfg)

    def follow(p):
        return reference.train_losses(
            cfg, reference.make_params(cfg, seed), batches, p)[0]

    ref_losses = follow("f32")
    first = losses if precision == "f32" else follow(precision)
    verdict = Verdict()
    for k, (got, want) in enumerate(zip(first, ref_losses)):
        verdict.hold(f"loss_rel_gap.step{k}", abs(got - want) / abs(want),
                     limits["loss_rel_gap"])
        verdict.rows[-1].update(loss=got, reference=want)
    # a step that returns its state unchanged leaves the loss where it was
    verdict.hold("last_loss_over_first", losses[-1] / losses[0],
                 limits["last_loss_over_first"])
    return verdict
