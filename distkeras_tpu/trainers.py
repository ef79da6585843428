"""Trainers — user-facing training orchestration.

Reference: distkeras/trainers.py. Every reference trainer class has a named
counterpart here with the same constructor vocabulary (worker_optimizer,
loss, metrics, features_col, label_col, batch_size, num_epoch,
communication_window, num_workers, rho/learning_rate for the elastic
family) and the same ``train(dataset) -> model`` contract.

Execution redesign (SURVEY.md §3.2's "TPU translation"):

- The reference's ``df.rdd.repartition(n).mapPartitionsWithIndex(worker
  .train).collect()`` becomes: repartition the :class:`PartitionedDataset`,
  run one worker per partition — as host threads driving jit-compiled
  device step loops (async algorithms, preserving real staleness), or as a
  single SPMD program over the device mesh (sync algorithms).
- The driver-hosted socket parameter server becomes an in-process
  lock-protected center variable (:mod:`distkeras_tpu.parameter_servers`)
  for async semantics, and ``lax.psum`` over ICI for sync semantics.
- ``collect()`` + ``ps.get_model()`` become a ``device_get`` of the final
  params.
"""

from __future__ import annotations

import threading
import time
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distkeras_tpu import parameter_servers as ps_mod
from distkeras_tpu import workers as workers_mod
from distkeras_tpu.data.dataset import PartitionedDataset
from distkeras_tpu.models.wrapper import Model
from distkeras_tpu.ops import rules
from distkeras_tpu.parallel.mesh import default_mesh
from distkeras_tpu.utils.history import History, average_histories
from distkeras_tpu.utils.losses import get_loss, get_optimizer, resolve_metrics


class Trainer:
    """Base trainer (reference: trainers.py · Trainer): holds the model,
    worker-side optimizer config, loss/metrics, column conventions, and
    timing/history bookkeeping."""

    def __init__(
        self,
        model,
        params: Optional[Any] = None,
        worker_optimizer="sgd",
        learning_rate: float = 0.01,
        loss="categorical_crossentropy",
        metrics: Sequence = ("accuracy",),
        features_col: str = "features",
        label_col: str = "label",
        batch_size: int = 32,
        num_epoch: int = 1,
        seed: int = 0,
        checkpointer=None,
        metrics_path: Optional[str] = None,
        profile_dir: Optional[str] = None,
        stage_limit_bytes: int = 1 << 30,
    ):
        self.model = model
        self.params = params
        self.worker_optimizer = worker_optimizer
        self.learning_rate = learning_rate
        self.loss = loss
        self.metrics = tuple(metrics)
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = batch_size
        self.num_epoch = num_epoch
        self.seed = seed
        self.checkpointer = checkpointer
        # data bigger than this budget is streamed instead of staged
        # resident on-device (applies to workers and the SPMD epoch path)
        self.stage_limit_bytes = stage_limit_bytes
        # observability (SURVEY.md §5.1/§5.5 — absent in the reference):
        # metrics_path= writes per-step JSONL via MetricsWriter;
        # profile_dir= wraps the hot loop in a jax.profiler trace
        self.metrics_path = metrics_path
        self.profile_dir = profile_dir
        self.metrics_writer = None
        self.staleness: Optional[dict] = None
        self._trace_cm = None
        self.history: History = []
        self.executor_histories: List[History] = []
        self._t_start = None
        self._t_end = None

    # -- bookkeeping (reference: record_training_start/end etc.) -----------

    def record_training_start(self):
        self._t_start = time.time()
        from distkeras_tpu import telemetry

        telemetry.get_registry().counter(
            "train_runs_total", "trainer.train() invocations",
            labelnames=("trainer",),
        ).labels(trainer=type(self).__name__).inc()
        if self.metrics_path is not None:
            from distkeras_tpu.utils.metrics import MetricsWriter

            self.metrics_writer = MetricsWriter(self.metrics_path)
        if self.profile_dir is not None:
            from distkeras_tpu.utils.profiling import trace

            cm = trace(self.profile_dir)
            cm.__enter__()
            # assign only after a successful enter so a failed start never
            # makes record_training_end stop a trace that isn't running
            self._trace_cm = cm

    def record_training_end(self):
        self._t_end = time.time()
        from distkeras_tpu import telemetry

        telemetry.get_registry().gauge(
            "train_last_run_seconds",
            "wall-clock duration of the most recent train() call",
            labelnames=("trainer",),
        ).labels(trainer=type(self).__name__).set(
            round(self._t_end - self._t_start, 3)
        )
        if self._trace_cm is not None:
            self._trace_cm.__exit__(None, None, None)
            self._trace_cm = None
        if self.metrics_writer is not None:
            tp = self.metrics_writer.throughput()
            if tp is not None:
                self.metrics_writer.summary(
                    "throughput", samples_per_sec=round(tp, 2),
                    training_time=round(self.get_training_time(), 4),
                )
            self.metrics_writer.close()

    def train(self, dataset: PartitionedDataset, shuffle: bool = False):
        """Run training (reference: Trainer.train). The timing/trace/metrics
        lifecycle is managed here so a failing run still stops the profiler
        and closes the metrics file; subclasses implement :meth:`_train`."""
        try:
            self.record_training_start()
            return self._train(self._coerce_dataset(dataset), shuffle)
        finally:
            self.record_training_end()

    def _coerce_dataset(self, dataset):
        """Accept a ShardedDataset anywhere a PartitionedDataset works.
        Trainers with a true streaming path (DataParallelTrainer, the
        async PS family) override this to pass it through; the rest
        materialize."""
        from distkeras_tpu.data.shard_io import ShardedDataset

        if isinstance(dataset, ShardedDataset):
            return dataset.load()
        return dataset

    def get_training_time(self) -> float:
        if self._t_start is None:
            return 0.0
        return (self._t_end or time.time()) - self._t_start

    def get_averaged_history(self) -> History:
        return average_histories(self.executor_histories)

    def get_executor_history(self, index: int) -> History:
        return self.executor_histories[index]

    # -- params ------------------------------------------------------------

    def ensure_params(self, dataset: PartitionedDataset):
        """Lazy init from a data sample (Keras builds weights at compile;
        flax needs one example shape)."""
        if self.params is None:
            x = dataset.partition(0)[self.features_col][:1]
            self.params = self.model.init(
                jax.random.PRNGKey(self.seed), jnp.asarray(x)
            )
        return self.params

    def worker_kwargs(self) -> dict:
        return dict(
            optimizer=self.worker_optimizer,
            learning_rate=self.learning_rate,
            loss=self.loss,
            metrics=self.metrics,
            features_col=self.features_col,
            label_col=self.label_col,
            batch_size=self.batch_size,
            num_epoch=self.num_epoch,
            stage_limit_bytes=self.stage_limit_bytes,
        )

    def serialize(self) -> dict:
        from distkeras_tpu.models.registry import model_spec
        from distkeras_tpu.utils.serde import serialize_model

        return serialize_model(model_spec(self.model), self.params)

    def _train(self, dataset: PartitionedDataset, shuffle: bool = False) -> Model:
        raise NotImplementedError


class SingleTrainer(Trainer):
    """Non-distributed baseline (reference: trainers.py · SingleTrainer):
    coalesce to one partition, run one sequential worker."""

    def _train(self, dataset: PartitionedDataset, shuffle: bool = False) -> Model:
        if shuffle:
            dataset = dataset.shuffle(seed=self.seed)
        dataset = dataset.coalesce(1)
        self.ensure_params(dataset)
        start_epoch = 0
        restored_opt_state = None
        if self.checkpointer is not None:
            opt_template = get_optimizer(
                self.worker_optimizer, self.learning_rate
            ).init(self.params)
            step, state = self.checkpointer.restore(like={
                "params": self.params, "opt_state": opt_template,
                "extra": {"epoch": 0},
            })
            if state is not None:
                self.params = state["params"]
                restored_opt_state = state["opt_state"] or None
                start_epoch = int(state["extra"].get("epoch", step))
        worker = workers_mod.SequentialWorker(
            self.model, self.params, **self.worker_kwargs()
        )
        worker.num_epoch = max(0, self.num_epoch - start_epoch)
        worker.initial_opt_state = restored_opt_state
        worker.metrics_writer = self.metrics_writer
        if self.checkpointer is not None:
            ckpt = self.checkpointer

            def _on_epoch(epoch, params, opt_state, _base=start_epoch):
                ckpt.maybe_save(
                    _base + epoch + 1, params, opt_state,
                    extra={"epoch": _base + epoch + 1},
                    force=(_base + epoch + 1 == self.num_epoch),
                )

            worker.epoch_callback = _on_epoch
        params, history = worker.train(0, dataset.partition(0))
        if self.checkpointer is not None:
            self.checkpointer.wait()
        self.params = params
        self.executor_histories = [history]
        self.history = history
        return Model(self.model, params)


class _StackedModelTrainer(Trainer):
    """Shared machinery for EnsembleTrainer / AveragingTrainer: train k
    independent models as ONE stacked program.

    The reference ran its k sequential workers concurrently on k Spark
    executors; the serial-loop equivalent here would leave (k-1)/k of the
    machine idle. TPU-native redesign (SURVEY.md §2 "cheap on TPU: vmapped
    per-device independent models"): stack the k models' params on a
    leading axis, ``vmap`` the epoch scan over it, and shard that axis
    over a ``model`` device mesh — k models train in one XLA dispatch per
    epoch with zero cross-model synchronization.
    """

    def _stacked_train(self, dataset: PartitionedDataset, k: int,
                       param_seeds: Sequence[int], shuffle: bool,
                       common_init: Optional[Any] = None):
        from jax.sharding import Mesh, NamedSharding

        if shuffle:
            dataset = dataset.shuffle(seed=self.seed)
        dataset = dataset.repartition(k)

        optimizer = get_optimizer(self.worker_optimizer, self.learning_rate)
        loss_fn = get_loss(self.loss)
        metric_fns = resolve_metrics(self.metrics)
        apply_fn = self.model.apply

        if common_init is not None:
            plist = [common_init] * k
        else:
            plist = []
            for i in range(k):
                x = dataset.partition(i)[self.features_col][:1]
                plist.append(self.model.init(
                    jax.random.PRNGKey(param_seeds[i]), jnp.asarray(x)
                ))
        params = jax.tree.map(lambda *xs: jnp.stack(xs), *plist)
        opt_state = jax.vmap(optimizer.init)(params)

        xs, ys = [], []
        for i in range(k):
            xb, yb = workers_mod.batch_partition(
                dataset.partition(i), self.features_col, self.label_col,
                self.batch_size,
            )
            xs.append(xb)
            ys.append(yb)
        # models advance in lockstep inside one program: truncate to the
        # shortest partition's batch count (repartition splits near-equally,
        # so at most one trailing batch per model is dropped — loudly)
        nb = min(len(x) for x in xs)
        dropped = sum(len(x) - nb for x in xs)
        if dropped:
            import warnings

            warnings.warn(
                f"ensemble lock-step truncated {dropped} trailing "
                f"batch(es) across {k} models (shortest partition has "
                f"{nb}); pick batch_size/partitions that divide evenly "
                "to keep them",
                RuntimeWarning,
            )
        xb = np.stack([x[:nb] for x in xs])
        yb = np.stack([y[:nb] for y in ys])

        # one model's epoch is exactly a communication window of its whole
        # batch list — reuse the canonical step math so ensemble/averaging
        # can never diverge from the worker path
        window = workers_mod.make_window_step(
            apply_fn, loss_fn, optimizer, metric_fns
        )
        vepoch = jax.jit(jax.vmap(window))

        # shard the model axis over as many devices as divide k
        ndev = len(jax.devices())
        m = max(d for d in range(1, min(k, ndev) + 1) if k % d == 0)
        sh = None
        if m > 1:
            mesh = Mesh(np.asarray(jax.devices()[:m]), ("model",))
            sh = NamedSharding(mesh, P("model"))
            params = jax.device_put(params, sh)
            opt_state = jax.device_put(opt_state, sh)

        def put(x):
            return jax.device_put(x, sh) if sh is not None else jnp.asarray(x)

        # stage the stacked epoch tensors resident once when they fit the
        # budget; else re-upload per epoch (bounded-memory fallback)
        staged = xb.nbytes + yb.nbytes <= self.stage_limit_bytes
        if staged:
            xb, yb = put(xb), put(yb)

        histories: List[History] = [[] for _ in range(k)]
        for _epoch in range(self.num_epoch):
            xe, ye = (xb, yb) if staged else (put(xb), put(yb))
            params, opt_state, ms = vepoch(params, opt_state, xe, ye)
            ms = {key: np.asarray(v) for key, v in ms.items()}
            for i in range(k):
                rows = [
                    {key: float(v[i, t]) for key, v in ms.items()}
                    for t in range(nb)
                ]
                if self.metrics_writer is not None:
                    base = len(histories[i])
                    for t, r in enumerate(rows):
                        self.metrics_writer.log(
                            step=base + t + 1, samples=self.batch_size,
                            worker=i, **r,
                        )
                histories[i].extend(rows)
        self.executor_histories = histories
        return params

    @staticmethod
    def _unstack(params, k: int):
        return [
            jax.tree.map(lambda x, i=i: np.asarray(x[i]), params)
            for i in range(k)
        ]


class EnsembleTrainer(_StackedModelTrainer):
    """Train k independent models on k partitions (reference: trainers.py ·
    EnsembleTrainer). Returns a list of Models; each starts from a
    differently-seeded init."""

    def __init__(self, *args, num_models: int = 2, **kwargs):
        super().__init__(*args, **kwargs)
        self.num_models = num_models

    def _train(self, dataset: PartitionedDataset, shuffle: bool = False) -> List[Model]:
        k = self.num_models
        stacked = self._stacked_train(
            dataset, k, [self.seed + i for i in range(k)], shuffle
        )
        return [Model(self.model, p) for p in self._unstack(stacked, k)]


class AveragingTrainer(_StackedModelTrainer):
    """One-shot parameter averaging (reference: trainers.py ·
    AveragingTrainer): train per-partition from a common init, average."""

    def __init__(self, *args, num_workers: int = 2, **kwargs):
        super().__init__(*args, **kwargs)
        self.num_workers = num_workers

    def _train(self, dataset: PartitionedDataset, shuffle: bool = False) -> Model:
        k = self.num_workers
        stacked = self._stacked_train(
            dataset, k, [self.seed] * k, shuffle, common_init=self.params
        )
        # one-shot average over the model axis
        self.params = jax.tree.map(lambda x: np.asarray(x).mean(axis=0), stacked)
        return Model(self.model, self.params)


class DistributedTrainer(Trainer):
    """Parameter-server orchestration base (reference: trainers.py ·
    DistributedTrainer): start PS → repartition → one worker per partition →
    barrier → stop PS → center is the trained model.

    Workers are host threads; each drives jit-compiled steps on the device.
    On one chip the threads interleave on the same device (true concurrency
    of *schedule*, shared compute), preserving the algorithms' staleness
    semantics exactly; on multi-host deployments each host runs its own
    workers against a transported PS (distkeras_tpu/networking.py).
    """

    WORKER_CLS = None  # set by subclasses

    def __init__(self, *args, num_workers: int = 2,
                 communication_window: int = 5,
                 remote_ps: Optional[tuple] = None,
                 devices: Optional[Sequence] = None,
                 max_retries: int = 0, **kwargs):
        super().__init__(*args, **kwargs)
        self.num_workers = num_workers
        self.communication_window = communication_window
        # Failure recovery (SURVEY.md §5.3 — the reference had NONE: a dead
        # executor either deadlocked the run or was silently re-run by Spark,
        # double-counting its updates). Here a crashed worker is restarted
        # up to max_retries times from the CURRENT center (its first act is
        # a fresh pull), so no update is ever double-counted and the center
        # never loses committed progress.
        self.max_retries = max_retries
        self.worker_restarts = 0
        # (host, port) of a ParameterServerService on another host: this
        # process then contributes workers over DCN instead of owning the
        # center (multi-host async topology; see networking.py)
        self.remote_ps = remote_ps
        # Devices the worker step loops are pinned to, round-robin. Default:
        # all local devices — N async workers on an N-chip host drive N
        # chips concurrently (the reference's one-worker-per-executor
        # topology, with chips playing the executors).
        self.devices = devices
        self.parameter_server: Optional[ps_mod.ParameterServer] = None
        self.workers: List[workers_mod.WindowedWorker] = []

    # reference: allocate_parameter_server / allocate_worker
    def allocate_parameter_server(self) -> ps_mod.ParameterServer:
        raise NotImplementedError

    def allocate_worker(self, index: int) -> workers_mod.WindowedWorker:
        kwargs = self.worker_kwargs()
        kwargs.update(communication_window=self.communication_window)
        kwargs.update(self.extra_worker_kwargs())
        devices = self.devices if self.devices is not None else jax.local_devices()
        kwargs.update(device=devices[index % len(devices)])
        return self.WORKER_CLS(self.model, self.params, **kwargs)

    def extra_worker_kwargs(self) -> dict:
        return {}

    @property
    def parallelism_factor(self) -> int:
        return 1

    def _coerce_dataset(self, dataset):
        return dataset  # streaming path below handles ShardedDataset

    def _train(self, dataset, shuffle: bool = False) -> Model:
        from distkeras_tpu import runtime
        from distkeras_tpu.data.shard_io import ShardedDataset

        self.worker_restarts = 0  # per-run counter (trainers are reusable)
        n_parts = self.num_workers * self.parallelism_factor
        sharded = isinstance(dataset, ShardedDataset)
        if sharded:
            # disk-resident path: each worker reads its own shard subset
            # inside its thread (native pread, GIL released — reads run in
            # parallel), two-level shuffle (shard assignment + in-worker
            # rows); a restarted worker re-reads from disk, so memory stays
            # bounded at one worker partition per live worker
            if dataset.num_shards < n_parts:
                raise ValueError(
                    f"{dataset.num_shards} shards cannot feed {n_parts} "
                    "workers — re-write with more shards (write_shards "
                    "rows_per_shard=...)"
                )
            shard_order = np.arange(dataset.num_shards)
            if shuffle:
                shard_order = np.random.default_rng(self.seed).permutation(
                    dataset.num_shards
                )

            def get_partition(i):
                shards = [
                    dataset.read_shard(int(s))
                    for s in shard_order[i::n_parts]
                ]
                part = {
                    c: np.concatenate([s[c] for s in shards])
                    for c in dataset.columns
                }
                if shuffle:
                    perm = np.random.default_rng(
                        self.seed + 1 + i
                    ).permutation(len(next(iter(part.values()))))
                    part = {c: v[perm] for c, v in part.items()}
                return part

            if self.params is None:
                self.ensure_params(
                    PartitionedDataset([dataset.read_shard(0)])
                )
        else:
            if shuffle:
                dataset = dataset.shuffle(seed=self.seed)
            dataset = dataset.repartition(n_parts)
            self.ensure_params(dataset)
            get_partition = dataset.partition

        # Topology: single-process (own the center in-process), explicit
        # remote_ps client, or auto-wired multi-host via the runtime
        # context — coordinator owns the center and serves it over DCN,
        # everyone else proxies (SURVEY.md §5.8 async-over-DCN).
        ctx = runtime.current()
        multihost = ctx is not None and ctx.num_processes > 1
        is_owner = self.remote_ps is None and (not multihost or ctx.is_coordinator)
        worker_offset = ctx.process_id * n_parts if multihost else 0
        if self.checkpointer is not None and not is_owner:
            raise ValueError(
                "checkpointer must live with the process that owns the "
                "center (the coordinator / ParameterServerService host), "
                "not a remote client — pass it there instead"
            )

        restored_worker_opt = None
        restored_step = 0
        if self.checkpointer is not None and self.checkpointer.latest_step is not None:
            # Checkpoints carry the center plus each worker's optimizer
            # state (reference parity: Keras set_weights kept the optimizer
            # state across weight swaps, so resume must too). The typed
            # restore assumes the worker count matches; when it doesn't
            # (topology change, or a pre-r2 params-only snapshot) the
            # structure mismatch raises and we fall back to center-only.
            opt_template = get_optimizer(
                self.worker_optimizer, self.learning_rate
            ).init(self.params)
            try:
                restored_step, state = self.checkpointer.restore(like={
                    "params": self.params,
                    "opt_state": {"workers": [opt_template] * n_parts},
                    "extra": {"n_workers": 0},
                })
                self.params = state["params"]
                restored_worker_opt = state["opt_state"]["workers"]
            except Exception:
                restored_step, raw = self.checkpointer.restore()
                n_saved = int(raw.get("extra", {}).get("n_workers", -1))
                if n_saved == n_parts:
                    # the snapshot matches this topology, so the typed
                    # restore should have worked — a swallowed failure here
                    # would silently drop worker momentum; stay loud
                    raise
                self.params = jax.tree.map(np.asarray, raw["params"])
        service = None
        if self.remote_ps is not None:
            from distkeras_tpu.networking import RemoteParameterServer

            ps = RemoteParameterServer(*self.remote_ps)
        elif multihost and not ctx.is_coordinator:
            from distkeras_tpu.networking import RemoteParameterServer

            ps = RemoteParameterServer(*ctx.ps_hostport, secret=ctx.secret)
        else:
            if multihost:
                # PS math that divides by the worker population (ADAG
                # normalization, the EASGD round barrier) must see the
                # GLOBAL count, not this process's share. Set only for this
                # allocation — a stale global count would deadlock a later
                # single-host run of the same trainer object.
                self._ps_num_workers = self.num_workers * ctx.num_processes
            try:
                ps = self.allocate_parameter_server()
            finally:
                self.__dict__.pop("_ps_num_workers", None)
            ps.checkpointer = self.checkpointer
            # continue save steps past the restored run's so a resumed
            # run's snapshots never collide with (and get skipped against)
            # the prior run's steps
            ps.step_offset = restored_step
            if multihost:
                from distkeras_tpu.networking import ParameterServerService

                host, port = ctx.ps_hostport
                bind = "0.0.0.0" if host not in ("127.0.0.1", "localhost") else host
                service = ParameterServerService(
                    ps, host=bind, port=port, secret=ctx.secret
                )
                service.start()
        self.parameter_server = ps
        ps.start()

        results: List[Optional[History]] = [None] * n_parts
        errors: List[BaseException] = []

        workers = [self.allocate_worker(i) for i in range(n_parts)]
        self.workers = workers
        workers_mod.share_compiled(workers)
        for w in workers:
            w.metrics_writer = self.metrics_writer
        if restored_worker_opt is not None:
            for w, s in zip(workers, restored_worker_opt):
                w.initial_opt_state = s
        if self.checkpointer is not None and is_owner:
            fallback_opt = workers[0].optimizer.init(self.params)

            def _worker_states():
                states = []
                for w in workers:
                    s = getattr(w, "opt_state", None)
                    states.append(jax.tree.map(
                        np.asarray, s if s is not None else fallback_opt
                    ))
                return {"workers": states}, {"n_workers": n_parts}

            ps.extra_state_fn = _worker_states

        restart_lock = threading.Lock()

        def run(i: int):
            gi = worker_offset + i  # globally-unique worker id
            attempts = 0
            try:
                while True:
                    try:
                        _, history = workers[i].train(
                            gi, get_partition(i), ps
                        )
                        results[i] = history
                        return
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except BaseException as e:
                        if attempts >= self.max_retries:
                            # out of budget: surface to the driver
                            errors.append(e)
                            return
                        attempts += 1
                        # Restart: fresh worker object (clean opt_state),
                        # same device slot and global id, sharing the
                        # already-compiled step. Its on_start pulls the
                        # current center, so committed progress survives
                        # and nothing is replayed twice. A sync (EASGD)
                        # restart re-enters the barrier under the same id;
                        # finished peers leave and shrink it, so the
                        # restarted worker's extra rounds cannot deadlock.
                        with restart_lock:
                            self.worker_restarts += 1
                        replacement = self.allocate_worker(i)
                        replacement.metrics_writer = self.metrics_writer
                        old = workers[i]
                        if getattr(old, "step", None) is not None:
                            replacement.set_compiled(old.step, old.window_step)
                        workers[i] = replacement
            finally:
                # shrink any synchronous barrier so survivors never deadlock
                ps.leave(gi)

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(n_parts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if is_owner:
            if service is not None and not errors:
                # other processes are still training against our center —
                # wait until each has read its final center before teardown
                done = service.wait_for_remote_done(ctx.num_processes - 1)
                if not done:
                    import warnings

                    warnings.warn(
                        "timed out waiting for remote processes to read the "
                        "final center — a peer likely died; the returned "
                        "model reflects all commits received so far",
                        RuntimeWarning,
                    )
            final = ps.get_model()
        elif errors:
            # a local worker failed: skip the final pull (it could hang on
            # a dead coordinator) but still send the done sentinel — it
            # only means "no further calls from this process", and without
            # it the owner would block out its full teardown timeout. The
            # failure itself surfaces via this process's nonzero exit
            # (Job.run raises) and the raise below.
            ps.leave(-1 - worker_offset)
            final = None
        else:
            # read the final center, then tell the owner this process is
            # completely done (negative-id leave = process-done sentinel)
            final = ps.pull()
            ps.leave(-1 - worker_offset)
        ps.stop()
        if service is not None:
            service.stop()
        if self.checkpointer is not None and is_owner:
            opt_state, extra = ps.extra_state_fn()
            self.checkpointer.maybe_save(
                ps.step_offset + ps.num_updates, ps.get_model(),
                opt_state=opt_state, extra=extra, force=True,
            )
            self.checkpointer.wait()
            # release the closure over device-resident worker state so the
            # trainer object doesn't pin N workers' opt_state in HBM
            ps.extra_state_fn = None
        # staleness observability (SURVEY.md §5.5): histogram of commit
        # staleness as recorded by the PS (DynSGD populates this)
        from distkeras_tpu.utils.metrics import staleness_histogram

        log = getattr(ps, "staleness_log", None) or []
        self.staleness = staleness_histogram(log)
        if self.metrics_writer is not None and log:
            self.metrics_writer.summary(
                "staleness", histogram=self.staleness,
                num_updates=ps.num_updates,
            )
        if self.metrics_writer is not None and self.worker_restarts:
            self.metrics_writer.summary(
                "failures", worker_restarts=self.worker_restarts
            )
        if errors:
            raise errors[0]
        self.executor_histories = [h for h in results if h is not None]
        self.params = jax.tree.map(jnp.asarray, final)
        return Model(self.model, self.params)


class AsynchronousDistributedTrainer(DistributedTrainer):
    """Async base (reference: trainers.py · AsynchronousDistributedTrainer):
    adds the partition-oversubscription knob."""

    def __init__(self, *args, parallelism_factor: int = 1, **kwargs):
        super().__init__(*args, **kwargs)
        self._parallelism_factor = parallelism_factor

    @property
    def parallelism_factor(self) -> int:
        return self._parallelism_factor


class _DeltaFamilySpmdMixin:
    """``spmd=True`` engine for the windowed delta-commit algorithms
    (VERDICT r3 next #6): W local steps per device, then one lock-step
    commit of every worker's delta inside the jitted window —
    DOWNPOUR sums deltas (:func:`rules.allreduce_sum_delta`, the
    DeltaParameterServer semantics), ADAG means them
    (:func:`rules.allreduce_mean_delta`) — and every worker re-pulls the
    new center, exactly the reference's push-then-pull. Equivalent to the
    host PS engine under a deterministic pull-all/commit-all schedule
    (tested against the PS classes driven directly). The true-async
    staleness semantics remain the default engine's job; spmd trades them
    for single-dispatch windows over ICI."""

    SPMD_ENGINE = ""  # subclass sets, e.g. 'downpour-spmd'

    def __init__(self, *args, spmd: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.spmd = spmd

    def _spmd_round(self, worker, center):
        delta = rules.tree_sub(worker, center)
        center = rules.tree_add(center, self._spmd_reduce(delta))
        # every worker pulls the committed center (reference: workers.py
        # push-then-pull at each communication_window boundary); the pull
        # is pcast device-varying so the engine's dp out_spec accepts it
        pulled = jax.tree.map(
            lambda c: jax.lax.pcast(c, ("dp",), to="varying"), center
        )
        return pulled, center

    def _train(self, dataset, shuffle: bool = False) -> Model:
        if getattr(self, "spmd", False):
            return _train_lockstep_spmd(
                self, dataset, shuffle, engine=self.SPMD_ENGINE,
                round_fn=self._spmd_round,
            )
        return super()._train(dataset, shuffle)


class DOWNPOUR(_DeltaFamilySpmdMixin, AsynchronousDistributedTrainer):
    """Dean et al. 2012 (reference: trainers.py · DOWNPOUR)."""

    WORKER_CLS = workers_mod.DOWNPOURWorker
    SPMD_ENGINE = "downpour-spmd"

    def _spmd_reduce(self, delta):
        return rules.allreduce_sum_delta(delta, "dp")

    def allocate_parameter_server(self):
        return ps_mod.DeltaParameterServer(self.params)


class ADAG(_DeltaFamilySpmdMixin, AsynchronousDistributedTrainer):
    """Asynchronous distributed adaptive gradients — the reference's
    recommended default (reference: trainers.py · ADAG)."""

    WORKER_CLS = workers_mod.ADAGWorker
    SPMD_ENGINE = "adag-spmd"

    def _spmd_reduce(self, delta):
        return rules.allreduce_mean_delta(delta, "dp")

    def allocate_parameter_server(self):
        # _ps_num_workers is the global population under multi-host runs
        return ps_mod.ADAGParameterServer(
            self.params, getattr(self, "_ps_num_workers", self.num_workers)
        )


class DynSGD(AsynchronousDistributedTrainer):
    """Staleness-damped async SGD (reference: trainers.py · DynSGD).

    ``spmd=True`` (VERDICT r4 next #6b) runs the lock-step mesh engine
    with per-device clocks: commits land in device order inside the
    round, worker ``i`` damped by ``1/(1+i)`` —
    :func:`distkeras_tpu.ops.rules.allreduce_dynsgd_round` has the
    staleness derivation. True async staleness stays with the default
    host/DCN engine."""

    WORKER_CLS = workers_mod.DynSGDWorker
    SPMD_ENGINE = "dynsgd-spmd"

    def __init__(self, *args, spmd: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.spmd = spmd

    def allocate_parameter_server(self):
        return ps_mod.DynSGDParameterServer(self.params)

    def _train(self, dataset, shuffle: bool = False) -> Model:
        if self.spmd:
            return _train_lockstep_spmd(
                self, dataset, shuffle, engine=self.SPMD_ENGINE,
                round_fn=lambda w, c: rules.allreduce_dynsgd_round(
                    w, c, "dp"
                ),
            )
        return super()._train(dataset, shuffle)


class AEASGD(AsynchronousDistributedTrainer):
    """Async elastic averaging (reference: trainers.py · AEASGD).

    ``spmd=True`` (VERDICT r4 next #6b) runs the lock-step mesh engine:
    each round is the elastic exchange
    (:func:`distkeras_tpu.ops.rules.allreduce_easgd_round`) — in
    lock-step the async elastic commit (worker pushes
    ``alpha*(w - c)``, applies the opposite force locally) lands
    identically to the synchronous round, so the engines share the
    rule; what AEASGD keeps over EASGD here is its trainer vocabulary
    (parallelism_factor, worker knobs) and its own checkpoint stamp."""

    WORKER_CLS = workers_mod.AEASGDWorker
    SPMD_ENGINE = "aeasgd-spmd"

    def __init__(self, *args, rho: float = 5.0, elastic_lr: float = 0.01,
                 spmd: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.rho = rho
        self.elastic_lr = elastic_lr
        self.spmd = spmd

    def extra_worker_kwargs(self):
        return dict(rho=self.rho, elastic_lr=self.elastic_lr)

    def allocate_parameter_server(self):
        return ps_mod.DeltaParameterServer(self.params)

    def _train(self, dataset, shuffle: bool = False) -> Model:
        if self.spmd:
            alpha = self.elastic_lr * self.rho
            return _train_lockstep_spmd(
                self, dataset, shuffle, engine=self.SPMD_ENGINE,
                round_fn=lambda w, c: rules.allreduce_easgd_round(
                    w, c, alpha, "dp"
                ),
            )
        return super()._train(dataset, shuffle)


class EAMSGD(AEASGD):
    """AEASGD + momentum (reference: trainers.py · EAMSGD). The worker-side
    momentum comes from the nesterov optax optimizer. ``spmd=True`` is
    inherited from AEASGD — the lock-step engine runs whatever
    ``worker_optimizer`` the trainer carries, so the Nesterov momentum
    built below rides along unchanged."""

    WORKER_CLS = workers_mod.EAMSGDWorker
    SPMD_ENGINE = "eamsgd-spmd"

    def __init__(self, *args, momentum: float = 0.9, **kwargs):
        if kwargs.get("worker_optimizer", "sgd") != "sgd":
            raise ValueError(
                "EAMSGD defines its own worker optimizer (Nesterov SGD with "
                "the `momentum` knob); a custom worker_optimizer would be "
                "silently ignored — use AEASGD if you need one"
            )
        super().__init__(*args, **kwargs)
        self.momentum = momentum
        # Build the Nesterov-momentum optimizer concretely so the momentum
        # knob is actually honored (a bare 'nesterov' string would fall back
        # to the registry default of 0.9).
        self.worker_optimizer = optax.sgd(
            self.learning_rate, momentum=self.momentum, nesterov=True
        )


class SynchronousDistributedTrainer(DistributedTrainer):
    """Sync base (reference: trainers.py · SynchronousDistributedTrainer)."""


class EASGD(SynchronousDistributedTrainer):
    """Synchronous elastic averaging (reference: trainers.py · EASGD):
    every round is a full barrier across workers.

    Two execution engines for the same math (SURVEY.md §2: "sync maps
    naturally to psum"):

    - default: worker threads + the host barrier PS
      (:class:`~distkeras_tpu.parameter_servers.EASGDParameterServer`) —
      tolerates unequal partitions and worker crashes (barrier shrink);
    - ``spmd=True``: every worker is a mesh device in lock-step — worker
      params/opt-state live sharded over ``dp``, the center is replicated,
      and each round is one
      :func:`distkeras_tpu.ops.rules.allreduce_easgd_round` inside the
      jitted ``shard_map`` window, so a whole window (W local steps +
      elastic round) is a single device dispatch with the round riding
      ICI. Equivalent trajectories under identical data order (tested).
      Single-process (one mesh per host); checkpoints carry the stacked
      worker params + moments, so resume is exact. Multi-host elastic
      averaging uses the host-barrier engine over the DCN service.
    """

    WORKER_CLS = workers_mod.EASGDWorker

    def __init__(self, *args, rho: float = 5.0, elastic_lr: float = 0.01,
                 spmd: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.rho = rho
        self.elastic_lr = elastic_lr
        self.spmd = spmd

    def extra_worker_kwargs(self):
        return dict(rho=self.rho, elastic_lr=self.elastic_lr)

    def allocate_parameter_server(self):
        return ps_mod.EASGDParameterServer(
            self.params, getattr(self, "_ps_num_workers", self.num_workers),
            rho=self.rho, elastic_lr=self.elastic_lr,
        )

    def _train(self, dataset, shuffle: bool = False) -> Model:
        if self.spmd:
            alpha = self.elastic_lr * self.rho
            return _train_lockstep_spmd(
                self, dataset, shuffle, engine="easgd-spmd",
                round_fn=lambda w, c: rules.allreduce_easgd_round(
                    w, c, alpha, "dp"
                ),
            )
        return super()._train(dataset, shuffle)


# integer stamps for the lock-step checkpoint header (orbax trees don't
# carry strings); 0 = unstamped legacy checkpoint, accepted with a warning
_SPMD_ENGINE_IDS = {"easgd-spmd": 1, "downpour-spmd": 2, "adag-spmd": 3,
                    "aeasgd-spmd": 4, "eamsgd-spmd": 5, "dynsgd-spmd": 6}


def _group_checksum_mismatch(gids, sums):
    """First replica group whose processes disagree on the feed checksum,
    as ``(group, {checksum: [process, ...]})`` — ``None`` when every group
    is internally consistent. Split out from the allgather so the
    comparison is unit-testable in a single process (ADVICE r4 #1)."""
    by: dict = {}
    for pi, (g, s) in enumerate(zip(gids, sums)):
        by.setdefault(int(g), {}).setdefault(int(s), []).append(pi)
    for g in sorted(by):
        if len(by[g]) > 1:
            return g, by[g]
    return None


def _verify_replica_feed(tokens, gid):
    """One-time cross-process check that replica-group processes were
    handed identical in-memory rows (ADVICE r4 #1): processes whose
    devices share batch coordinates assemble the SAME global rows
    per-shard, so different arrays would train on inconsistent data with
    no error anywhere. The disk-streaming path is consistent by
    construction; this guards the in-memory path it replaced a hard
    refusal for."""
    if jax.process_count() == 1:
        return
    import zlib

    from jax.experimental import multihost_utils

    # order-SENSITIVE digest: a plain element sum is permutation-
    # invariant and would miss the most likely divergence — the same
    # rows shuffled with different seeds per process
    csum = zlib.crc32(np.ascontiguousarray(tokens).tobytes())
    gathered = np.asarray(
        multihost_utils.process_allgather(np.asarray([gid, csum], np.int64))
    )
    bad = _group_checksum_mismatch(gathered[:, 0], gathered[:, 1])
    if bad is not None:
        g, variants = bad
        raise RuntimeError(
            f"replica group {g} processes disagree on the in-memory "
            f"dataset feed (checksum -> processes: {variants}); replica "
            "processes of an sp/tp group must pass identical rows — use "
            "a ShardedDataset (consistent by construction) or fix the "
            "feed"
        )


def _train_lockstep_spmd(self, dataset: PartitionedDataset, shuffle: bool,
                         engine: str, round_fn) -> Model:
    """Shared lock-step SPMD engine for the windowed PS algorithms
    (EASGD/DOWNPOUR/ADAG with ``spmd=True``): every worker is a mesh
    device, worker params/opt-state live sharded over ``dp``, the center
    is replicated, and a whole window — W local steps plus the algorithm's
    commit ``round_fn(stacked_workers, center) -> (workers, center)`` —
    is ONE jitted ``shard_map`` dispatch with the exchange riding ICI.

    ``self`` is the trainer (kept as the parameter name so the engine
    reads like the method it was extracted from)."""
    import warnings

    from distkeras_tpu.parallel.mesh import default_mesh
    from jax.sharding import NamedSharding

    if jax.process_count() > 1:
        raise NotImplementedError(
            f"{engine} is single-process (one mesh per host); multi-host "
            "runs use the host/DCN PS service engine (spmd=False)"
        )
    if shuffle:
        dataset = dataset.shuffle(seed=self.seed)
    self.ensure_params(dataset)
    mesh = default_mesh(self.num_workers)
    n_dev = mesh.devices.size

    optimizer = get_optimizer(self.worker_optimizer, self.learning_rate)
    loss_fn = get_loss(self.loss)
    metric_fns = resolve_metrics(self.metrics)
    apply_fn = self.model.apply

    # worker i's partition becomes device i's batch stream: batch each
    # partition, pad shorter workers to the longest with masked no-op
    # batches (VERDICT r4 weak #2 — the r4 engine truncated to the
    # shortest and silently dropped data; now every row is processed
    # exactly once, matching the host engine), and interleave so global
    # batch g carries worker i's rows at slice i
    parts = dataset.repartition(n_dev)
    per_worker = [
        workers_mod.batch_partition(
            parts.partition(i), self.features_col, self.label_col,
            self.batch_size,
        )
        for i in range(n_dev)
    ]
    lens = [len(xw) for xw, _ in per_worker]
    n_b = max(lens)
    if len(set(lens)) > 1:
        warnings.warn(
            f"{engine}: partitions are unequal ({min(lens)}–{n_b} "
            f"batches across {n_dev} workers); exhausted workers idle "
            "through masked no-op steps but still join every commit — "
            "no rows are dropped",
            RuntimeWarning,
        )

    def _pad_batches(a):
        if len(a) == n_b:
            return a
        pad = np.zeros((n_b - len(a),) + a.shape[1:], a.dtype)
        return np.concatenate([a, pad], axis=0)

    # [n_b, feed_dev*B, ...]: concat worker slices per global batch
    xb = np.concatenate(
        [_pad_batches(xw) for xw, _ in per_worker], axis=1
    )
    yb = np.concatenate(
        [_pad_batches(yw) for _, yw in per_worker], axis=1
    )
    # valid[b, w]: is worker w's b-th batch real data? (f32 so it feeds
    # through the same device_put path as the batches)
    valid = np.stack(
        [(np.arange(n_b) < n).astype(np.float32) for n in lens], axis=1
    )

    W = self.communication_window

    def device_window(worker, opt_state, center, xs, ys, vs):
        # worker/opt_state arrive dp-sharded with a leading axis of 1
        # (this device's slice); squeeze it for the step math. vs is this
        # device's [W] validity column (0.0 = padded no-op batch).
        worker = jax.tree.map(lambda x: x[0], worker)
        opt_state = jax.tree.map(lambda x: x[0], opt_state)
        vs = vs[:, 0]

        def one(carry, batch):
            p, s = carry
            x, y, v = batch

            def objective(pp):
                logits = apply_fn(pp, x)
                return loss_fn(logits, y), logits

            (loss, logits), grads = jax.value_and_grad(
                objective, has_aux=True)(p)
            updates, s_new = optimizer.update(grads, s, p)
            p_new = optax.apply_updates(p, updates)
            # masked no-op: a padded batch leaves params, moments AND
            # step counters untouched, as if the step never ran
            p = jax.tree.map(lambda n, o: jnp.where(v > 0, n, o), p_new, p)
            s = jax.tree.map(lambda n, o: jnp.where(v > 0, n, o), s_new, s)
            out = {"loss": loss}
            for name, fn in metric_fns:
                out[name] = fn(logits, y)
            return (p, s), out

        (worker, opt_state), ms = jax.lax.scan(
            one, (worker, opt_state), (xs, ys, vs)
        )
        worker, center = round_fn(worker, center)
        # re-lead every per-device output so the dp out_spec stacks
        # them back to [n_dev, ...] ([n_dev, W] for the metrics)
        lead = jax.tree.map(lambda x: x[None], worker)
        lead_s = jax.tree.map(lambda x: x[None], opt_state)
        ms = jax.tree.map(lambda x: x[None], ms)
        return lead, lead_s, center, ms

    # donated worker/opt/center: the loop below rebinds all three every
    # window. worker/opt_state start as numpy broadcasts (safe to donate
    # their uploads), but center starts as self.params — possibly live
    # jax Arrays the caller still owns — so it gets a device-local copy
    # below before the first donated call.
    window_step = jax.jit(
        shard_map(
            device_window,
            mesh=mesh,
            in_specs=(P("dp"), P("dp"), P(), P(None, "dp"), P(None, "dp"),
                      P(None, "dp")),
            out_specs=(P("dp"), P("dp"), P(), P("dp")),
        ),
        donate_argnums=(0, 1, 2),
    )

    center = self.params
    # every worker starts from the center (reference: workers pull the
    # initial center before their first round)
    worker = jax.tree.map(
        lambda x: np.broadcast_to(np.asarray(x), (n_dev,) + x.shape),
        center,
    )
    opt0 = optimizer.init(self.params)
    opt_state = jax.tree.map(
        lambda x: np.broadcast_to(np.asarray(x), (n_dev,) + np.shape(x)),
        opt0,
    )

    # checkpoints carry center AND the stacked per-worker state (params
    # + optimizer moments) so a resume is EXACT: restoring only the
    # center would pair each worker's surviving momentum with params it
    # was never computed for. The engine/worker-count stamp makes a
    # cross-engine or resized resume fail loudly (ADVICE r3 #4): the
    # host-barrier engines write a different opt_state layout, and a
    # different worker count changes the stacked leading axis.
    start_epoch = 0
    if self.checkpointer is not None:
        like = {
            "params": center,
            "opt_state": {
                "worker": jax.tree.map(np.asarray, worker),
                "opt": jax.tree.map(np.asarray, opt_state),
            },
            "extra": {"epoch": 0, "engine_id": 0, "workers": 0},
        }
        try:
            ck_step, state = self.checkpointer.restore(like=like)
        except ValueError:
            # pre-stamp checkpoint: its extra tree lacks engine_id/workers
            # and orbax refuses the structure mismatch — retry with the
            # legacy template and accept it unstamped
            like["extra"] = {"epoch": 0}
            ck_step, state = self.checkpointer.restore(like=like)
        if state is not None:
            saved_id = int(state["extra"].get("engine_id", 0))
            saved_workers = int(state["extra"].get("workers", 0))
            if not saved_id:
                # pre-r4 checkpoints carry no stamp, so a cross-engine
                # resume (e.g. EASGD-spmd state into DOWNPOUR-spmd) cannot
                # be detected — say which engine will consume it so the
                # operator can verify (ADVICE r4 #3)
                warnings.warn(
                    "restoring an unstamped (pre-engine-stamp) lockstep "
                    f"checkpoint into the '{engine}' spmd engine; if it "
                    "was written by a different algorithm the layouts "
                    "differ silently — verify the source trainer matches"
                )
            if saved_id and saved_id != _SPMD_ENGINE_IDS[engine]:
                names = {v: k for k, v in _SPMD_ENGINE_IDS.items()}
                raise ValueError(
                    "checkpoint was written by engine "
                    f"'{names.get(saved_id, saved_id)}' but this trainer "
                    f"runs '{engine}' — their state layouts are "
                    "incompatible; resume with the matching trainer/spmd "
                    "flag or point at a fresh directory"
                )
            if saved_workers and saved_workers != n_dev:
                raise ValueError(
                    f"checkpoint carries {saved_workers} stacked workers "
                    f"but this run has {n_dev} — per-worker state cannot "
                    "be re-sliced; resume with num_workers="
                    f"{saved_workers} or start fresh"
                )
            center = state["params"]
            start_epoch = int(state["extra"].get("epoch", ck_step))
            if state["opt_state"]:
                worker = state["opt_state"]["worker"]
                opt_state = state["opt_state"]["opt"]

    # donation safety: center may be live caller-owned jax Arrays (see
    # the window_step note); give the loop its own device copy
    center = jax.tree.map(jnp.copy, center)

    batch_sharding = NamedSharding(mesh, P(None, "dp"))

    def put_feed(arr):
        return jax.device_put(arr, batch_sharding)

    # windows: full W-batch groups + one tail group (its own compile)
    groups = [(s, min(s + W, n_b)) for s in range(0, n_b, W)]
    staged = xb.nbytes + yb.nbytes <= self.stage_limit_bytes
    if staged:
        xb_d, yb_d, vb_d = put_feed(xb), put_feed(yb), put_feed(valid)

    history_per_worker: List[History] = [[] for _ in range(n_dev)]
    for epoch in range(start_epoch, self.num_epoch):
        epoch_ms = []
        for s, e in groups:
            if staged:
                xw, yw, vw = xb_d[s:e], yb_d[s:e], vb_d[s:e]
            else:
                xw, yw, vw = (put_feed(xb[s:e]), put_feed(yb[s:e]),
                              put_feed(valid[s:e]))
            worker, opt_state, center, ms = window_step(
                worker, opt_state, center, xw, yw, vw
            )
            epoch_ms.append(ms)
        for (s, e), ms in zip(groups, epoch_ms):
            ms = {k: np.asarray(v) for k, v in ms.items()}
            steps = next(iter(ms.values())).shape[1]
            for w in range(n_dev):
                # only this worker's REAL steps reach its history: padded
                # no-op batches (global index >= its batch count) produced
                # metrics-on-zeros that never happened
                rows = [
                    {k: float(v[w, t]) for k, v in ms.items()}
                    for t in range(steps)
                    if s + t < lens[w]
                ]
                history_per_worker[w].extend(rows)
                if self.metrics_writer is not None:
                    base = len(history_per_worker[w]) - len(rows)
                    for t, r in enumerate(rows):
                        self.metrics_writer.log(
                            step=base + t + 1, worker=w,
                            samples=self.batch_size, **r,
                        )
        if self.checkpointer is not None:
            self.checkpointer.maybe_save(
                epoch + 1, jax.tree.map(np.asarray, center),
                {
                    "worker": jax.tree.map(np.asarray, worker),
                    "opt": jax.tree.map(np.asarray, opt_state),
                },
                extra={"epoch": epoch + 1,
                       "engine_id": _SPMD_ENGINE_IDS[engine],
                       "workers": n_dev},
                force=(epoch + 1 == self.num_epoch),
            )
    self.params = jax.tree.map(np.asarray, center)
    self.executor_histories = history_per_worker
    self.history = history_per_worker[0]
    return Model(self.model, self.params)


class DataParallelTrainer(Trainer):
    """TPU-native synchronous data parallelism — the fast path.

    No reference counterpart (the reference's closest is ADAG run
    synchronously); this is the capability the whole rebuild exists for:
    batch sharded over the ``dp`` mesh axis, params replicated, gradients
    mean-reduced with ``lax.psum`` over ICI inside one jit-compiled
    ``shard_map`` step, and the whole epoch driven by ``lax.scan`` so an
    epoch is ONE XLA dispatch. Mathematically equivalent to ADAG with
    communication_window=1 under identical data order (tested).
    """

    def __init__(self, *args, num_workers: Optional[int] = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.num_workers = num_workers

    def _coerce_dataset(self, dataset):
        return dataset  # _train streams ShardedDatasets natively

    # global batches per stacked dispatch on the disk-streaming path: one
    # XLA call covers this many batches, compiled once (+ one tail shape)
    STREAM_GROUP = 16

    def _train(self, dataset, shuffle: bool = False) -> Model:
        from distkeras_tpu.data.shard_io import ShardedDataset

        sharded = isinstance(dataset, ShardedDataset)
        if sharded:
            # disk-resident data plane: shards stream through the epoch
            # loop via the native loader (never merged into one host
            # array), reshuffled two-level per epoch when shuffle=True
            if self.params is None:
                self.ensure_params(
                    PartitionedDataset([dataset.read_shard(0)])
                )
        else:
            if shuffle:
                dataset = dataset.shuffle(seed=self.seed)
            self.ensure_params(dataset)
        mesh = default_mesh(self.num_workers)
        n_dev = mesh.devices.size

        optimizer = get_optimizer(self.worker_optimizer, self.learning_rate)
        loss_fn = get_loss(self.loss)
        metric_fns = resolve_metrics(self.metrics)
        apply_fn = self.model.apply

        # Multi-process SPMD (pod-style): when jax.distributed is up, the
        # mesh spans every process's devices; each process feeds ITS
        # devices' slice of every global batch and
        # make_array_from_process_local_data assembles the global array —
        # the sync-over-ICI/DCN analogue of the reference's per-executor
        # partitions (runtime.py brings the processes up).
        multiproc = jax.process_count() > 1
        feed_dev = (
            len([d for d in mesh.devices.flat
                 if d.process_index == jax.process_index()])
            if multiproc else n_dev
        )
        if multiproc and feed_dev == 0:
            raise ValueError(
                "this process owns no devices in the mesh — check "
                "num_workers vs the per-process device count"
            )

        if not sharded:
            # Global batches: [n_batches, n_dev * batch_size, ...] — each
            # device takes its batch_size-slice of every global batch
            # (per process, its local feed_dev share).
            merged = dataset.repartition(1).partition(0)
            xb, yb = workers_mod.batch_partition(
                merged, self.features_col, self.label_col,
                self.batch_size * feed_dev,
            )

        def device_step(carry, batch):
            params, opt_state = carry
            x, y = batch

            def objective(p):
                logits = apply_fn(p, x)
                return loss_fn(logits, y), logits

            (loss, logits), grads = jax.value_and_grad(
                objective, has_aux=True)(params)
            # params enter the shard_map replicated (in_specs P()), so the
            # backward pass has already psum'd grads over 'dp' — the
            # transpose of a broadcast is a psum. Dividing by the axis size
            # yields the global-mean gradient; an explicit psum here would
            # double-count by N.
            n_dev_ax = jax.lax.psum(1, "dp")
            grads = rules.tree_scale(grads, 1.0 / n_dev_ax)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            out = {"loss": jax.lax.pmean(loss, "dp")}
            for name, fn in metric_fns:
                out[name] = jax.lax.pmean(fn(logits, y), "dp")
            return (params, opt_state), out

        def epoch_fn(params, opt_state, xs, ys):
            (params, opt_state), ms = jax.lax.scan(
                device_step, (params, opt_state), (xs, ys)
            )
            return params, opt_state, ms

        sharded_epoch = jax.jit(
            shard_map(
                epoch_fn,
                mesh=mesh,
                in_specs=(P(), P(), P(None, "dp"), P(None, "dp")),
                out_specs=(P(), P(), P()),
            )
        )

        params = self.params
        opt_state = optimizer.init(params)
        start_epoch = 0
        if self.checkpointer is not None:
            step, state = self.checkpointer.restore(like={
                "params": params, "opt_state": opt_state,
                "extra": {"epoch": 0},
            })
            if state is not None:
                params = state["params"]
                opt_state = state["opt_state"] or opt_state
                start_epoch = int(state["extra"].get("epoch", step))
        # Input staging (VERDICT r1 weak #4): shard the epoch tensor over
        # the dp axis and upload it ONCE before the epoch loop — zero
        # host->device traffic per epoch. Datasets over the staging budget
        # stream through in equal chunks instead (one upload per chunk per
        # epoch, bounded residency). ShardedDatasets always stream from
        # disk through the native loader.
        from jax.sharding import NamedSharding

        batch_sharding = NamedSharding(mesh, P(None, "dp"))

        def put_batches(arr):
            if multiproc:
                return jax.make_array_from_process_local_data(
                    batch_sharding, arr
                )
            return jax.device_put(arr, batch_sharding)

        staged = False
        if sharded:
            # Multi-process: each process streams a DISJOINT stride of the
            # shard directory (ADVICE r2 #4 — a shared seed would otherwise
            # feed every process identical rows, silently duplicating data
            # across the global batch).
            my_shards = None
            batch_cap = None
            if multiproc:
                pi, pc = jax.process_index(), jax.process_count()
                if dataset.num_shards < pc:
                    raise ValueError(
                        f"sharded multi-process training needs >= "
                        f"{pc} shards (one per process); directory has "
                        f"{dataset.num_shards} — rewrite with a smaller "
                        "rows_per_shard"
                    )
                my_shards = list(range(pi, dataset.num_shards, pc))
                # Every process must enter the collective step the SAME
                # number of times: truncate all streams to the smallest
                # per-process batch count (known from meta, no IO) so
                # unequal shard row-sums can't desynchronize shard_map.
                # Each process p feeds its OWN device count's share of a
                # global batch, so its batch capacity divides by ITS
                # feed size, not ours (uneven meshes are supported).
                feed_of = [0] * pc
                for dv in mesh.devices.flat:
                    feed_of[dv.process_index] += 1
                batch_cap = min(
                    sum(dataset.shard_rows[s]
                        for s in range(p, dataset.num_shards, pc))
                    // (self.batch_size * feed_of[p])
                    for p in range(pc) if feed_of[p] > 0
                )
                if batch_cap == 0:
                    raise ValueError(
                        "some process's shard slice holds fewer rows than "
                        "its share of one global batch "
                        f"(batch_size={self.batch_size} × its device "
                        "count) — use smaller batches or rebalance the "
                        "shard directory"
                    )

            def epoch_chunks(epoch):
                seed = self.seed + epoch if shuffle else None
                bx, by = [], []
                n_seen = 0
                for b in dataset.batches(
                    self.batch_size * feed_dev, shuffle_seed=seed,
                    shards=my_shards,
                ):
                    if batch_cap is not None and n_seen >= batch_cap:
                        break
                    n_seen += 1
                    bx.append(b[self.features_col])
                    by.append(b[self.label_col])
                    if len(bx) == self.STREAM_GROUP:
                        yield np.stack(bx), np.stack(by)
                        bx, by = [], []
                if bx:
                    yield np.stack(bx), np.stack(by)
        elif xb.nbytes + yb.nbytes <= self.stage_limit_bytes:
            chunks = [(put_batches(xb), put_batches(yb))]
            staged = True
        else:
            bytes_per_batch = max(1, (xb.nbytes + yb.nbytes) // len(xb))
            per_chunk = max(1, self.stage_limit_bytes // (2 * bytes_per_batch))
            chunks = [
                (xb[i:i + per_chunk], yb[i:i + per_chunk])
                for i in range(0, len(xb), per_chunk)
            ]

        history: History = []
        for epoch in range(start_epoch, self.num_epoch):
            epoch_rows: List[dict] = []
            for cx, cy in (epoch_chunks(epoch) if sharded else chunks):
                if not staged:
                    cx = put_batches(cx)
                    cy = put_batches(cy)
                params, opt_state, ms = sharded_epoch(params, opt_state, cx, cy)
                ms = {k: np.asarray(v) for k, v in ms.items()}
                epoch_rows.extend(
                    {k: float(v[t]) for k, v in ms.items()}
                    for t in range(len(cx))
                )
            if self.checkpointer is not None:
                self.checkpointer.maybe_save(
                    epoch + 1, params, opt_state,
                    extra={"epoch": epoch + 1},
                    force=(epoch + 1 == self.num_epoch),
                )
            if self.metrics_writer is not None:
                base = len(history)
                for t, r in enumerate(epoch_rows):
                    self.metrics_writer.log(
                        step=base + t + 1,
                        samples=self.batch_size * n_dev, **r,
                    )
            history.extend(epoch_rows)
        self.params = params
        self.history = history
        self.executor_histories = [history]
        return Model(self.model, params)


class LMTrainer(Trainer):
    """Flagship long-context path as a Trainer: a language model trained
    over a dp x sp (x tp) mesh with the SPMD LM step
    (:func:`distkeras_tpu.parallel.spmd.make_lm_train_step`).

    What the model has to offer (``transformer_lm`` and ``afmoe_lm``
    do; one that lacks a piece is refused with a message that names it,
    :func:`distkeras_tpu.parallel.spmd.lm_step_model`): a
    ``features_only`` field (the step applies the copy that returns the
    final norm's output and runs the head inside the fused loss), a
    ``head`` subtree of its parameters (``kernel [D, V]``, with or
    without a ``bias``), ``remat`` as a field if activations are to be
    recomputed, ``attention`` / ``tp_size`` / ``ep_size`` where it can
    be sharded (read with defaults: absent means one chip's model) and
    ``training_refusals(axes)`` where it cannot. Optionally state that a
    rule updates and per-step counters: ``step_counters`` sown into
    ``"counters"``, ``rule_update(params, counters)`` run inside the
    dispatched window after each optimizer step, and
    ``step_metrics(params, counters)``, whose scalars come back with
    the ``[W]`` losses and join each step's metrics row and history
    entry. Rows of a model without counters hold ``loss`` alone.

    While ``train()`` runs, the loop's tree is the only copy of the
    parameters the trainer keeps on the device (``self.params`` is None
    until the run ends; a caller who handed a tree and wants it back
    after a failure keeps a reference of their own).

    No reference counterpart (the reference has no sequence models); this
    folds the framework's headline capability — ring-attention sequence
    parallelism + optional Megatron tensor parallelism — into the same
    Trainer API (checkpointing, JSONL metrics, timing, history) every
    other trainer speaks.

    Data contract: the dataset carries a ``tokens_col`` column of int
    token ids ``[N, T]``; each step consumes a ``[batch_size, T]`` global
    batch sharded batch-over-dp, sequence-over-sp. The loss is the global
    mean next-token cross-entropy (``loss``/``metrics``/``label_col``
    kwargs are ignored — an LM supervises itself). A
    :class:`~distkeras_tpu.data.shard_io.ShardedDataset` streams from
    disk shard by shard (peak host memory O(shard), identical
    trajectory to the in-memory path; ``shuffle=True`` becomes the
    two-level per-epoch reshuffle).

    Multi-process (pod) runs: with ``jax.distributed`` up (see
    :mod:`distkeras_tpu.runtime`) the mesh spans all processes; each
    process supplies its own token rows and ``batch_size`` counts THIS
    process's contribution per step. When the mesh keeps processes
    disjoint along dp, the global batch is batch_size x num_processes;
    when sp/tp span processes, processes sharing dp coordinates form
    replica groups (:func:`distkeras_tpu.parallel.mesh.replica_groups`)
    — the global batch is batch_size x num_groups, replica processes must
    supply IDENTICAL rows for in-memory datasets, and disk streaming
    arranges that automatically (one shard stride per group).
    """

    def __init__(self, model, *args, axes: Optional[dict] = None,
                 tokens_col: str = "tokens",
                 microbatches: Optional[int] = None, **kwargs):
        super().__init__(model, *args, **kwargs)
        # e.g. {"dp": 4, "sp": 2}, {"dp": 2, "sp": 2, "tp": 2},
        # or {"pp": 2, "dp": 4} (GPipe pipeline over the layer stack)
        self.axes = axes
        self.tokens_col = tokens_col
        # pipeline (pp) only: microbatches per optimizer step (GPipe M);
        # default 4*pp keeps the bubble fraction (pp-1)/(M+pp-1) under ~20%
        self.microbatches = microbatches
        if microbatches is not None and (axes or {}).get("pp", 1) <= 1:
            raise ValueError(
                "microbatches only applies to pipeline training — set "
                "axes={'pp': ..., 'dp': ...} (or drop microbatches)"
            )
        # while the window loop runs the trainer holds no tree but the
        # loop's own (see _train): whether a run that raised took a tree
        # the caller had given
        self._params_lost = False

    def _coerce_dataset(self, dataset):
        return dataset  # both LM paths stream ShardedDatasets natively

    # token batches per stacked dispatch on the disk-streaming path
    STREAM_GROUP = 16

    def _maybe_materialize(self, dataset):
        """(dataset, sharded): a sharded corpus that fits the staging
        budget is materialized so it gets the stage-once-on-device path
        (re-reading disk + re-uploading per epoch would be pure waste);
        bigger ones stream. Multi-process runs always stream — after a
        load() every process would hold ALL shards and silently feed
        duplicate rows."""
        from distkeras_tpu.data.shard_io import ShardedDataset

        if not isinstance(dataset, ShardedDataset):
            return dataset, False
        T = self._sharded_seq_len(dataset)
        itemsize = np.dtype(
            dataset.meta["columns"][self.tokens_col]["dtype"]
        ).itemsize
        small = dataset.num_rows * T * itemsize <= self.stage_limit_bytes
        if small and jax.process_count() == 1:
            return dataset.load(), False
        return dataset, True

    def _sharded_seq_len(self, sds) -> int:
        """Sequence length from shard metadata (no IO)."""
        if self.tokens_col not in sds.columns:
            raise ValueError(
                f"shard directory has no '{self.tokens_col}' column; "
                f"available: {sds.columns}"
            )
        _, row_shape = sds._col_info(self.tokens_col)
        if len(row_shape) != 1:
            raise ValueError(
                f"'{self.tokens_col}' must be [N, T] token ids; shard "
                f"rows have shape {row_shape}"
            )
        return row_shape[0]

    def _shard_slice(self, sds, rows_per_step: int, group=None):
        """(shard indices, per-epoch step cap) for THIS process.

        Multi-process runs stream disjoint shard strides — one stride per
        REPLICA GROUP (``group=(gid, n_groups)``, from
        :func:`distkeras_tpu.parallel.mesh.replica_groups`, when sp/tp
        span processes; one per process otherwise, the DataParallelTrainer
        convention). Replica processes pass the same gid, so they stream
        identical rows in identical order. Every stride is truncated to
        the smallest per-stride step count so the collective step can't
        desynchronize; single-process runs stream everything uncapped.

        The cap divides by a flat ``rows_per_step`` because LMTrainer's
        ``batch_size`` counts each process's OWN contribution (class
        docstring) — unlike DataParallelTrainer, whose batch_size is
        per-device and therefore scales by each process's device count
        (``feed_of[p]`` there, trainers.py · DataParallelTrainer._train).
        """
        if jax.process_count() <= 1:
            return None, None
        if group is not None:
            gid, n_strides = group
        else:
            gid, n_strides = jax.process_index(), jax.process_count()
        if sds.num_shards < n_strides:
            raise ValueError(
                f"sharded multi-process LM training needs >= {n_strides} "
                f"shards (one per feed stride); directory has "
                f"{sds.num_shards}"
            )
        cap = min(
            sum(sds.shard_rows[s] for s in range(g, sds.num_shards,
                                                 n_strides))
            // rows_per_step
            for g in range(n_strides)
        )
        if cap == 0:
            raise ValueError(
                "some stride's shard slice holds fewer rows than one "
                f"step's batch ({rows_per_step}) — use smaller batches "
                "or rebalance the shard directory"
            )
        return list(range(gid, sds.num_shards, n_strides)), cap

    def _stream_steps(self, sds, rows_per_step: int, shuffle: bool,
                      epoch: int, my_shards, cap):
        """Yield [rows_per_step, T] int32 arrays for one epoch, reading
        shard by shard (peak host memory O(shard), not O(corpus)); the
        two-level reshuffle uses a per-epoch seed."""
        seed = self.seed + epoch if shuffle else None
        n = 0
        for b in sds.batches(rows_per_step, shuffle_seed=seed,
                             shards=my_shards):
            if cap is not None and n >= cap:
                break
            n += 1
            yield np.ascontiguousarray(b[self.tokens_col], np.int32)

    def _single_chip_twin(self):
        """A standard-attention, unsharded twin of the model: identical
        param tree, applies FULL-SIZE params outside any mesh. Used for
        host init (ring attention only traces inside shard_map with the
        axis bound) and as the module of the returned Model (a tp-sharded
        module would expect 1/tp-size local param slices on predict)."""
        from distkeras_tpu.models import get_model
        from distkeras_tpu.models.registry import model_spec

        if (getattr(self.model, "tp_size", 1) == 1
                and getattr(self.model, "attention", None) != "ring"
                and getattr(self.model, "ep_size", 1) == 1):
            return self.model
        spec = model_spec(self.model)
        kwargs = dict(spec["kwargs"])
        kwargs.update(attention="standard", tp_size=1)
        if "ep_size" in kwargs:
            kwargs["ep_size"] = 1  # full expert banks; mesh slices them
        return get_model(spec["name"], **kwargs)

    def _init_params(self, tokens: np.ndarray, sp: int):
        """Full-size host init via the single-chip twin; the SPMD step
        slices any tp/ep-sharded leaves onto the mesh."""
        if self.params is not None:
            return self.params
        if self._params_lost:
            raise RuntimeError(
                "the train() call that raised held this trainer's only "
                "copy of the parameters it was given (the window step "
                "donates its state, and no second copy stays on the "
                "device beside it): set trainer.params again"
            )
        T_local = tokens.shape[1] // sp
        variables = self._single_chip_twin().init(
            jax.random.PRNGKey(self.seed),
            jnp.asarray(tokens[:1, :T_local], jnp.int32),
        )
        # what a model counts while it runs is no state of the trainer's
        self.params = {k: v for k, v in variables.items()
                       if k != "counters"}
        return self.params

    def _train(self, dataset: PartitionedDataset, shuffle: bool = False) -> Model:
        from distkeras_tpu.data.shard_io import ShardedDataset
        from distkeras_tpu.parallel.mesh import make_mesh
        from distkeras_tpu.parallel.spmd import (
            lm_state_shardings,
            lm_step_model,
            make_lm_train_step,
        )
        from jax.sharding import NamedSharding

        # in-memory datasets (and small sharded corpora, which materialize)
        # shuffle once up front; streaming ShardedDatasets get the
        # two-level per-epoch reshuffle inside the feed instead
        dataset, sharded = self._maybe_materialize(dataset)
        if shuffle and not sharded:
            dataset = dataset.shuffle(seed=self.seed)
        axes = dict(self.axes) if self.axes else {"dp": len(jax.devices())}
        refusals = getattr(self.model, "training_refusals", None)
        if refusals is not None:
            refusals(axes)
        if axes.get("pp", 1) > 1:
            return self._train_pp(dataset, shuffle)
        # an MoE model (ep_size > 1) trains on a (dp, ep) mesh via the
        # MoE step; everything else on dp x sp (x tp) via the LM step
        moe = getattr(self.model, "ep_size", 1) > 1
        if moe:
            if "ep" not in axes:
                raise ValueError(
                    "MoE model (ep_size > 1) needs an 'ep' mesh axis, "
                    "e.g. axes={'dp': 2, 'ep': 4}"
                )
            for bad in ("sp", "tp"):
                if axes.pop(bad, 1) > 1:
                    raise ValueError(
                        f"MoE training shards (dp, ep) only; drop {bad}"
                    )
            axes.setdefault("dp", 1)  # the feed spec always names dp
            mesh = make_mesh(axes)
            sp = tp = 1
        else:
            # the LM step always addresses the sp axis (ppermute targets,
            # axis_index for global positions); a size-1 axis makes the
            # single-chip case the same program as the sharded one
            axes.setdefault("sp", 1)
            if axes.get("tp", 1) == 1:
                axes.pop("tp", None)
            mesh = make_mesh(axes)
            sp = axes.get("sp", 1)
            tp = axes.get("tp", 1)
            if sp > 1 and getattr(self.model, "attention", None) != "ring":
                raise ValueError(
                    "sp > 1 needs the model built with attention='ring' "
                    "(seq_axis='sp')"
                )
            if getattr(self.model, "tp_size", 1) != tp:
                raise ValueError(
                    f"model.tp_size={getattr(self.model, 'tp_size', 1)} != "
                    f"mesh tp size {tp}"
                )

        # multi-process sp/tp meshes: processes whose devices share batch
        # (dp) coordinates are REPLICAS and must feed identical rows
        # (VERDICT r3 next #7 — the r3 code refused this configuration).
        # replica_groups() derives the grouping from the mesh itself;
        # groups stream the same shard stride and the feed assembles the
        # global batch per-shard via make_array_from_callback, so replica
        # consistency holds by construction.
        groups = None
        if jax.process_count() > 1 and (sp > 1 or tp > 1):
            from distkeras_tpu.parallel.mesh import replica_groups

            groups = replica_groups(mesh, "dp")
        if sharded:
            # disk-resident corpus: stream shard by shard (VERDICT r2 #3 —
            # the long-context path is the one most likely to meet a
            # corpus bigger than host RAM)
            T = self._sharded_seq_len(dataset)
            n_rows = dataset.num_rows
        else:
            tokens = np.asarray(dataset.column(self.tokens_col))
            if tokens.ndim != 2:
                raise ValueError(
                    f"'{self.tokens_col}' must be [N, T] int token ids, "
                    f"got shape {tokens.shape}"
                )
            T = tokens.shape[1]
            n_rows = len(tokens)
        if T % max(sp, 1) != 0:
            raise ValueError(
                f"sequence length {T} not divisible by sp={sp}"
            )
        seeded = self.params is None  # else given, or an earlier run's
        if sharded:
            first = dataset.read_shard(0)[self.tokens_col]
            self._init_params(np.ascontiguousarray(first[:1], np.int32), sp)
            del first
        else:
            self._init_params(tokens, sp)

        optimizer = get_optimizer(self.worker_optimizer, self.learning_rate)
        if moe:
            from distkeras_tpu.parallel.spmd import make_moe_lm_train_step

            step = make_moe_lm_train_step(
                self.model, optimizer, mesh, params_template=self.params,
                window=True,
            )
        else:
            lm_step_model(self.model, self.params)  # or say what it lacks
            step = make_lm_train_step(
                self.model, optimizer, mesh,
                tp_axis="tp" if tp > 1 else None,
                params_template=self.params if tp > 1 else None,
                window=True,
            )

        B = self.batch_size
        if n_rows < B:
            raise ValueError(
                f"dataset of {n_rows} rows is smaller than batch_size={B}"
            )
        if not sharded:
            n = (n_rows // B) * B
            batches = tokens[:n].reshape(-1, B, T).astype(np.int32)

        params = self.params
        opt_state = optimizer.init(params)
        start_epoch = 0
        if self.checkpointer is not None:
            ck_step, state = self.checkpointer.restore(like={
                "params": params, "opt_state": opt_state,
                "extra": {"epoch": 0},
            })
            if state is not None:
                params = state["params"]
                opt_state = state["opt_state"] or opt_state
                start_epoch = int(state["extra"].get("epoch", ck_step))

        # windowed steps: [W, B, T] stacked batches, one device dispatch
        # per group — the scan runs the W optimizer steps on-device
        if moe:
            feed_sharding = NamedSharding(mesh, P(None, ("dp", "ep")))
        else:
            feed_sharding = NamedSharding(
                mesh, P(None, "dp", "sp") if sp > 1 else P(None, "dp")
            )
        W = self.STREAM_GROUP

        # multi-process pod runs: this process feeds its devices' share of
        # every global token batch (same contract as DataParallelTrainer).
        # With replica groups (sp/tp spanning processes) the global batch
        # is B rows per GROUP, assembled per-shard from each process's
        # identical group feed — jax only asks the callback for this
        # process's addressable shards, and any sequence (sp) slicing
        # falls out of the requested index.
        if groups is not None:
            gid, n_groups = groups

            def put_feed(arr):
                gshape = (arr.shape[0], B * n_groups, T)
                base = gid * B

                def cb(index):
                    w_sl, r_sl, t_sl = index
                    r0, r1, _ = r_sl.indices(gshape[1])
                    if not (base <= r0 and r1 <= base + B):
                        # a bare assert would vanish under python -O and
                        # turn this into silent wrong-row reads
                        # (ADVICE r4 #2)
                        raise RuntimeError(
                            "feed asked for rows outside this process's "
                            f"replica group: [{r0}, {r1}) vs group block "
                            f"[{base}, {base + B})"
                        )
                    return arr[w_sl, r0 - base:r1 - base, t_sl]

                return jax.make_array_from_callback(
                    gshape, feed_sharding, cb
                )
        else:
            def put_feed(arr):
                if jax.process_count() > 1:
                    return jax.make_array_from_process_local_data(
                        feed_sharding, arr
                    )
                return jax.device_put(arr, feed_sharding)

        if groups is not None and not sharded:
            # replicas must feed IDENTICAL rows; nothing upstream enforces
            # that every process of the group was handed the same array,
            # so checksum-compare once before the first window
            # (ADVICE r4 #1)
            _verify_replica_feed(batches, groups[0])
        staged = False
        if sharded:
            my_shards, step_cap = self._shard_slice(dataset, B,
                                                    group=groups)

            def epoch_groups(epoch):
                group = []
                for tb in self._stream_steps(dataset, B, shuffle, epoch,
                                             my_shards, step_cap):
                    group.append(tb)
                    if len(group) == W:
                        yield np.stack(group)
                        group = []
                if group:
                    yield np.stack(group)
        else:
            # stage everything once when it fits the budget — zero
            # re-upload across epochs
            staged = batches.nbytes <= self.stage_limit_bytes
            if staged:
                with jax.profiler.TraceAnnotation("lm_trainer.stage",
                                                  staged=1):
                    feed = [put_feed(batches)]
            else:
                feed = [batches[i:i + W]
                        for i in range(0, len(batches), W)]
        # the windowed step DONATES params/opt_state (+13% measured — the
        # params+moments tree updates in place instead of copying per
        # window).
        # The loop rebinds both, but the FIRST call would donate buffers
        # the caller may still own (self.params / user-passed init / the
        # restored checkpoint) and leave self.params a deleted tree if
        # training raises mid-epoch — hand the loop copies of its own
        # (one cheap D2D copy per train(), not per window), already in
        # the step's layout on the mesh: state left on a single device
        # is a different input type and the step compiled a second time
        # when its own outputs came back in
        p_sh, o_sh = lm_state_shardings(
            optimizer, mesh, self.params,
            tp_axis="tp" if tp > 1 else None,
            ep_axis="ep" if moe else None,
        )
        params = jax.device_put(params, p_sh, may_alias=False)
        opt_state = jax.device_put(opt_state, o_sh, may_alias=False)
        # ... and the only ones: the trainer's own hold on the tree it
        # started from kept every parameter on the device a second time
        # for the whole run (2.0 GB of 16 at 504 M parameters, the room
        # a second 8k row of afmoe_lm needs). A caller who wants that
        # tree afterwards keeps a reference; a run that raises leaves
        # self.params None, and the next train() starts from the seed
        # again or, where the tree was given, asks for it
        self._params_lost = not seeded
        self.params = None
        history: History = []
        # the loop's phases as spans on the profiler's clock (what
        # Trainer(profile_dir=) shows beside the device's operations);
        # nothing at all while no profile is being taken
        span = jax.profiler.TraceAnnotation
        for epoch in range(start_epoch, self.num_epoch):
            # keep losses on-device until the epoch ends so dispatches
            # pipeline (no per-step host sync)
            epoch_losses = []
            for w, fb in enumerate(epoch_groups(epoch) if sharded
                                   else feed):
                if not staged:
                    with span("lm_trainer.stage", epoch=epoch, window=w):
                        fb = put_feed(fb)
                with span("lm_trainer.dispatch", epoch=epoch, window=w):
                    params, opt_state, losses = step(params, opt_state, fb)
                epoch_losses.append(losses)
            with span("lm_trainer.drain", epoch=epoch):
                for losses in epoch_losses:
                    # a model with step counters hands its steps'
                    # metrics back beside the losses, [W] each
                    if not isinstance(losses, dict):
                        losses = {"loss": losses}
                    cols = {k: np.atleast_1d(np.asarray(v))
                            for k, v in losses.items()}
                    for i in range(len(cols["loss"])):
                        row = {k: float(v[i]) for k, v in cols.items()}
                        history.append(row)
                        if self.metrics_writer is not None:
                            self.metrics_writer.log(
                                step=len(history), samples=B * T, **row,
                            )
            if self.checkpointer is not None:
                with span("lm_trainer.checkpoint", epoch=epoch):
                    self.checkpointer.maybe_save(
                        epoch + 1, jax.tree.map(np.asarray, params),
                        jax.tree.map(np.asarray, opt_state),
                        extra={"epoch": epoch + 1},
                        force=(epoch + 1 == self.num_epoch),
                    )
        self.params = jax.tree.map(np.asarray, params)
        self._params_lost = False
        self.history = history
        self.executor_histories = [history]
        return Model(self._single_chip_twin(), self.params)

    def _train_pp(self, dataset, shuffle: bool = False) -> Model:
        """Pipeline-parallel training: ``axes={"pp": ..., "dp": ...}``.

        The layer stack is split into ``pp`` contiguous stages
        (:func:`distkeras_tpu.parallel.pipeline.make_pp_lm_train_step`);
        every optimizer step consumes ``batch_size`` rows as ``M``
        microbatches of ``batch_size / M`` each (``M = self.microbatches``,
        default ``4 * pp``), batch sharded over ``dp``. Checkpoints store
        the PLAIN module layout (portable to every other LMTrainer mesh);
        the pipeline layout exists only on device.
        """
        from distkeras_tpu.parallel.mesh import make_mesh
        from distkeras_tpu.parallel.pipeline import (
            from_pipeline_params,
            make_pp_lm_train_step,
            to_pipeline_params,
        )
        from jax.sharding import NamedSharding

        axes = dict(self.axes)
        pp = axes.pop("pp")
        tp = axes.pop("tp", 1)
        for bad in ("sp", "ep"):
            if axes.pop(bad, 1) > 1:
                raise ValueError(
                    f"pipeline training shards (pp, dp, tp) only; drop "
                    f"'{bad}' (see ARCHITECTURE.md on pp composition)"
                )
        dp = axes.pop("dp", 1)
        if axes:
            raise ValueError(f"unknown mesh axes with pp: {sorted(axes)}")
        if (self.model.attention == "ring"
                or getattr(self.model, "moe_experts", 0) > 0):
            raise ValueError(
                "pp training takes a plain TransformerLM "
                "(non-ring attention, no MoE)"
            )
        if getattr(self.model, "tp_size", 1) != tp:
            raise ValueError(
                f"model.tp_size={getattr(self.model, 'tp_size', 1)} != "
                f"mesh tp size {tp} — build the model with tp_size={tp}, "
                "tp_axis='tp'"
            )
        # dp MAJOR, pp minor: multi-process meshes then split along dp, so
        # each process holds complete pipelines and feeds only its own
        # batch rows (pp-major would make processes replicas that must
        # feed identical data — unchecked, and silently wrong). Minor-axis
        # pp also keeps stage neighbors adjacent for the per-tick ppermute.
        if jax.process_count() > 1 and dp % jax.process_count() != 0:
            raise NotImplementedError(
                f"multi-process pp training needs dp ({dp}) divisible by "
                f"the process count ({jax.process_count()}) so every "
                "process holds complete pipelines and disjoint batch rows"
            )
        # tp innermost: the per-matmul psums ride the fastest links, the
        # per-tick pp ppermute the next ring out, dp's once-per-step
        # gradient reduction the outermost
        mesh = make_mesh({"dp": dp, "pp": pp, "tp": tp})

        # Checkpoints store the PLAIN module layout for params AND the
        # optimizer state's param-mirror subtrees (mu/nu/trace/... embed a
        # params-shaped tree each), so a pp checkpoint restores on any
        # other LMTrainer mesh and vice versa.
        def _map_mirrors(opt_state, convert, mirror_keys):
            def is_mirror(x):
                return isinstance(x, dict) and set(x) == mirror_keys

            return jax.tree.map(
                lambda x: convert(x) if is_mirror(x) else x,
                opt_state, is_leaf=is_mirror,
            )

        def opt_state_to_plain(opt_state, L):
            return _map_mirrors(
                opt_state, lambda m: from_pipeline_params(m, L),
                {"blocks", "rest"},
            )

        def opt_state_to_pipeline(opt_state, L):
            return _map_mirrors(
                opt_state, lambda m: to_pipeline_params(m, L), {"params"}
            )

        # device->host for pp-sharded trees: replicate on device first (an
        # all-gather over the mesh) so np.asarray sees an addressable
        # replica even when the pp axis spans processes
        _replicate = jax.jit(
            lambda t: t,
            out_shardings=NamedSharding(mesh, P()),
        )

        def _gather_host(tree):
            return jax.tree.map(np.asarray, _replicate(tree))

        from distkeras_tpu.data.shard_io import ShardedDataset

        sharded = isinstance(dataset, ShardedDataset)
        if sharded:
            T = self._sharded_seq_len(dataset)
            n_rows = dataset.num_rows
            first = dataset.read_shard(0)[self.tokens_col]
            self._init_params(np.ascontiguousarray(first[:1], np.int32), 1)
            del first
        else:
            tokens = np.asarray(dataset.column(self.tokens_col))
            if tokens.ndim != 2:
                raise ValueError(
                    f"'{self.tokens_col}' must be [N, T] int token ids, "
                    f"got shape {tokens.shape}"
                )
            T = tokens.shape[1]
            n_rows = len(tokens)
            self._init_params(tokens, sp=1)
        L = self.model.num_layers

        M = self.microbatches or 4 * pp
        B = self.batch_size
        if B % M != 0:
            raise ValueError(
                f"batch_size={B} not divisible by microbatches={M}"
            )
        micro_B = B // M
        # batch_size counts THIS process's rows; the assembled global
        # microbatch is micro_B * process_count, and that is what the dp
        # axis slices (ADVICE r3 #3 — validating the per-process count
        # against the global dp extent rejected valid multi-process
        # configs like pc=2, dp=4, micro_B=2)
        global_micro_B = micro_B * jax.process_count()
        if global_micro_B % dp != 0:
            raise ValueError(
                f"global microbatch size {global_micro_B} (= batch_size/"
                f"{M} x {jax.process_count()} processes) not divisible "
                f"by dp={dp}"
            )

        optimizer = get_optimizer(self.worker_optimizer, self.learning_rate)
        step = make_pp_lm_train_step(
            self.model, optimizer, mesh, params_template=self.params,
            tp_axis="tp" if tp > 1 else None,
        )

        if n_rows < B:
            raise ValueError(
                f"dataset of {n_rows} rows is smaller than batch_size={B}"
            )
        if not sharded:
            n = (n_rows // B) * B
            # [steps, M, micro_B, T] — one optimizer step per leading index
            batches = tokens[:n].reshape(-1, M, micro_B, T).astype(np.int32)

        pp_params = to_pipeline_params(self.params, L)
        opt_state = optimizer.init(pp_params)
        start_epoch = 0
        if self.checkpointer is not None:
            plain_opt_template = jax.tree.map(
                np.asarray, opt_state_to_plain(opt_state, L)
            )
            ck_step, state = self.checkpointer.restore(like={
                "params": self.params, "opt_state": plain_opt_template,
                "extra": {"epoch": 0},
            })
            if state is not None:
                pp_params = to_pipeline_params(state["params"], L)
                if state["opt_state"]:
                    opt_state = opt_state_to_pipeline(state["opt_state"], L)
                start_epoch = int(state["extra"].get("epoch", ck_step))

        feed_sharding = NamedSharding(mesh, P(None, "dp", None))

        def put_feed(arr):
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(
                    feed_sharding, arr
                )
            return jax.device_put(arr, feed_sharding)

        staged = False
        if sharded:
            my_shards, step_cap = self._shard_slice(dataset, B)

            def epoch_steps(epoch):
                for tb in self._stream_steps(dataset, B, shuffle, epoch,
                                             my_shards, step_cap):
                    yield tb.reshape(M, micro_B, T)
        else:
            staged = batches.nbytes <= self.stage_limit_bytes
            if staged:
                with jax.profiler.TraceAnnotation("lm_trainer.stage",
                                                  staged=1):
                    feed = [put_feed(b) for b in batches]
            else:
                feed = list(batches)
        history: History = []
        span = jax.profiler.TraceAnnotation  # as in _train
        for epoch in range(start_epoch, self.num_epoch):
            epoch_losses = []
            for w, fb in enumerate(epoch_steps(epoch) if sharded
                                   else feed):
                if not staged:
                    with span("lm_trainer.stage", epoch=epoch, window=w):
                        fb = put_feed(fb)
                with span("lm_trainer.dispatch", epoch=epoch, window=w):
                    pp_params, opt_state, loss = step(
                        pp_params, opt_state, fb)
                epoch_losses.append(loss)
            with span("lm_trainer.drain", epoch=epoch):
                for loss in epoch_losses:
                    row = {"loss": float(np.asarray(loss))}
                    history.append(row)
                    if self.metrics_writer is not None:
                        self.metrics_writer.log(
                            step=len(history), samples=B * T, **row,
                        )
            if self.checkpointer is not None:
                final = epoch + 1 == self.num_epoch
                # gate the (params-sized, cross-mesh) gather on the save
                # cadence — maybe_save would skip the step anyway
                if final or (epoch + 1) % self.checkpointer.every_steps == 0:
                    with span("lm_trainer.checkpoint", epoch=epoch):
                        self.checkpointer.maybe_save(
                            epoch + 1,
                            from_pipeline_params(
                                _gather_host(pp_params), L),
                            opt_state_to_plain(
                                _gather_host(opt_state), L),
                            extra={"epoch": epoch + 1},
                            force=final,
                        )
        self.params = from_pipeline_params(_gather_host(pp_params), L)
        self.history = history
        self.executor_histories = [history]
        return Model(self._single_chip_twin(), self.params)
