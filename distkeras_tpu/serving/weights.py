"""Live weight updates: payload serialization, structural validation,
and the two standard feeders (checkpoint directory, parameter server).

The paper's soul is a parameter server streaming weight deltas into
*running* workers; this module closes the train→serve loop the same
way: a serving fleet whose weights can be replaced while it streams.
The pieces, bottom-up:

- :func:`serialize_weights` / :func:`deserialize_weights` — the wire
  payload: one msgpack blob of the full variables pytree (the same
  flax codec every other frame uses), chunked by the client so a
  multi-GB tree rides many bounded frames instead of one giant one.
- :func:`validate_like` — the admission gate for a pushed tree:
  structure, shape, and dtype must match the weights the serving engine
  was built on exactly; the first mismatched leaf (in the current tree's
  flatten order) is named in a typed :class:`WeightPushError`, so a
  bad checkpoint is refused at the boundary instead of surfacing as a
  shape error inside a jitted tick.
- :class:`CheckpointWatcher` — polls a checkpoint directory
  (:class:`~distkeras_tpu.checkpoint.Checkpointer` layout) and pushes
  every new step's params to a serving endpoint (continuous
  deployment from training checkpoints).
- :class:`ParameterServerFeed` — subscribes to a running parameter
  server (local or :class:`~distkeras_tpu.networking.RemoteParameterServer`)
  and pushes the committed center variable whenever it has advanced by
  ``min_updates`` commits (the online-learning scenario: the serving
  fleet follows the trainer live).

Both feeders push through any object with a ``push_weights`` method —
a :class:`~distkeras_tpu.serving.ServingClient` against one server, or
against a :class:`~distkeras_tpu.serving.Router` (where one push is a
fleet-wide rolling update). They are duck-typed on purpose: this
module must not import the server (the server imports it).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional, Tuple

import numpy as np


class WeightPushError(RuntimeError):
    """A pushed weight tree was refused before any swap happened: its
    structure, a leaf's shape, or a leaf's dtype does not match the
    serving engine's current weights. Always names the first offending
    leaf (in the current tree's flatten order) so the bad checkpoint
    is attributable at the boundary — the pre-typed failure mode was a
    broadcast error deep inside a jitted tick, far from the cause.
    ``leaf`` carries the key path structurally. Travels the wire as
    the typed ``weight_push`` error code."""

    def __init__(self, msg: str, leaf: Optional[str] = None):
        super().__init__(msg)
        self.leaf = leaf


# -- payload codec -----------------------------------------------------------


def serialize_weights(variables: Any) -> bytes:
    """Variables pytree → one msgpack blob (host numpy leaves). The
    caller chunks the blob across frames; the receiving server joins
    and :func:`deserialize_weights` it."""
    import jax
    from flax import serialization as flax_serialization

    return flax_serialization.msgpack_serialize(
        jax.tree.map(np.asarray, variables)
    )


def deserialize_weights(payload: bytes) -> Any:
    """Inverse of :func:`serialize_weights` (numpy-leaf pytree)."""
    from flax import serialization as flax_serialization

    return flax_serialization.msgpack_restore(payload)


# -- validation --------------------------------------------------------------


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    """(key-path string, leaf) pairs in flatten order."""
    import jax

    return [
        (jax.tree_util.keystr(path), leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def _dtype(leaf) -> np.dtype:
    """A leaf's dtype without fetching it: arrays on the host or on a
    device and ``ShapeDtypeStruct``s say it themselves."""
    dt = getattr(leaf, "dtype", None)
    return np.dtype(dt) if dt is not None else np.asarray(leaf).dtype


def validate_like(current: Any, new: Any):
    """Raise :class:`WeightPushError` naming the first leaf (in the
    current tree's flatten order) whose presence, shape, or dtype
    differs between ``current`` (the weights the engine was built on:
    arrays, or their ``ShapeDtypeStruct``s) and ``new`` (the pushed
    tree); return silently when the trees match.
    Values are never compared — a weight update is *supposed* to
    change them."""
    cur = _leaf_paths(current)
    new_map = dict(_leaf_paths(new))
    cur_keys = {p for p, _ in cur}
    for path, leaf in cur:
        got = new_map.get(path)
        if got is None:
            raise WeightPushError(
                f"pushed weights are missing leaf {path}: expected "
                f"shape {tuple(np.shape(leaf))} "
                f"dtype {_dtype(leaf)}",
                leaf=path,
            )
        want_shape = tuple(np.shape(leaf))
        got_shape = tuple(np.shape(got))
        if want_shape != got_shape:
            raise WeightPushError(
                f"pushed weights mismatch at leaf {path}: shape "
                f"{got_shape} != expected {want_shape}",
                leaf=path,
            )
        want_dt, got_dt = _dtype(leaf), _dtype(got)
        if want_dt != got_dt:
            raise WeightPushError(
                f"pushed weights mismatch at leaf {path}: dtype "
                f"{got_dt} != expected {want_dt}",
                leaf=path,
            )
    for path in sorted(new_map):
        if path not in cur_keys:
            raise WeightPushError(
                f"pushed weights carry unknown leaf {path} (not in "
                f"the serving model's tree)",
                leaf=path,
            )


# -- feeders -----------------------------------------------------------------


class CheckpointWatcher:
    """Poll a checkpoint directory and push every new step's params.

    ``directory`` uses the :class:`~distkeras_tpu.checkpoint.Checkpointer`
    layout (orbax step dirs); ``target`` is anything with a
    ``push_weights(params, version=)`` method — a
    :class:`~distkeras_tpu.serving.ServingClient` against one LM
    server, or against a :class:`~distkeras_tpu.serving.Router`, where
    one push becomes a fleet-wide rolling update. The checkpoint step
    is forwarded as the pushed ``version``, so fleet weight versions
    are attributable to training steps. ``transform`` maps the restored
    ``state["params"]`` onto the variables tree the serving engine
    expects (default: wrap as ``{"params": ...}`` when not already a
    variables dict).

    A push refused by validation (:class:`WeightPushError` — the
    checkpoint does not fit the serving model) is recorded in
    ``errors`` and does NOT stop the watcher: the next checkpoint may
    be fine, and a bad artifact must not kill the deploy loop.

    ``journal`` (an :class:`~distkeras_tpu.telemetry.EventJournal`)
    records each push attempt as a ``weight_push`` control-plane event
    by outcome — the deploy loop's side of the story the receiving
    engine/router journals from theirs.
    """

    def __init__(self, directory: str, target: Any,
                 interval_s: float = 1.0, like: Optional[dict] = None,
                 transform: Optional[Callable[[Any], Any]] = None,
                 journal: Optional[Any] = None):
        self.directory = directory
        self.target = target
        self.interval_s = interval_s
        self.like = like
        self.transform = transform
        self.journal = journal
        self.last_step: Optional[int] = None
        self.pushed = 0
        self.errors: List[Tuple[int, str]] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._ckpt = None

    def _checkpointer(self):
        if self._ckpt is None:
            from distkeras_tpu.checkpoint import Checkpointer

            self._ckpt = Checkpointer(self.directory)
        else:
            # orbax caches the step list per manager; a writer in
            # another process (the trainer) advances it behind our
            # back, so refresh before reading latest_step
            try:
                self._ckpt._mgr.reload()
            except AttributeError:
                self._ckpt.close()
                from distkeras_tpu.checkpoint import Checkpointer

                self._ckpt = Checkpointer(self.directory)
        return self._ckpt

    @staticmethod
    def _as_variables(params):
        if isinstance(params, dict) and "params" in params:
            return params
        return {"params": params}

    def poll_once(self) -> bool:
        """One poll: push the latest step if it is new. Returns True
        when a push happened. Separated from the thread loop so tests
        (and cron-style callers) can drive it deterministically."""
        ckpt = self._checkpointer()
        step = ckpt.latest_step
        if step is None or step == self.last_step:
            return False
        _, state = ckpt.restore(step, like=self.like)
        params = state["params"]
        variables = (self.transform(params) if self.transform is not None
                     else self._as_variables(params))
        self.last_step = step
        try:
            self.target.push_weights(variables, version=step)
        except WeightPushError as e:
            self.errors.append((step, str(e)))
            if self.journal is not None:
                self.journal.append("weight_push",
                                    actor="ckpt_watcher",
                                    version=step, outcome="refused",
                                    reason=str(e))
            return False
        self.pushed += 1
        if self.journal is not None:
            self.journal.append("weight_push", actor="ckpt_watcher",
                                version=step, outcome="ok")
        return True

    def start(self) -> "CheckpointWatcher":
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.poll_once()
                except WeightPushError:
                    pass  # recorded by poll_once
                except Exception as e:  # transport blip: retry next poll
                    self.errors.append((-1, f"{type(e).__name__}: {e}"))

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        if self._ckpt is not None:
            self._ckpt.close()
            self._ckpt = None


class ParameterServerFeed:
    """Subscribe a serving endpoint to a running parameter server: the
    continuous-deployment loop where the fleet follows the trainer.

    ``ps`` is anything with ``num_updates`` and ``pull_host()`` (or
    ``pull()``) — a local
    :class:`~distkeras_tpu.parameter_servers.ParameterServer` or a
    :class:`~distkeras_tpu.networking.RemoteParameterServer` proxy.
    Every poll compares the server's commit count against the last
    pushed one; once it has advanced by at least ``min_updates``, the
    committed center variable is pulled and pushed to ``target``
    (``push_weights``), with the commit count as the weight version —
    every served token is thereby attributable to a training commit.
    ``transform`` adapts the center tree to the serving variables dict
    (default: wrap as ``{"params": center}`` unless already one)."""

    def __init__(self, ps: Any, target: Any, min_updates: int = 1,
                 interval_s: float = 0.5,
                 transform: Optional[Callable[[Any], Any]] = None,
                 journal: Optional[Any] = None):
        if min_updates < 1:
            raise ValueError(
                f"min_updates must be >= 1; got {min_updates}"
            )
        self.ps = ps
        self.target = target
        self.min_updates = min_updates
        self.interval_s = interval_s
        self.transform = transform
        self.journal = journal
        self.last_pushed_updates = 0
        self.pushed = 0
        self.errors: List[str] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _center(self):
        if hasattr(self.ps, "pull_host"):
            tree = self.ps.pull_host()
        else:
            import jax

            tree = jax.tree.map(np.asarray, self.ps.pull())
        if self.transform is not None:
            return self.transform(tree)
        if isinstance(tree, dict) and "params" in tree:
            return tree
        return {"params": tree}

    def poll_once(self) -> bool:
        """Push the center iff commits advanced by ``min_updates``
        since the last push. Returns True when a push happened."""
        n = int(self.ps.num_updates)
        if n - self.last_pushed_updates < self.min_updates:
            return False
        variables = self._center()
        self.last_pushed_updates = n
        self.target.push_weights(variables, version=n)
        self.pushed += 1
        if self.journal is not None:
            self.journal.append("weight_push", actor="ps_feed",
                                version=n, outcome="ok")
        return True

    def start(self) -> "ParameterServerFeed":
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.poll_once()
                except Exception as e:  # refused push / transport blip:
                    # record, keep following the trainer
                    self.errors.append(f"{type(e).__name__}: {e}")

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None


def chunk_payload(payload: bytes, chunk_bytes: int) -> List[bytes]:
    """Split one serialized weight blob into wire-frame-sized chunks
    (at least one, even for an empty payload)."""
    if chunk_bytes < 1:
        raise ValueError(f"chunk_bytes must be >= 1; got {chunk_bytes}")
    out = [payload[i:i + chunk_bytes]
           for i in range(0, len(payload), chunk_bytes)]
    return out or [b""]


__all__ = [
    "WeightPushError",
    "serialize_weights",
    "deserialize_weights",
    "validate_like",
    "chunk_payload",
    "CheckpointWatcher",
    "ParameterServerFeed",
]
