"""Microbenchmark: async PS commit+pull round-trip, device-resident vs host.

VERDICT r2 #4 asked for proof the host round-trip is gone from the async
exchange. This measures one window's PS traffic for the CIFAR CNN (the
model configs 3-4 train): worker computes a delta on its chip, commits,
pulls the fresh center — repeated R times.

- "device" is the shipped path: the center lives in HBM, the commit is a
  donated jit add, the pull a device copy (`parameter_servers.py`).
- "host" re-enacts round 2's semantics for comparison: np.asarray the
  delta to host, numpy add under the lock, re-upload the pulled center —
  i.e. two crossings of the host link per window.

Prints one JSON line with both times and the speedup.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from distkeras_tpu.models import get_model
    from distkeras_tpu.ops import rules
    from distkeras_tpu.parameter_servers import DeltaParameterServer

    dev = jax.devices()[0]
    model = get_model("cifar_cnn")
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3), jnp.float32)
    )
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    delta = jax.device_put(
        jax.tree.map(lambda x: jnp.full_like(x, 1e-4), params), dev
    )
    rounds = 50

    # -- shipped path: device-resident center --------------------------------
    ps = DeltaParameterServer(params, device=dev)
    ps.commit(delta)  # warm the donated jit
    pulled = ps.pull(device=dev)
    jax.block_until_ready(pulled)
    t0 = time.perf_counter()
    for _ in range(rounds):
        ps.commit(delta)
        pulled = ps.pull(device=dev)
    jax.block_until_ready(pulled)
    dt_dev = (time.perf_counter() - t0) / rounds

    # -- round-2 semantics: host center, two link crossings per window -------
    center = jax.tree.map(np.asarray, params)
    lock = threading.Lock()
    delta_dev = delta

    def host_round():
        nonlocal center
        d = jax.tree.map(np.asarray, delta_dev)  # device -> host
        with lock:
            center = rules.downpour_commit(center, d)  # numpy add
            snap = jax.tree.map(np.copy, center)
        return jax.device_put(snap, dev)  # host -> device

    jax.block_until_ready(host_round())  # warm
    t0 = time.perf_counter()
    for _ in range(rounds):
        pulled = host_round()
    jax.block_until_ready(pulled)
    dt_host = (time.perf_counter() - t0) / rounds

    print(json.dumps({
        "metric": "async_ps_commit_pull_roundtrip",
        "model_bytes": n_bytes,
        "device_ms": round(dt_dev * 1e3, 3),
        "host_ms": round(dt_host * 1e3, 3),
        "speedup": round(dt_host / dt_dev, 1),
        "unit": "ms/window",
        "device_kind": dev.device_kind,
    }))


if __name__ == "__main__":
    from distkeras_tpu.utils import compile_cache

    compile_cache.enable()
    main()
