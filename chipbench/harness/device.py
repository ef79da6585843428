"""The device the run is on: the chip check, the table of peaks, peak
memory, the compile cache and a count of what compiled."""

from __future__ import annotations

import os

from chipbench.harness.spec import ROOT

# One row per ``device_kind`` as JAX reports it. A device that is not
# here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s, 16 GB per chip",
    },
}


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip without a row of peaks."""


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    import jax

    if jax.default_backend() != "tpu":
        raise NoChip(f"JAX found no TPU (backend {jax.default_backend()!r}):"
                     f" the benchmark measures on the chip only")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise NoChip(f"no peaks known for device kind {device_kind!r}; "
                     f"known: {sorted(PEAKS)}") from None


def describe(devices) -> dict:
    """The ``device`` object of the result line."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                int(s.get("peak_bytes_in_use", 0)) for s in stats)}


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, whatever the
    environment says: the path is part of the cache's key, and two
    checkouts (the parent's and the change's) must share nothing. No size
    cap: a capped cache that is smaller than a cell's programs evicts
    each before the next run asks for it."""
    import jax

    path = os.path.join(os.path.dirname(ROOT), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


class CompileLog:
    """Backend compiles and persistent-cache hits and misses, as JAX's
    monitoring reports them; ``mark()`` then ``since(mark)`` counts what
    happened in between (the window must show none)."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.compiles = []  # (function name, seconds)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, seconds, **kw):
        if event == self._COMPILE:
            self.compiles.append((kw.get("fun_name"), float(seconds)))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return len(self.compiles), self.hits, self.misses

    def since(self, mark) -> dict:
        n, hits, misses = mark
        new = self.compiles[n:]
        return {"backend_compiles": len(new),
                "backend_compile_s": round(sum(s for _, s in new), 3),
                "slowest": sorted(new, key=lambda c: -c[1])[:4],
                "cache_hits": self.hits - hits,
                "cache_misses": self.misses - misses}
