"""Continuous-batching decode engine (Orca/vLLM-style iteration-level
scheduling) for :class:`~distkeras_tpu.models.transformer.TransformerLM`.

The static :func:`~distkeras_tpu.models.transformer.generate` path runs a
whole batch to ``max_new_tokens`` in lock step — a finished row burns
decode steps emitting padding, and a new request waits for the slowest
row. This engine removes both taxes while keeping every shape static
(zero recompiles in steady state):

- **Slot pool.** One preallocated per-layer KV cache of batch ``S``
  (``slots``), the same GQA/int8 layout ``CausalSelfAttention`` already
  uses, but with ``slot_cursor=True``: the cache cursor is a ``[S]``
  vector, so each batch row is an independent sequence at its own depth.
- **Chunked prefill, fused into the tick** (Sarathi-Serve-style; the
  default). A joining prompt never runs as one monolithic prefill
  dispatch: it streams into its slot ``prefill_chunk`` tokens per tick,
  coalesced with the decoding rows into ONE ``[S, C]`` mixed dispatch —
  each row at its own per-row valid length (decoding rows carry 1
  token, prefilling rows up to C), K/V written at absolute per-row
  positions, logits taken at each row's last valid token. The
  scheduler's ``tick_token_budget`` meters how many prompt tokens each
  tick carries (decodes reserved first), so a 2048-token prompt costs
  live streams a bounded per-tick overhead instead of a
  multi-hundred-ms inter-token-latency spike. ``prefill_chunk=None``
  restores the legacy monolithic B=1 prefill scattered in with
  ``dynamic_update_slice`` (kept as the bench baseline).
- **One jitted tick.** Each tick samples one token per decoding slot
  from the pooled last-logits (per-slot sampling config and RNG chain,
  same math as a solo ``generate``; a slot's RNG only advances on ticks
  it sampled) and advances all ``S`` slots through one mixed step.
  Ticks are compiled once per distinct per-slot sampling configuration
  tuple — twice with chunking (the ``[S, C]`` mixed shape and the
  ``[S, 1]`` all-decode shape), so an all-decode steady state pays
  exactly the unchunked tick.
- **A tick ahead** (the default, ``pipeline=True``): the step is a
  depth-2 software pipeline — tick N+1 is planned from host state and
  dispatched BEFORE tick N's tokens are read back, so the host's
  planning, streaming and admission pass under the device's tick and
  not between two of them. A row whose token budget runs out in the
  unread tick is not fed again (a length finish is host-known); a row
  that samples its eos there is: its one overrun token is dropped at
  reconciliation and streams stay bit-identical to the alternating
  loop. A freed slot refills on the next step's admit, one tick later
  than the alternating loop refills it. Every tick's host control
  arguments ride one packed int32 transfer in both loops.
- **The alternating loop** (``pipeline=False``, the bit-parity
  reference): plan, dispatch, read, stream, and only then the next
  plan. A slot whose request sampled its eos (or hit its token budget)
  is freed when the tick's tokens are processed and refilled from the
  scheduler queue in the same :meth:`step` call — the next tick already
  decodes the new request (same-tick refill).
- **Paged mode** (``paged=True``): the per-slot slabs become one pool of
  fixed-size KV blocks (:mod:`distkeras_tpu.serving.kvpool`) addressed
  through per-row block tables, with radix-tree prompt-prefix sharing
  (:mod:`distkeras_tpu.serving.prefix`) — a request whose prompt opens
  with an already-cached prefix increfs those blocks and prefills only
  the suffix (copy-on-write when it diverges mid-block). Admission
  becomes free-block-aware so live sequences are never evicted
  mid-decode. Token streams remain bit-identical to solo ``generate()``
  in both modes.

Observability is the :mod:`distkeras_tpu.telemetry` layer: every request
leaves a span chain (``queued → prefill → decode → finish``, with slot
id and token counts) in the tracer, and the engine publishes live
counters/gauges/histograms (tick count, tokens, occupancy, queue depth,
TTFT, per-token latency, per-stream inter-token latency
``serving_itl_ms``, decode-stall count, prefill fraction) into a
:class:`~distkeras_tpu.telemetry.MetricRegistry` — scrapeable over the
msgpack ``stats``/``trace_dump`` ops and the HTTP endpoint. The
per-tick/per-request JSONL records ride a
:class:`~distkeras_tpu.utils.metrics.MetricsWriter` for offline
analysis where the caller hands one (``metrics=``). The engine also
keeps a black box: a per-tick
:class:`~distkeras_tpu.telemetry.FlightRecorder` snapshot (slot states,
budget split, phase-decomposed latency) dumped to a postmortem JSONL on
crash or stall, plus runtime introspection — jit recompile counting
inside the traced bodies and RSS/device-memory watermark gauges. All
instrumentation is host-side bookkeeping around the jitted calls —
token streams stay bit-identical to solo ``generate()``.
"""

from __future__ import annotations

import functools
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, List, NamedTuple, Optional, Tuple

import flax
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distkeras_tpu import telemetry
from distkeras_tpu.models.transformer import (
    compute_params,
    filter_logits,
    sample_tokens,
)
from distkeras_tpu.ops import splash_prefill
from distkeras_tpu.telemetry.events import EventJournal
from distkeras_tpu.telemetry.flight import FlightRecorder
from distkeras_tpu.telemetry.runtime import MemoryWatermarks, recompiles
from distkeras_tpu.telemetry.slo import StallWatchdog
from distkeras_tpu.serving.kvpool import BlockPool, HostBlockPool
from distkeras_tpu.serving.prefix import RadixPrefixIndex
from distkeras_tpu.serving.weights import validate_like
from distkeras_tpu.serving.scheduler import (
    DEFAULT_PREFILL_CHUNK,
    QOS_TIERS,
    DrainingError,
    FIFOScheduler,
    Request,
)
from distkeras_tpu.utils.metrics import MetricsWriter, percentiles


def _shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` with vma checking disabled — the serving bodies
    keep sampling on replicated post-psum logits by construction, and
    the mesh-parity suite asserts the streams, which is the check that
    matters (the training steps in parallel/spmd.py keep strict
    checking; they differentiate, serving doesn't)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _pack_i32(*arrs) -> np.ndarray:
    """Flatten a tick's host-side control arguments (block tables, seq
    lens, fed tokens, valid lens, masks) into ONE int32 buffer so every
    dispatch pays a single host→device transfer instead of one per
    array. The unpack order inside the jitted bodies must match the
    pack order here (:func:`_unpack_i32`)."""
    return np.concatenate(
        [np.ascontiguousarray(a, np.int32).ravel() for a in arrs]
    )


def _unpack_i32(packed, shapes):
    """Static-shape views into a packed control buffer (traced: offsets
    and shapes are python ints, so the slices compile to free
    reshapes)."""
    out, off = [], 0
    for shp in shapes:
        n = int(np.prod(shp))
        out.append(packed[off:off + n].reshape(shp))
        off += n
    return out


def _freeze(tree, is_leaf=None):
    """Pytree -> hashable (treedef, leaves) so spec trees can ride the
    lru_cache keys of the tick builders (compiled ticks stay shared
    across engines with identical model/mesh/spec config, which is what
    lets a warm engine pre-trace for a measured one)."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_leaf)
    return (treedef, tuple(leaves))


def _thaw(frozen):
    treedef, leaves = frozen
    return jax.tree.unflatten(treedef, list(leaves))


class _ShardCtx(NamedTuple):
    """Hashable tensor-parallel context for the jitted serving bodies:
    the mesh, its model axis, and frozen PartitionSpec trees for the
    weight and cache pytrees (per lm_param_specs / serving_cache_specs —
    Q/KV heads column-sharded, out/mlp_down row-sharded with one psum
    per block, cache KV-head axis sharded, everything else replicated).
    ``cache1`` is the frozen LOCAL (shape, dtype) tree for the B=1
    scratch cache of the monolithic slot prefill — eval_shape of a
    tp>1 module can't trace outside shard_map (unbound psum axis), so
    the engine precomputes the per-shard shapes instead."""

    mesh: Any
    axis: str
    pspec: Any
    cspec: Any
    cache1: Any = None

    def spec(self, kind: str):
        if kind == "p":
            return _thaw(self.pspec)
        if kind == "c":
            return _thaw(self.cspec)
        return P()


def _compile(body, ctx: Optional[_ShardCtx], in_kinds: str,
             out_kinds: str, donate):
    """jit the tick/prefill ``body`` — plain (single-chip) when ``ctx``
    is None, else under ``shard_map`` on the ctx's mesh with per-arg
    specs by kind: 'p' = the weight spec tree, 'c' = the cache spec
    tree, 'r' = replicated. All bodies keep sampling/logits/rng math on
    replicated values, so every shard emits identical tokens and only
    the weight/cache pytrees (and the head-sharded compute between
    them) differ per device."""
    if ctx is None:
        return jax.jit(body, donate_argnums=donate)
    return jax.jit(
        _shard_map(
            body, ctx.mesh,
            tuple(ctx.spec(k) for k in in_kinds),
            tuple(ctx.spec(k) for k in out_kinds),
        ),
        donate_argnums=donate,
    )


class _CacheLayout(NamedTuple):
    """How the KV cache is laid out, as far as a tick can tell: the one
    hashable value the tick builders key on and the only place that
    knows how the layouts differ inside a tick. ``dm`` is the decode
    module a tick applies. ``max_blocks`` None is the SLOT layout: one
    slab per row whose ``[S]`` cursors live in the cache pytree and
    advance on the device, so a tick's control buffer holds the kind's
    own fields and nothing else. An int is the PAGED layout's
    block-table width: tables and cursors (``seq_lens``) are the
    host's, and ride at the head of every tick's control buffer."""

    dm: Any
    max_blocks: Optional[int] = None

    @property
    def paged(self) -> bool:
        return self.max_blocks is not None

    def tag(self, kind: str) -> str:
        """The name this layout's program of ``kind`` compiles under."""
        return ("serve.paged_" if self.paged else "serve.") + kind

    def unpack(self, packed, S: int, shapes=()):
        """Device side (traced): take this layout's head off a tick's
        control buffer. Returns the keywords ``dm.apply`` takes to find
        each row's K/V, and the kind's own fields by ``shapes``."""
        if not self.paged:
            return {}, _unpack_i32(packed, shapes)
        tables, lens, *fields = _unpack_i32(
            packed, ((S, self.max_blocks), (S,), *shapes))
        return {"block_tables": tables, "seq_lens": lens}, fields

    def pack(self, eng, fields=(), advance=None):
        """Host side: one tick's control buffer for ``eng`` — this
        layout's head, then the kind's own ``fields`` — with the
        host-owned cursors moved on by ``advance`` [S], what the
        dispatch writes, once they are packed. None where there is
        nothing to send: the slot layout's plain decode tick."""
        if self.paged:
            fields = (eng._block_tables, eng._seq_lens, *fields)
        packed = _pack_i32(*fields) if fields else None
        if advance is not None:
            self.advance(eng, advance)
        return packed

    def advance(self, eng, by):
        """Move ``eng``'s host-owned cursors on by ``by`` [S]; idle rows
        stay parked at 0 on the trash block. The slot layout's cursors
        advance on the device, inside the cache."""
        if self.paged:
            # REBIND, never mutate: jnp.asarray can alias the numpy
            # buffer zero-copy while the async tick still reads it —
            # in-place writes would race the device
            eng._seq_lens = eng._seq_lens + np.asarray(by, np.int32)


def _sample_rows(cfgs, logits, rngs, advance=None):
    """One token per slot from the pooled ``[S, vocab]`` ``logits``
    (traced): row ``s`` by its own ``cfgs[s] = (temperature, top_k,
    top_p)``, from its own RNG chain, on a ``[1, vocab]`` slice — the
    exact call shape of a solo B=1 ``generate``, so streams are
    token-identical. A chain moves on only where ``advance`` [S] says
    the row really sampled (a prefilling, restoring or stopped row must
    not burn the chain that makes its stream identical to solo
    ``generate()``); ``None`` moves every row's. Returns the ``[S]``
    tokens and the rows' chains, a list the caller stacks where it
    returns them."""
    toks, chains = [], []
    with jax.named_scope("sample"):
        for s, (temp, top_k, top_p) in enumerate(cfgs):
            rng, sub = jax.random.split(rngs[s])
            toks.append(
                sample_tokens(logits[s][None], sub, temp,
                              top_k, top_p)[0]
            )
            chains.append(rng if advance is None
                          else jnp.where(advance[s], rng, rngs[s]))
    return jnp.stack(toks), chains


@functools.lru_cache(maxsize=64)
def _prefill_fn(dm_one, ctx: Optional[_ShardCtx] = None):
    """Compiled per-slot prefill for a B=1 decode module: run the prompt
    through the ordinary prefill (writing a fresh B=1 cache), then
    scatter every cache leaf into row ``slot`` of the pooled cache.
    Cached per decode-module config; each distinct prompt length traces
    its own prefill, exactly like ``generate``. Under a mesh (``ctx``)
    the body runs per-shard on its KV-head slice; the scratch cache is
    built from the ctx's precomputed LOCAL shapes (a tp module's init
    can't eval_shape outside shard_map — unbound psum axis)."""

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrr",
                       out_kinds="cr", donate=(1, 2))
    def prefill(params_only, pooled, last_logits, prompt, slot):
        recompiles.note("serve.prefill")
        if ctx is None:
            cache1 = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(
                    dm_one.init, jax.random.PRNGKey(0),
                    jnp.zeros((1, 1), jnp.int32),
                )["cache"],
            )
        else:
            cache1 = jax.tree.map(
                lambda sd: jnp.zeros(sd[0], sd[1]), _thaw(ctx.cache1),
                is_leaf=lambda x: isinstance(x, tuple),
            )
        logits, vs = dm_one.apply(
            {**params_only, "cache": cache1}, prompt, mutable=["cache"]
        )

        def merge(pool, one):
            if one.ndim == 0:  # scalar cursor -> row of the [S] vector
                return pool.at[slot].set(one.astype(pool.dtype))
            # [1, ...] leaf -> rows [slot:slot+1, ...] of the pool
            return jax.lax.dynamic_update_slice(
                pool, one.astype(pool.dtype),
                (slot,) + (0,) * (one.ndim - 1),
            )

        new_pool = jax.tree.map(merge, pooled, vs["cache"])
        new_last = last_logits.at[slot].set(
            logits[0, -1].astype(last_logits.dtype)
        )
        return new_pool, new_last

    return prefill


@functools.lru_cache(maxsize=256)
def _tick_fn(layout, cfgs, ctx: Optional[_ShardCtx] = None):
    """Compiled decode tick for one per-slot sampling-config tuple
    ``cfgs = ((temperature, top_k, top_p), ...)``: sample one token per
    slot (:func:`_sample_rows`; every chain advances), then advance all
    slots one decode step. With a mesh ``ctx`` the same body runs under
    shard_map: sampling happens on the replicated post-psum logits
    (every shard draws the identical token), the decode step on each
    shard's head slice. The slot layout's tick takes NO control buffer
    — its cursors are in the cache — and its dispatch uploads nothing;
    the paged layout's takes its head (tables and seq lens) as one
    packed int32 transfer."""

    @functools.partial(_compile, ctx=ctx,
                       in_kinds="pcrrr" if layout.paged else "pcrr",
                       out_kinds="crrr", donate=(1, 2, 3))
    def tick(params_only, cache, last_logits, rngs, packed=None):
        recompiles.note(layout.tag("tick"))
        where, _ = layout.unpack(packed, rngs.shape[0])
        tok, chains = _sample_rows(cfgs, last_logits, rngs)  # [S]
        logits, vs = layout.dm.apply(
            {**params_only, "cache": cache}, tok[:, None],
            mutable=["cache"], **where,
        )
        return vs["cache"], logits[:, -1], tok, jnp.stack(chains)

    return tick


@functools.lru_cache(maxsize=64)
def _paged_prefill_fn(dm_paged, ctx: Optional[_ShardCtx] = None):
    """Compiled paged prefill: run the prompt's UNCACHED suffix at B=1
    against the shared block pool — the row's block table maps each
    suffix position into blocks this row owns, and cached prefix
    positions are simply attended (their K/V was written by whichever
    request computed them first). The cache IS the global pool, so
    unlike the slot path there is no per-slot scatter-merge step."""

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrrrr",
                       out_kinds="cr", donate=(1, 2))
    def prefill(params_only, cache, last_logits, suffix, table, start,
                slot):
        recompiles.note("serve.paged_prefill")
        logits, vs = dm_paged.apply(
            {**params_only, "cache": cache}, suffix,
            block_tables=table, seq_lens=start, mutable=["cache"],
        )
        new_last = last_logits.at[slot].set(
            logits[0, -1].astype(last_logits.dtype)
        )
        return vs["cache"], new_last

    return prefill


# a tick's work that only some models have (see ServingEngine.stats)
_MODEL_WORK = ("index_positions_scored", "keys_selected", "routed_here",
               "routed_total", "expert_rows_computed", "expert_weight_bytes",
               "full_key_positions",
               "window_key_positions", "state_rows_stepped",
               "chunk_positions_live", "chunk_positions_computed",
               "window_positions", "mtp_positions_fed")


def _counter_sums(sown, names):
    """``[len(names)]`` int32: each named counter summed over the
    modules that sowed it (inside the jitted bodies)."""
    flat = flax.traverse_util.flatten_dict(flax.core.unfreeze(sown))
    return jnp.stack([
        sum((v for path, v in flat.items() if path[-1] == name),
            jnp.zeros((), jnp.int32)).astype(jnp.int32)
        for name in names])


def _counter_work(model, words) -> dict:
    """A tick's ``model.tick_counters`` as the host keeps them: a
    counter the model names in ``tick_counter_units`` under the name
    and times the unit given there (what the device can only count in
    whole units of, an int32 like the rest)."""
    units = getattr(model, "tick_counter_units", {})
    work = {}
    for name, n in zip(model.tick_counters, words):
        kept, unit = units.get(name, (name, 1))
        work[kept] = n * unit
    return work


def _packed_count(budget: int, slots: int, chunk: int) -> int:
    """``N``, the ONE compiled count the per-token layers of a
    ``[slots, chunk]`` mixed tick run over once its live tokens are
    packed. Derived, not set: the default scheduler deals a tick at most
    ``max(budget, slots)`` tokens (every decoding row reserves one, the
    remainder goes to chunks), rounded up to the sublane's 8 rows, and
    never more than the tick holds. One count and no ladder: under the
    chip's ridge fewer rows buy nothing, and each further program is
    seconds of set-up and a compile waiting inside steady state."""
    return min(slots * chunk, -(-max(int(budget), slots) // 8) * 8)


@functools.lru_cache(maxsize=256)
def _mixed_tick_fn(layout, cfgs, chunk, ctx: Optional[_ShardCtx] = None,
                   live: Optional[int] = None):
    """Compiled CHUNKED mixed prefill/decode tick (the Sarathi-style
    fused step): one ``[S, chunk]`` dispatch advances every slot —
    decoding rows consume 1 valid token (their own freshly-sampled
    one), prefilling rows consume up to ``chunk`` prompt tokens, idle
    rows run padding. Per-slot sampling is identical to :func:`_tick_fn`
    (:func:`_sample_rows`), but a slot's RNG only advances when it
    actually sampled (``sample_mask``) — prefill ticks must not burn
    the chain that makes streams token-identical to solo
    ``generate()``. Logits are taken at each row's LAST VALID token, so
    the tick that feeds a prompt's final chunk leaves exactly the
    logits a monolithic prefill would have. A mesh ``ctx`` runs the
    identical body per head-shard under shard_map — the ``[S, C]``
    chunk semantics (absolute per-row positions, valid-length writes,
    RNG discipline) are untouched, so sharded streams stay
    bit-identical to the single-chip path. Host control arguments (the
    layout's head, then fed tokens, valid lens, sample mask) arrive as
    ONE packed int32 buffer — a single transfer per tick; under the
    paged layout K/V reads and writes go through each row's block
    table, and chunk padding lands in the reserved trash block. A model
    that declares ``tick_counters`` (names it sows into the
    ``counters`` collection) gets their sums over the layers appended
    to the tokens, ``[S + len(counters)]``; for any other model the
    program is unchanged.

    ``live`` (``N``, see :meth:`ServingEngine._live_count`) is the
    PACKED form, for a decode module that declares
    ``packs_live_tokens``: the per-token layers run over the tick's
    live tokens packed to ``N`` rows, found on the device from the
    valid lens alone; only the attend sees ``[S, chunk]``, and the head
    runs on each row's last valid token, so the logits come back ``[S,
    1, vocab]``. Same control buffer, cache bytes, cursors and
    tokens. ``None`` is the full-width program, text for text what it
    was before there was a packed one."""
    counters = tuple(getattr(layout.dm, "tick_counters", ()))

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrr",
                       out_kinds="crrr", donate=(1, 2, 3))
    def tick(params_only, cache, last_logits, rngs, packed):
        recompiles.note(layout.tag("mixed_tick"))
        S = rngs.shape[0]
        where, (fed, valid, smask) = layout.unpack(
            packed, S, ((S, chunk), (S,), (S,)))
        sample_mask = smask != 0
        sampled, chains = _sample_rows(cfgs, last_logits, rngs,
                                       sample_mask)  # [S]
        inputs = fed.at[:, 0].set(
            jnp.where(sample_mask, sampled, fed[:, 0])
        )
        if live is not None:
            where = {**where, "live_tokens": live}
        logits, vs = layout.dm.apply(
            {**params_only, "cache": cache}, inputs,
            valid_lens=valid,
            mutable=["cache", "counters"] if counters else ["cache"],
            **where,
        )
        if live is not None:
            last = logits[:, 0]  # the model took them there itself
        else:
            # row s's next-step logits live at its last valid token; a
            # starved prefill row (valid 0) wraps to garbage it never
            # reads
            last = jnp.take_along_axis(
                logits, jnp.maximum(valid - 1, 0)[:, None, None], axis=1
            )[:, 0]
        if counters:
            # what the model counted on the device this tick rides
            # behind the S tokens: one readback carries both
            sampled = jnp.concatenate(
                [sampled, _counter_sums(vs.get("counters", {}), counters)])
        return vs["cache"], last, sampled, jnp.stack(chains)

    return tick


# -- device-resident multi-step decode (k tokens per dispatch) ---------------
#
# When every occupied slot is DECODING (no chunk dealt, no restores in
# flight, no speculative window, no staged control call), the per-token
# cost of the engine is one host->device dispatch plus one
# device->host readback — the tick body itself is tiny on small models.
# The multi-step tick runs k of those steps inside ONE dispatch via
# lax.scan over the exact k=1 body: per step it samples each row from
# the carried last-token logits (same RNG split, same [1, vocab] call
# shape as _tick_fn — streams stay bit-identical), feeds the sampled
# token with a per-row valid length, and detects EOS / budget
# exhaustion ON DEVICE so stopped rows go quiet (valid 0: no KV write,
# no cursor advance, RNG chain untouched) for the window's remainder.
# The host reads back [S, k] tokens plus per-row emitted counts and
# trims the unread tail exactly like the pipelined loop's late-EOS
# path. A row's post-stop state is unobservable by construction: the
# stop reason that froze it also completes the request at reconcile,
# and admission reseeds the slot's RNG and resets its cursor.


@functools.lru_cache(maxsize=256)
def _multi_tick_fn(layout, cfgs, k, ctx: Optional[_ShardCtx] = None):
    """Compiled k-step decode window: ``lax.scan`` over the
    :func:`_tick_fn` body. The packed control buffer carries, behind
    the layout's head, per-row EOS ids (-1 = none) and emission limits
    ``lim = min(k, remaining)`` (0 = idle row); a row is ALIVE while it
    has neither hit its EOS nor emitted ``lim`` tokens. Alive rows
    advance exactly as k consecutive k=1 ticks would — the EOS token
    itself is fed in its own step, as the sync loop feeds it in its own
    tick — and stopped rows run valid-0 padding (no KV write, no cursor
    advance; under the paged layout the write is steered to the
    reserved trash block). Returns ``[S, k]`` tokens (column-major per
    step; garbage past each row's count, never read) and the per-row
    counts the reconcile trims by."""

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrr",
                       out_kinds="crrrr", donate=(1, 2, 3))
    def tick(params_only, cache, last_logits, rngs, packed):
        recompiles.note(layout.tag("multi_tick"))
        S = rngs.shape[0]
        where, (eos, lim) = layout.unpack(packed, S, ((S,), (S,)))

        def step(carry, _):
            cache, last, rngs, stopped, emitted = carry
            alive = ~stopped & (emitted < lim)
            tok, chains = _sample_rows(cfgs, last, rngs, alive)  # [S]
            valid = alive.astype(jnp.int32)
            inputs = tok[:, None]
            at = where
            if layout.paged:
                # the host's cursors came up once, at the WINDOW'S
                # START: each step writes alive rows at ``lens +
                # emitted``, the device-side mirror of the advance the
                # host makes per dispatch at k=1. It preallocated the
                # worst case at admission (``_blocks_for`` covers
                # prompt + max_new), so a window never allocates;
                # writes past a trimmed row's chain land in the trash
                # block (its table is zero beyond the chain)
                at = {**where, "seq_lens": where["seq_lens"] + emitted}
            logits, vs = layout.dm.apply(
                {**params_only, "cache": cache}, inputs,
                valid_lens=valid, mutable=["cache"], **at,
            )
            last = jnp.where(alive[:, None], logits[:, -1], last)
            stopped = stopped | (alive & (eos >= 0) & (tok == eos))
            return ((vs["cache"], last, jnp.stack(chains), stopped,
                     emitted + valid), tok)

        init = (cache, last_logits, rngs,
                jnp.zeros((S,), bool), jnp.zeros((S,), jnp.int32))
        (cache, last, rngs, _, counts), toks = jax.lax.scan(
            step, init, None, length=k)
        return cache, last, toks.T, counts, rngs

    return tick


# -- speculative decoding (draft-assisted verify ticks) ----------------------
#
# A speculative tick generalizes the mixed tick's per-row roles into one
# (n_forced, valid) pair per row: the row feeds `n_forced` tokens
# unconditionally (its PENDING token — emitted last tick but not yet in
# the cache — or a prompt chunk), plus `valid - n_forced` draft tokens
# that must survive rejection sampling. With full = concat(last_logits,
# window logits), window token j's target distribution is uniformly
# full[:, j], so one accept rule covers every role:
#
#   idle row          n_forced=0 valid=0   nothing fed, nothing emitted
#   prefill chunk     n_forced=C valid=C   no tests, no z (chunk tick)
#   transition row    n_forced=0 valid=0   z ~ full[:,0]=last_logits —
#                     the row's first decode token, emitted UNFED
#   speculating row   n_forced=1 valid=1+w pending fed, w drafts tested
#
# Every sampling row emits its accepted drafts plus ONE extra token z ~
# full[:, n_forced + accepted] (the rejection-sampling residual when a
# draft was rejected, the bonus distribution when all survived), and z
# is never fed — it becomes next tick's host-known pending token, which
# is what lets the host (or the draft model) propose the next window
# before the dispatch. Greedy rows accept a draft iff it IS the argmax,
# so greedy streams are the non-speculative engine's wherever a window
# position's logits are the single tick's bit for bit (float32; in
# bfloat16 they differ by rounding and the streams part at near-ties);
# sampled rows are distributionally exact by the standard
# rejection-sampling argument (Leviathan et al.). Rollback of rejected
# suffixes is a cursor rewind only — rejected K/V bytes sit beyond the
# rewound cursor, where the next tick's writes land before any query
# can reach them (the same invariant _reset_slot_cursors relies on),
# and verify windows never write outside the row's admitted region
# (window width <= remaining tokens <= the preallocated block chain).


def _rewind_cursors(cache, rewind):
    """Subtract ``rewind`` [S] from every per-row cursor leaf (the [S]
    int32 vectors: cache_index per layer, pos_index) — the rejected-
    suffix rollback for the slot layout, and the draft cache's overshoot
    undo. Runs inside the jitted bodies."""
    return jax.tree.map(
        lambda c: c - rewind if (c.ndim == 1 and c.dtype == jnp.int32)
        else c, cache
    )


@jax.named_scope("sample")
def _spec_accept(cfgs, k, onehot_q, full, rngs, valid, n_forced,
                 sample_mask, draft_toks, q_probs):
    """Rejection-sampling core of the verify tick (traced).

    ``full`` [S, W+1, V]: position j is the target's filtered-sampling
    source for window token j (j=0 is the pre-window ``last_logits``).
    Per row: accept the longest draft prefix where each draft d_i
    survives ``u < min(1, p_i(d_i)/q_i(d_i))`` (greedy: ``d_i ==
    argmax p_i``), then sample the extra token z from the residual
    ``norm(max(p - q, 0))`` at the first rejection — or from the full
    target distribution when every draft survived (the bonus token).
    ``onehot_q`` marks a deterministic drafter (the n-gram fallback):
    q is one-hot at the proposal, so the accept ratio is just p(d) and
    the residual is p with the rejected token zeroed. The accept
    draws and z ride ONE split of the row's RNG chain, advanced only
    for rows that actually sampled (``sample_mask``) — prefill/idle
    rows keep their chains untouched.

    The greedy rows are taken TOGETHER, in one batched comparison (a
    loop a row unrolled ``S`` argmaxes over the vocabulary into the
    program: 24 s of compile at 64 slots of a 150 k vocabulary); the
    rows that sample go through :func:`_accept_sampled_row` one by one.

    Returns ``(out_toks [S, k+1], acc [S], new_last [S, V],
    new_rngs)``: out_toks rows are [accepted drafts..., z, 0 pad];
    new_last is uniformly ``full[s, n_forced + acc]`` — for prefill
    rows (acc 0, n_forced = valid) that is exactly the
    logits-at-last-valid-token rule of the mixed tick."""
    S = len(cfgs)
    cols = jnp.arange(k)[None]
    n_draft = valid - n_forced
    at = n_forced[:, None] + cols  # [S, k]: window position of draft i
    # (a position past the window is masked by n_draft)
    best = jnp.argmax(jnp.take_along_axis(full, at[..., None], axis=1),
                      axis=-1).astype(jnp.int32)
    ok = (draft_toks == best) & (cols < n_draft[:, None])
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    new_last = jnp.take_along_axis(
        full, (n_forced + acc)[:, None, None], axis=1)[:, 0]
    z = jnp.argmax(new_last, axis=-1).astype(jnp.int32)
    chains = jax.vmap(jax.random.split)(rngs)  # [S, 2]: the chain, the draw
    for s, cfg in enumerate(cfgs):
        if cfg[0] == 0.0:
            continue
        row = _accept_sampled_row(
            cfg, k, full[s], chains[s, 1], n_draft[s], n_forced[s],
            draft_toks[s], None if onehot_q else q_probs[s])
        acc, z, new_last = (held.at[s].set(new) for held, new in zip(
            (acc, z, new_last), row))
    pos = jnp.arange(k + 1)[None]
    out = jnp.where(
        pos < acc[:, None],
        jnp.concatenate([draft_toks, jnp.zeros((S, 1), jnp.int32)], axis=1),
        jnp.where(pos == acc[:, None], z[:, None], 0))
    return out, acc, new_last, jnp.where(sample_mask[:, None],
                                         chains[:, 0], rngs)


def _accept_sampled_row(cfg, k, full, key, n_draft, n_forced, d, q):
    """One sampling row of :func:`_spec_accept`: ``full`` [W+1, V], its
    drafts ``d`` [k] and their distributions ``q`` [k, V] (``None``: a
    deterministic drafter). Returns ``(acc, z, z_logits)``."""
    temp, top_k, top_p = cfg
    V = full.shape[-1]
    u_key, z_key = jax.random.split(key)
    # (a position past the window is clipped, and masked by n_draft)
    pd = jnp.take(full, n_forced + jnp.arange(k), axis=0)
    p_prob = jax.nn.softmax(filter_logits(pd, temp, top_k, top_p), axis=-1)
    ratio = jnp.take_along_axis(p_prob, d[:, None], axis=-1)[:, 0]
    if q is not None:
        ratio = ratio / jnp.maximum(
            jnp.take_along_axis(q, d[:, None], axis=-1)[:, 0], 1e-30)
    ok = jax.random.uniform(u_key, (k,)) < jnp.minimum(ratio, 1.0)
    ok = ok & (jnp.arange(k) < n_draft)
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
    z_logits = jnp.take(full, n_forced + acc, axis=0)
    p_z = jax.nn.softmax(filter_logits(z_logits, temp, top_k, top_p))
    a_clip = jnp.minimum(acc, k - 1)  # the first-rejected draft
    q_z = (jax.nn.one_hot(jnp.take(d, a_clip), V, dtype=p_z.dtype)
           if q is None else jnp.take(q, a_clip, axis=0))
    dist = jnp.where(acc >= n_draft, p_z, jnp.maximum(p_z - q_z, 0.0))
    tot = jnp.sum(dist)
    # p == q exactly makes the residual vanish; rejection then had
    # probability 0, so the fallback is never drawn — it only keeps the
    # categorical finite
    dist = jnp.where(tot > 0, dist / jnp.maximum(tot, 1e-30), p_z)
    z = jax.random.categorical(
        z_key, jnp.log(jnp.maximum(dist, 1e-38))).astype(jnp.int32)
    return acc, z, z_logits


def _merge_drafts(fed, valid, n_forced, draft_toks, k):
    """Scatter each row's draft tokens into its window columns
    ``n_forced .. valid-1`` (device-side: a model drafter's proposals
    never round-trip the host). Forced columns and prefill chunks stay
    as the host built them."""
    cols = jnp.arange(fed.shape[1])[None, :]
    di = cols - n_forced[:, None]
    return jnp.where(
        (di >= 0) & (cols < valid[:, None]),
        jnp.take_along_axis(draft_toks, jnp.clip(di, 0, k - 1), axis=1),
        fed,
    )


@functools.lru_cache(maxsize=256)
def _spec_verify_fn(layout, cfgs, W, k, onehot_q,
                    ctx: Optional[_ShardCtx] = None):
    """Compiled speculative verify tick: ONE ``[S, W]`` dispatch writes
    every row's window K/V at its absolute positions (the chunked mixed
    tick's valid_lens machinery verbatim; through each row's block
    table under the paged layout), scores all window positions, runs
    per-row rejection sampling (:func:`_spec_accept`), and rolls the
    rejected suffixes back — acceptance-length variation changes only
    traced values, never shapes, so steady state stays at zero
    recompiles. Under a mesh ``ctx`` the body runs per head-shard with
    sampling on replicated logits, like every other tick. Host int
    controls (the layout's head, then fed, valid, n_forced, sample
    mask) ride one packed transfer; ``draft_toks`` stays a separate arg
    because a model drafter's proposals are already device-resident."""

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrrrr",
                       out_kinds="crrrr", donate=(1, 2, 3))
    def tick(params_only, cache, last_logits, rngs, packed, draft_toks,
             q_probs):
        recompiles.note(layout.tag("spec_tick"))
        S = rngs.shape[0]
        where, (fed, valid, n_forced, smask) = layout.unpack(
            packed, S, ((S, W), (S,), (S,), (S,)))
        sample_mask = smask != 0
        merged = _merge_drafts(fed, valid, n_forced, draft_toks, k)
        logits, vs = layout.dm.apply(
            {**params_only, "cache": cache}, merged,
            valid_lens=valid, mutable=["cache"], **where,
        )
        full = jnp.concatenate(
            [last_logits[:, None], logits.astype(jnp.float32)], axis=1)
        out_toks, acc, new_last, new_rngs = _spec_accept(
            cfgs, k, onehot_q, full, rngs, valid, n_forced,
            sample_mask, draft_toks, q_probs)
        new_cache = vs["cache"]
        if not layout.paged:
            # the slot cursors are in the cache: rewind them past the
            # rejected suffixes in the same dispatch. The paged cursors
            # are the host's, so there is no rollback here at all: the
            # engine advances each row by ``n_forced + acc`` instead of
            # ``valid`` when it reads ``acc`` back, and rejected-draft
            # bytes sit in row-private blocks beyond the cursor
            # (windows never reach shared prefix blocks: those end
            # before the row's write region by the COW-at-admission
            # invariant, and never past the chain: window width <=
            # remaining <= the preallocated worst case — so rollback
            # touches no block refcounts)
            new_cache = _rewind_cursors(new_cache,
                                        valid - (n_forced + acc))
        return new_cache, new_last, out_toks, acc, new_rngs

    return tick


def _draft_rows(cfgs, logits, rngs, advance):
    """One proposal per slot from the drafter's ``[S, vocab]``
    ``logits`` (traced): a greedy row its best token, any other a draw
    from its own filtered distribution, which is the ``q`` the verify
    tick's accept ratio divides by (zeros for a greedy row: nothing
    reads them). The draft chains are apart from the emission chains
    and move only where ``advance`` [S]. Returns ``(tokens [S], q [S,
    vocab], chains [S, 2])``."""
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    q = jnp.zeros(logits.shape, jnp.float32)
    for s, (temp, top_k, top_p) in enumerate(cfgs):
        if temp == 0.0:
            continue
        rng, sub = jax.random.split(rngs[s])
        f = filter_logits(logits[s], temp, top_k, top_p)
        toks = toks.at[s].set(
            jax.random.categorical(sub, f).astype(jnp.int32))
        q = q.at[s].set(jax.nn.softmax(f))
        rngs = rngs.at[s].set(jnp.where(advance[s], rng, rngs[s]))
    return toks, q, rngs


@functools.lru_cache(maxsize=256)
def _mtp_verify_fn(layout, cfgs, W, ctx: Optional[_ShardCtx] = None,
                   live: Optional[int] = None):
    """Compiled verify tick of a model that drafts with ITS OWN
    multi-token-prediction module (``draft="mtp"``; one draft a row a
    tick): :func:`_spec_verify_fn`'s window, acceptance and rewind, with
    three differences the module forces.

    **The drafter runs here, behind the acceptance.** The module's row
    at position ``t`` reads the main model's hidden state at ``t``
    beside the embedding of the token at ``t + 1``, and which token that
    is at a window's last kept position is known only once the window
    is accepted. So the one dispatch is: the main layers over the ``[S,
    W]`` window (``dm.apply(..., head_at=)``: the hidden states of every
    row, the logits at the window's ``2`` positions alone: a chunk's
    64 positions of a 150 k vocabulary are never multiplied),
    :func:`_spec_accept`, then ``dm.draft`` over the same positions with
    each one's next token (the window shifted left; at the last kept
    position the token just sampled, or for a prompt chunk the prompt's
    next token, which the host sends), then ONE rewind of every cursor,
    the module's cache leaf among them, past the rejected draft. The
    module's logits at the last kept position give the next tick's
    draft.

    **Its state stays on the device**: ``state`` = the row's pending
    token (sampled last tick, not yet in the cache), its draft, the
    draft's distribution (for a sampled row's accept ratio) and the
    draft chains. The next tick's window is built from them here
    (``use_pending`` says for which rows), so no token crosses the host
    between an acceptance and the next draft.

    **A prompt's last chunk samples the row's first token** (the host
    sets its ``sample_mask``) in place of a transition tick: the module
    needs that token beside the chunk's last hidden state.

    ``live`` packs the per-token layers as in :func:`_mixed_tick_fn`.
    The model's ``tick_counters`` ride behind the accepted lengths,
    ``[S + len(counters)]``."""
    counters = tuple(getattr(layout.dm, "tick_counters", ()))
    mutable = ["cache", "counters"] if counters else ["cache"]

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrrr",
                       out_kinds="crrrrr", donate=(1, 2, 3))
    def tick(params_only, cache, last_logits, rngs, packed, state):
        recompiles.note(layout.tag("mtp_tick"))
        pending, draft_toks, q_probs, draft_rngs = state
        S = rngs.shape[0]
        where, (fed, valid, n_forced, smask, next_tok, use_pending) = (
            layout.unpack(packed, S,
                          ((S, W), (S,), (S,), (S,), (S,), (S,))))
        if live is not None:
            where = {**where, "live_tokens": live}
        sample_mask = smask != 0
        fed = fed.at[:, 0].set(
            jnp.where(use_pending != 0, pending, fed[:, 0]))
        merged = _merge_drafts(fed, valid, n_forced, draft_toks, 1)
        # the window's two positions: the last forced token's column and
        # the draft's (a row that feeds nothing reads neither)
        head_at = jnp.clip(n_forced[:, None] - 1 + jnp.arange(2)[None],
                           0, W - 1)
        (hidden, logits), vs = layout.dm.apply(
            {**params_only, "cache": cache}, merged, valid_lens=valid,
            head_at=head_at, mutable=mutable, **where)
        # _spec_accept reads window position j at full[:, j]: here that
        # is last_logits, then the two head positions, so a row that
        # forced any tokens counts as having forced one
        forced = jnp.minimum(n_forced, 1)
        out_toks, acc, new_last, new_rngs = _spec_accept(
            cfgs, 1, False,
            jnp.concatenate([last_logits[:, None],
                             logits.astype(jnp.float32)], axis=1),
            rngs, valid - n_forced + forced, forced, sample_mask,
            draft_toks, q_probs)
        kept = n_forced + acc
        z = jnp.take_along_axis(out_toks, acc[:, None], axis=1)[:, 0]
        after = jnp.where(sample_mask, z, next_tok)
        following = jnp.where(
            jnp.arange(W)[None] == kept[:, None] - 1, after[:, None],
            jnp.roll(merged, -1, axis=1))
        drafted, vs2 = layout.dm.apply(
            {**params_only, "cache": vs["cache"]}, hidden, following,
            valid_lens=valid, draft_at=jnp.maximum(kept - 1, 0),
            mutable=mutable, method="draft", **where)
        new_cache = _rewind_cursors(vs2["cache"], valid - kept)
        tok, q, draft_rngs = _draft_rows(
            cfgs, drafted.astype(jnp.float32), draft_rngs, sample_mask)
        # a row that sampled nothing (a chunk mid-prompt, a held row)
        # keeps the pending token and the draft it had
        state = (jnp.where(sample_mask, z, pending),
                 jnp.where(sample_mask[:, None], tok[:, None], draft_toks),
                 jnp.where(sample_mask[:, None, None], q[:, None], q_probs),
                 draft_rngs)
        if counters:
            acc = jnp.concatenate([acc, sum(
                _counter_sums(v.get("counters", {}), counters)
                for v in (vs, vs2))])
        return new_cache, new_last, out_toks, acc, state, new_rngs

    return tick


@functools.lru_cache(maxsize=64)
def _draft_feed_fn(dm_draft, ctx: Optional[_ShardCtx] = None):
    """Compiled draft-cache catch-up feed: one ``[S, Wd]`` valid_lens
    dispatch that (1) rewinds each row's draft cursors past last
    tick's rejected proposals, then (2) feeds each row's queue of true
    tokens the draft hasn't consumed yet — prompt chunks during
    prefill, the 1-2 tokens emitted-since-last-draft in steady state —
    and returns the logits at each row's last valid token (the
    distribution the first proposal samples from)."""

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrr",
                       out_kinds="cr", donate=(1,))
    def feed(draft_params, cache, fed, valid, rewind):
        recompiles.note("serve.draft_feed")
        cache = _rewind_cursors(cache, rewind)
        logits, vs = dm_draft.apply(
            {**draft_params, "cache": cache}, fed,
            valid_lens=valid, mutable=["cache"],
        )
        last = jnp.take_along_axis(
            logits, jnp.maximum(valid - 1, 0)[:, None, None], axis=1
        )[:, 0]
        return vs["cache"], last.astype(jnp.float32)

    return feed


@functools.lru_cache(maxsize=256)
def _draft_step_fn(dm_draft, cfgs, ctx: Optional[_ShardCtx] = None):
    """Compiled draft proposal step: sample one proposal per row from
    the incoming draft logits (each row's own sampling config — the
    proposal distribution q must be the draft's *filtered* softmax,
    because that q enters the verify tick's accept ratio), feed the
    proposals back into the draft cache (``feed_valid`` 0 on the last
    step: the k-th proposal is never fed), and return the next logits
    plus the proposal tokens and their full q distributions. Draft
    RNG chains are separate from the engine's emission chains and
    advance only for speculating rows."""

    @functools.partial(_compile, ctx=ctx, in_kinds="pcrrrr",
                       out_kinds="crrrr", donate=(1, 2, 3))
    def step(draft_params, cache, logits_in, rngs, feed_valid,
             spec_mask):
        recompiles.note("serve.draft_step")
        V = logits_in.shape[-1]
        toks, qs, new_rngs = [], [], []
        for s, (temp, top_k, top_p) in enumerate(cfgs):
            if temp == 0.0:
                tok = jnp.argmax(logits_in[s]).astype(jnp.int32)
                # greedy q is a formality (the verify tick's greedy
                # branch never reads it); the chain stays untouched
                qs.append(jax.nn.one_hot(tok, V, dtype=jnp.float32))
                new_rngs.append(rngs[s])
            else:
                rng, sub = jax.random.split(rngs[s])
                f = filter_logits(logits_in[s], temp, top_k, top_p)
                tok = jax.random.categorical(sub, f).astype(jnp.int32)
                qs.append(jax.nn.softmax(f))
                new_rngs.append(jnp.where(spec_mask[s], rng, rngs[s]))
            toks.append(tok)
        tok = jnp.stack(toks)
        logits, vs = dm_draft.apply(
            {**draft_params, "cache": cache}, tok[:, None],
            valid_lens=feed_valid, mutable=["cache"],
        )
        return (vs["cache"], logits[:, 0].astype(jnp.float32), tok,
                jnp.stack(qs), jnp.stack(new_rngs))

    return step


def _ngram_propose(history: np.ndarray, k: int, max_n: int = 3):
    """Self-speculative n-gram drafter (host-side, no second model):
    match the stream's suffix n-gram (n from ``max_n`` down to 1)
    against its most recent earlier occurrence in ``history`` (prompt +
    emitted tokens) and propose the k tokens that followed it. Overlap
    with the suffix itself is allowed — a stream stuck on one token
    matches at distance 1 and proposes the repeat, the common case
    that makes greedy loops nearly free. Returns ``(proposal [k]
    int32, found)``; found 0 means no match (the row decodes plain
    this tick)."""
    L = int(history.size)
    for n in range(min(max_n, L - 1), 0, -1):
        # candidate starts 0 .. L-n-1: strictly before the suffix, with
        # at least one continuation token inside history
        hay = history[:L - 1]
        if hay.size < n:
            continue
        windows = np.lib.stride_tricks.sliding_window_view(hay, n)
        hits = np.nonzero((windows == history[L - n:]).all(axis=1))[0]
        if hits.size:
            start = int(hits[-1])
            # continuation read from the stream EXTENDED BY THE PROPOSAL
            # itself: once the read index crosses the end of history it
            # lands on an already-proposed token, i.e. the periodic
            # extension of the matched cycle — a repeat-token stream
            # (distance-1 match) proposes k repeats, not one
            ext = history.tolist()
            out = np.empty(k, np.int32)
            for i in range(k):
                t = int(ext[start + n + i])
                out[i] = t
                ext.append(t)
            return out, k
    return np.zeros(k, np.int32), 0


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset_slot_cursors(cache, slot):
    """Park slot ``slot`` at depth 0 for its next tenant: the [S]
    cursor vectors (cache_index per layer, pos_index) zero out; the K/V
    slabs stay — every position a new request attends is rewritten by
    its own chunks before any query can reach it (causal mask at the
    row's own cursor), so stale bytes beyond the cursor are
    unreachable."""
    recompiles.note("serve.reset_cursors")
    return _parked(cache, slot)


def _parked(cache, slot):
    return jax.tree.map(
        lambda c: c.at[slot].set(0) if c.ndim == 1 else c, cache
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _seed_slot(rngs, slot, seed):
    """Start slot ``slot``'s RNG chain at ``PRNGKey(seed)``: the key is
    made and written in one program, so an admission costs the engine
    thread one enqueue behind the tick in flight and not three."""
    recompiles.note("serve.seed_slot")
    return rngs.at[slot].set(jax.random.PRNGKey(seed))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _enter_slot(cache, rngs, slot, seed):
    """A slot-cache admission's whole device side as one program:
    :func:`_reset_slot_cursors` and :func:`_seed_slot` behind each
    other. ``slot`` and ``seed`` come as host scalars and ride the
    call, so nothing is uploaded ahead of it."""
    recompiles.note("serve.enter_slot")
    return (_parked(cache, slot),
            rngs.at[slot].set(jax.random.PRNGKey(seed)))


@functools.lru_cache(maxsize=32)
def _gather_block_fn(blk_leaf_idx):
    """Compiled block gather for demotion: slice one physical block's
    rows out of every block-major paged cache leaf (K, V, int8 scales).
    ``blk_leaf_idx`` is the tuple of flattened-leaf indices whose
    leading axis is the block axis — precomputed once per engine so the
    traced body carries no shape probing. NOT donated: the cache must
    survive (the block's contents are being copied out, not moved).
    Under a mesh the leaves arrive sharded along the KV-head axis; the
    host-side ``np.asarray`` of the outputs assembles the GLOBAL view,
    so the host tier always stores unsharded blocks (mesh-agnostic —
    the restore upload re-shards onto whatever mesh is current)."""

    @jax.jit
    def gather(cache, blk):
        recompiles.note("serve.gather_block")
        leaves = jax.tree.leaves(cache)
        return [leaves[i][blk] for i in blk_leaf_idx]

    return gather


@functools.lru_cache(maxsize=32)
def _restore_blocks_fn(blk_leaf_idx):
    """Compiled batched restore upload: scatter up to ``R`` demoted
    blocks' host contents into their destination blocks across every
    block-major cache leaf. ``R`` is the scheduler's ``restore_budget``
    (a fixed compiled width — short batches pad with destination 0, the
    reserved trash block, so restore count variation never recompiles).
    One dispatch per tick, issued from the plan body BEFORE the tick's
    compute: the upload is asynchronous and overlaps whatever is still
    in flight, and the cache data dependency guarantees every later
    tick observes the restored bytes — no explicit completion sync.
    Unsharded host arrays re-shard onto the cache's sharding here (the
    TP reshard-on-upload path)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def restore(cache, stacked, dsts):
        recompiles.note("serve.restore_blocks")
        leaves, treedef = jax.tree.flatten(cache)
        for j, i in enumerate(blk_leaf_idx):
            leaves[i] = leaves[i].at[dsts].set(
                stacked[j].astype(leaves[i].dtype)
            )
        return jax.tree.unflatten(treedef, leaves)

    return restore


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_block(cache, src, dst):
    """Copy-on-write: duplicate physical block ``src`` into ``dst``
    across every paged cache leaf (K, V, int8 scales — all block-major),
    so a sequence that diverges mid-block writes into its own copy and
    the shared original stays immutable."""
    recompiles.note("serve.copy_block")
    return jax.tree.map(lambda c: c.at[dst].set(c[src]), cache)


_IDLE_CFG = (0.0, None, None)  # free slots sample greedily into the void


@dataclass
class _SlotState:
    req: Request
    remaining: int
    blocks: Optional[List[int]] = None  # paged: this row's block chain
    cached_tokens: int = 0  # paged: prompt tokens served from the index
    # chunked prefill: prompt tokens not yet fed through a mixed tick
    # (None = monolithic mode, already prefilled). A slot is PREFILLING
    # while decoding is False and DECODING after its last chunk landed.
    pending: Optional[np.ndarray] = None
    decoding: bool = True
    # tiered KV cache: (host handle, prompt-token offset) pairs this
    # row still waits on — non-None marks the RESTORING state: the row
    # holds its slot and chain but ticks over it idle (valid 0, RNG
    # untouched, NO token-budget charge) until the engine's batched
    # restore uploads land, then flips to PREFILLING and streams its
    # uncached suffix like any other admission
    restoring: Optional[List[tuple]] = None
    # tokens of this row the cache holds once every dispatched tick has
    # run: advanced when a tick is planned (chunked engines; the mixed
    # tick's work counters read it)
    cursor: int = 0
    # tokens the dispatched-but-unread ticks sample for this row: the
    # loop that runs a tick ahead plans against :attr:`unplanned`, so a
    # row whose budget runs out in an unread tick is not fed again (a
    # length finish is host-known; only an EOS finish overruns)
    inflight: int = 0
    admit_seq: int = 0  # admission order: prefill budget is dealt FIFO
    admit_t: float = 0.0  # monotonic admission time (prefill span)
    # speculative decoding (engine.spec): the row's emitted-but-unfed
    # token (None until the transition tick samples the first one; with
    # draft="mtp" the device holds it, and -1 here says so until the
    # tick that sampled it is read), the
    # prompt+emitted history the n-gram drafter matches against, the
    # queue of true tokens the draft model hasn't consumed yet, and the
    # draft-cursor overshoot (rejected proposals) to rewind at its next
    # feed
    pending_tok: Optional[int] = None
    history: Optional[np.ndarray] = None
    draft_queue: Optional[np.ndarray] = None
    draft_rewind: int = 0

    @property
    def unplanned(self) -> int:
        """Tokens of the row's budget that no dispatched tick samples
        yet: what the next plan may still deal it."""
        return self.remaining - self.inflight


@dataclass
class _InflightTick:
    """One dispatched-but-unread tick: the device-side token refs plus
    the host plan that produced them. Sync mode reconciles the record
    immediately after dispatch; the pipelined loop holds exactly one
    while the NEXT tick is planned and dispatched, so host planning and
    token streaming for tick N overlap device compute of tick N+1.
    ``rows`` pins the exact :class:`_SlotState` each row was planned
    against — reconciliation drops a row's token when the slot no
    longer holds that state (the request finished in an
    earlier-reconciled tick after this one was optimistically
    dispatched: the late-EOS overrun, never emitted). Only tick
    OUTPUTS are held here; the donated inputs (cache/logits/rngs) were
    rebound by the dispatch statement and must never be parked on a
    record that outlives the step (the donation-safety pass checks
    this handoff)."""

    toks: Any                       # device [S] ([S, k+1] spec, [S, k] multi)
    # per slot: None (idle at plan) | ("dec", st, n) — n tokens sampled
    # for the row, 1 outside a multi-step window | ("pre", st, take,
    # flipped) — flipped marks the prompt's last chunk landing
    rows: List[Optional[tuple]]
    tick: int                       # the number this tick reconciles as
    plan_ms: float
    upload_ms: float                # control-buffer transfer (or reuse)
    dispatch_ms: float              # upload + the jitted call returning
    n_dec: int
    fed_tokens: int
    chunk: Optional[int]
    # mixed ticks: what the dispatch computes and copies in against
    # what was dealt (attended_tokens, key_positions,
    # key_positions_fetched, cache_positions, query_positions,
    # attend_query_positions; see the plan in _plan_dispatch_mixed)
    work: Optional[dict] = None
    # multi-step decode: the window width this record dispatched (None
    # = ordinary one-token tick); ``acc`` doubles as its device [S]
    # per-row emitted counts
    multi_k: Optional[int] = None
    # speculative extras (the n-gram and draft-model drafters run a
    # depth-1 pipeline: emissions defer, plans don't; draft="mtp" runs
    # a tick ahead like a non-speculative engine)
    acc: Any = None                 # device [S] accepted-prefix lengths
    n_forced: Optional[np.ndarray] = None
    granted: Optional[np.ndarray] = None
    spec_set: Optional[set] = None
    # the device clock's stamps (:class:`_DeviceClock`, perf_counter
    # seconds): the jitted call began and returned, the read began, and
    # the last moment the tokens were seen not ready / the first they
    # were seen ready (``ready_hi`` None until then; a read that blocked
    # sets both ends to the moment it returned)
    program: str = "decode"         # "decode" | "mixed" | "multi" | "spec"
    dispatching_t: float = 0.0
    dispatched_t: float = 0.0
    read_t: float = 0.0
    ready_lo: float = 0.0
    ready_hi: Optional[float] = None
    dozed: bool = False             # an idle phase since the dispatch before
    epoch: int = 0                  # the clock's mark this tick counts under
    first: bool = False             # the first dispatch after a mark


class _Phase:
    """One open bracket of :class:`_PhaseClock`; ``ms`` after exit."""

    __slots__ = ("_clock", "_name", "_span", "_t0", "ms")

    def __init__(self, clock, name, args):
        self._clock, self._name = clock, name
        self._span = jax.profiler.TraceAnnotation(clock.prefix + name, **args)
        self.ms = 0.0

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._span.__exit__(*exc)
        clock = self._clock
        acc = clock.acc
        acc[self._name] = acc.get(self._name, 0.0) + self.ms
        clock.boundary(self._name)
        return False


class _PhaseClock:
    """The one bracket the engine thread times its phases with:
    ``with clock("plan", tick=n) as ph`` opens a
    ``jax.profiler.TraceAnnotation("engine.plan", tick=n)`` — a span on
    the engine thread's line of a profile, on the device trace's clock,
    and nothing at all while no profiler session runs — and adds the
    bracket's milliseconds (``ph.ms``) to the current period. A period
    runs from the end of one tick's ``record`` to the end of the next
    one's; :meth:`take` closes it and hands out what each phase took
    in it, with the period's own length as ``loop``."""

    def __init__(self, prefix: str, boundary):
        self.prefix = prefix
        self.acc: dict = {}
        # called with the phase's name as each bracket closes: where the
        # device clock probes the unread tick
        self.boundary = boundary
        self._period_t0 = time.perf_counter()

    def __call__(self, name: str, **args) -> _Phase:
        return _Phase(self, name, args)

    def take(self) -> dict:
        now = time.perf_counter()
        out, self.acc = self.acc, {}
        out["loop"] = (now - self._period_t0) * 1e3
        self._period_t0 = now
        return out


class _ClockTotals(NamedTuple):
    """What :class:`_DeviceClock` has summed since its mark
    (milliseconds; ``by_program``: program -> (sum of tick ms, ticks),
    a new dict a tick, never written to once it is here)."""

    busy_ms: float = 0.0
    starved_ms: float = 0.0
    unasked_ms: float = 0.0
    err_ms: float = 0.0
    ticks: int = 0
    exact: int = 0
    by_program: dict = {}
    # the first counted tick's start and the last one's read, seconds on
    # the clock: the span the three sums add up to
    origin_t: Optional[float] = None
    last_t: Optional[float] = None


class _DeviceClock:
    """The device's time a tick, with no profiler running: when each
    tick's tokens became ready (the tokens of a TPU program become
    ready when the program ends), from one non-blocking ``is_ready()``
    at every phase boundary of the engine thread while a tick is unread
    and not yet seen ready, or from the read itself where it blocked.

    Per tick N, once read: ``start = max(dispatching_t(N),
    ready(N-1))``, ``device_tick_ms = ready(N) - start``, and the gap
    ``max(0, dispatching_t(N) - ready(N-1))`` in which the device had
    nothing queued is ``device_starved_ms`` -- or ``device_unasked_ms``
    where the loop dozed in it (an ``idle`` phase: nothing to run). The
    hand-over is stamped where the jitted call BEGINS: the call
    enqueues the program at its head (an idle v5e took a tick up 5-19 %
    into the ``engine.dispatch`` span) and beside a server's threads
    returns 4-10 ms later, when it has the interpreter lock back, by
    which time the tick may have ended. A readiness seen at a boundary
    is known to the interval between that boundary and the one before;
    the values use its midpoint and ``device_clock_err_ms`` is half the
    widths of the two intervals used, and, where the device was free
    before the call returned, what of the call (``dispatching_t`` to
    ``dispatched_t``) it was free for: the start lies somewhere in
    that. 0 is ``exact``: both reads blocked and the device was still
    busy when the call returned. The alternating loop gets every host
    millisecond between two ticks as starved, which is what it does.

    Pure arithmetic over the stamps on :class:`_InflightTick` (anything
    with ``toks.is_ready()`` and those fields does), the clock handed
    in: a test drives it with a made-up one. Everything but
    :meth:`mark` and :meth:`stats` belongs to the engine thread."""

    ZERO = _ClockTotals()

    def __init__(self, now=time.perf_counter):
        self._now = now
        self._unread: deque = deque()   # dispatched, not yet read
        self._prev: Optional[tuple] = None  # (lo, hi) of the last read tick
        self._dozed = False
        self._began = 0.0               # the dispatch call under way
        self._epoch = 0
        self._mark_asked = False
        self._totals = self.ZERO

    # -- stamps -------------------------------------------------------------

    def boundary(self, phase: str):
        """A phase of the loop closed: note a doze, and probe the oldest
        unread tick not yet seen ready (ticks end in order: where that
        one is not ready, none after it is)."""
        if phase == "idle":
            self._dozed = True
        for rec in self._unread:
            if rec.ready_hi is None:
                t = self._now()
                if rec.toks.is_ready():
                    rec.ready_hi = self._now()
                else:
                    rec.ready_lo = t
                return

    def dispatching(self):
        """The jitted call of the next tick begins."""
        self._began = self._now()

    def dispatched(self, rec):
        """The jitted call of ``rec`` has returned."""
        rec.dispatching_t = rec.ready_lo = self._began
        rec.dispatched_t = self._now()
        rec.dozed, self._dozed = self._dozed, False
        if self._mark_asked:
            # the mark takes effect here, on the engine thread: ticks
            # dispatched from now on count, the tick in flight does not
            self._epoch += 1
            self._totals = self.ZERO
            self._mark_asked = False
            rec.first = True
        rec.epoch = self._epoch
        self._unread.append(rec)

    def read_begins(self, rec):
        """The ``wait`` phase begins: a last probe says whether the read
        will block."""
        rec.read_t = self._now()
        if rec.ready_hi is None:
            if rec.toks.is_ready():
                rec.ready_hi = self._now()
            else:
                rec.ready_lo = rec.read_t

    def read_ends(self, rec) -> tuple:
        """The tokens of ``rec`` are on the host. Returns
        ``(device_tick_ms, device_starved_ms, device_unasked_ms,
        device_clock_err_ms)`` and adds them to the totals."""
        if rec.ready_hi is None:        # the read blocked: ready now
            rec.ready_lo = rec.ready_hi = self._now()
        self._unread.remove(rec)
        lo, hi = rec.ready_lo, rec.ready_hi
        prev, self._prev = self._prev, (lo, hi)
        d = rec.dispatching_t
        if prev is None:
            start, gap, err = d, 0.0, 0.0
        else:
            # N ends after N-1 does: what N-1 was last seen not ready
            # at bounds N from below too
            lo = max(lo, prev[0])
            before = (prev[0] + prev[1]) / 2
            start, gap = max(d, before), max(0.0, d - before)
            err = (prev[1] - prev[0]) / 2
        # ... plus what of the call the device was free for
        err += (hi - lo) / 2 + max(0.0, rec.dispatched_t - start)
        tick = max(0.0, (lo + hi) / 2 - start)
        out = (tick * 1e3, 0.0 if rec.dozed else gap * 1e3,
               gap * 1e3 if rec.dozed else 0.0, err * 1e3)
        if rec.epoch == self._epoch and not self._mark_asked:
            self._add(rec, out, start, hi)
        return out

    def _add(self, rec, out, start, hi):
        tick, starved, unasked, err = out
        t = self._totals
        origin = t.origin_t
        if rec.first or origin is None:
            # the sums run from the first dispatch after the mark (or
            # the end of the tick the device was still running then):
            # the gap before it is the time before the mark's
            origin, starved, unasked = start, 0.0, 0.0
        by = dict(t.by_program)
        # a verify tick is ``spec`` whether or not a prompt chunk rode
        # in it; those that fed prompt tokens are summed apart as well
        # (the wider program of the two)
        fed = rec.program == "spec" and getattr(rec, "fed_tokens", 0)
        for program in (rec.program, "spec_chunk") if fed else (
                rec.program,):
            total, n = by.get(program, (0.0, 0))
            by[program] = (total + tick, n + 1)
        self._totals = _ClockTotals(
            t.busy_ms + tick, t.starved_ms + starved,
            t.unasked_ms + unasked, t.err_ms + err, t.ticks + 1,
            t.exact + (err == 0.0), by, origin, hi)

    # -- any thread ---------------------------------------------------------

    def mark(self):
        """Count from the next dispatch on (``mark_steady``)."""
        self._mark_asked = True

    def stats(self) -> dict:
        """The ``device_*`` keys of :meth:`ServingEngine.stats`."""
        t = self.ZERO if self._mark_asked else self._totals
        whole = t.busy_ms + t.starved_ms + t.unasked_ms

        def mean(program):
            total, n = t.by_program.get(program, (0.0, 0))
            return total / n if n else None

        return {
            "device_busy_ms": t.busy_ms,
            "device_starved_ms": t.starved_ms,
            "device_unasked_ms": t.unasked_ms,
            "device_clock_err_ms": t.err_ms,
            "device_starved_pct": (100.0 * t.starved_ms / whole
                                   if whole else None),
            "device_unasked_pct": (100.0 * t.unasked_ms / whole
                                   if whole else None),
            **{f"device_{p}_tick_ms": mean(p)
               for p in ("decode", "mixed", "multi", "spec", "spec_chunk")},
            "device_clock_exact_pct": (100.0 * t.exact / t.ticks
                                       if t.ticks else None),
            # first dispatch after the mark to the last read: what the
            # three sums add up to, within device_clock_err_ms
            "device_clock_span_ms": ((t.last_t - t.origin_t) * 1e3
                                     if t.ticks else 0.0),
            "device_clock_ticks": t.ticks,
        }


class ServingEngine:
    """Continuous-batching serving over a fixed slot pool.

    Args:
      model: a TRAINING-mode :class:`TransformerLM` (``decode=False``) —
        decode twins are cloned internally, so trained checkpoints work
        as-is (same param tree) — or another registered LM with a slot
        cache of its own ("Models with another cache" below).
      params: trained variables (``{"params": ...}``). The engine
        holds, for every leaf the model casts to its compute dtype as
        the first thing it does with it (``models/transformer.py ·
        compute_params``: the kernels, not the norms), the result of
        that cast, made once on the device: the tick programs take the
        weights as arguments, and a wide leaf would be read and
        converted every tick. Served tokens are bit for bit those of
        the tree as handed. The engine keeps no reference to a handed
        leaf it replaced: a caller that wants the memory back drops its
        own tree. ``stats()["weight_bytes_held"]`` beside
        ``weight_bytes_handed`` says what it came to. A ``float32``
        model, a model that brings no rule and a tree already in the
        compute dtype are held as handed, leaf objects included.
      slots: number of concurrent sequences ``S`` — the pooled KV cache
        is ``[S, max_len, ...]`` per layer, allocated once.
      max_len: serving context length (prompt + generated); defaults to
        ``model.max_len``. Smaller values shrink the pooled cache.
      scheduler: admission policy; defaults to a
        :class:`FIFOScheduler` with its default backpressure knobs
        (``tick_token_budget`` 256). The budget also bounds what a
        ``[S, C]`` mixed tick multiplies: where it is under ``S x C``
        and the model's MLPs are dense, the tick's per-token layers run
        over its live tokens packed to the one count
        :func:`_packed_count` derives from it, and only the attend spans
        ``S x C``; an engine of 32 slots and long prompts may still
        want ``S x C`` dealt. A ``dict`` is taken as
        :class:`FIFOScheduler`'s arguments, for callers that build the
        engine from a file.
      metrics: a :class:`MetricsWriter` for the per-tick and per-request
        JSONL rows. Omitted, none are written and the engine keeps no
        list that grows with its ticks; :meth:`stats` reads its
        ``ttft_ms`` and ``token_ms`` percentiles off the latest
        ``STATS_RECENT`` observations either way.
      registry: the :class:`~distkeras_tpu.telemetry.MetricRegistry` the
        engine publishes into; defaults to the process-global one. Pass
        a fresh instance to isolate a run (benchmarks, tests).
      tracer: the :class:`~distkeras_tpu.telemetry.Tracer` recording the
        per-request span chain; defaults to the process-global one. The
        scheduler (given or created) is adopted into the same pair so
        trace ids and queue metrics stay coherent.
      paged: replace the contiguous ``[S, max_len, ...]`` slabs with a
        pool of fixed-size KV blocks (``[num_blocks, block_size, ...]``
        per layer) plus per-row block tables — memory committed as
        sequences grow, prompt prefixes shared across requests through
        the radix index (prefill skipped for the shared span,
        copy-on-write at mid-block divergence), and LRU eviction of
        unreferenced cached blocks. Token streams remain bit-identical
        to solo ``generate()`` (tests/test_paged.py parity matrix).
      block_size: tokens per KV block; ``max_len`` must be a multiple.
      num_blocks: physical blocks in the pool (one is the reserved
        trash block). Defaults to worst-case-per-slot + 1; raise it for
        prefix-cache headroom.
      prefix_cache: set False to disable radix prefix sharing (every
        prompt fully prefills; blocks free immediately at finish).
      host_blocks: capacity (in KV blocks) of the host-RAM spill tier
        under the block pool. With a tier, evicting a cached
        unreferenced block DEMOTES its contents to pinned host memory
        (radix node re-keyed ``device -> host``) instead of discarding
        them, and a prefix hit on a demoted entry admits the request
        into a RESTORING slot state: its blocks are uploaded back
        asynchronously from the plan bodies — batched per tick, capped
        by the scheduler's ``restore_budget`` so restores never starve
        decode, overlapped with in-flight device compute — and the row
        flips to PREFILLING (charging the token budget only then) once
        every block is resident. Multiplies effective prefix-cache
        capacity by roughly ``host_blocks / num_blocks`` at fixed
        device memory; token streams stay bit-identical to the
        tier-less engine (restored bytes are the demoted bytes).
        Requires ``paged=True``, ``prefix_cache=True``, and chunked
        prefill. ``None`` (default) disables the tier.
      prefill_chunk: Sarathi-style chunked prefill (the default, C=64):
        an admitted prompt streams into its slot C tokens at a time
        *inside* the decode tick — one fused ``[S, C]`` dispatch
        advances prefilling and decoding rows together, each row at its
        own valid length, so a 2048-token prompt never injects a
        monolithic-prefill stall into live streams. How many prompt
        tokens each tick actually carries is metered by the scheduler's
        ``tick_token_budget`` (decodes reserved first). ``None``
        restores the legacy monolithic whole-prompt B=1 prefill
        dispatch (kept as the bench baseline). Streams are
        bit-identical either way, at any chunk size.
      flight: the black box. ``True`` (default) records one structured
        snapshot per tick (slot states, queue depth, budget split,
        phase-decomposed latency) into a fresh bounded
        :class:`~distkeras_tpu.telemetry.FlightRecorder`; pass a
        recorder to share one, or ``None`` to disable. A crash inside
        :meth:`step` (and a :meth:`watchdog` stall) dumps it to a
        postmortem JSONL that ``report --flight`` renders.
      flight_capacity: ring size in ticks for the engine-owned recorder.
      postmortem_dir: where crash/stall dumps land (default ``/tmp``,
        the path CI uploads on tier-1 failure).
      mesh: a 1-D device mesh (``make_mesh({"model": n})``) to run the
        jitted tick bodies tensor-parallel under ``shard_map``: Q/KV
        projections column-sharded and out-projections row-sharded per
        :func:`~distkeras_tpu.parallel.spmd.lm_param_specs` (one psum
        per block), the KV cache sharded along its head axis per
        :func:`~distkeras_tpu.parallel.spmd.serving_cache_specs`.
        Sampling/logits/RNG stay replicated, so token streams are
        bit-identical to the single-chip engine (asserted by
        tests/test_tp_serving.py on forced host devices). Host-side
        state — scheduler, BlockPool, RadixPrefixIndex, flight
        recorder — is untouched: only the weight/cache pytrees and the
        compiled tick bodies gain shardings. Pass the TRAINING-mode
        ``tp_size=1`` model as always; the engine clones tp twins.
        ``num_kv_heads`` (or ``num_heads``) must divide by the mesh
        size.
      tp_axis: the mesh axis name to shard heads over (default
        ``"model"``).
      paged_kernel: paged attend implementation — 'auto' (the Pallas
        paged-attention kernel of :mod:`distkeras_tpu.ops.paged_attention`
        where the shape tiles on this backend, else the gathered
        reference), 'pallas' (force; interpret mode off-TPU), 'gather'
        (force the reference). Paged mode only.
      prefill_kernel: the attend over the decode cache, for every tick
        shape (a mixed tick's chunk, a decode tick's one token, a
        verify window; both cache layouts) — 'auto' (the Pallas kernel
        of :mod:`distkeras_tpu.ops.splash_prefill` where the shape
        tiles on this backend: each row's K/V is copied in up to the
        row's cursor and no further, where the dense attend reads all
        ``max_len`` positions of every row), 'splash' (force;
        interpret mode off-TPU), 'gather' (force the dense masked
        reference, which stays the bit-parity baseline).
        ``stats()["key_positions_fetched_total"]`` over
        ``cache_positions_total`` says how far it engages.
      role: advertised replica specialization for disaggregated
        serving — 'mixed' (default), 'prefill' (a compute-optimized
        replica the router sends long prompts to, exporting their KV
        blocks afterwards via :meth:`export_blocks`), or 'decode' (a
        memory-optimized replica that imports migrated blocks via
        :meth:`import_blocks` and serves the decode steady state).
        Purely declarative: surfaced in :meth:`stats` for the router's
        pool classification; shape the replica itself with
        ``tick_token_budget`` / ``prefill_chunk`` /
        ``prefill_kernel``.
      draft: enable speculative decoding (chunked mode only). Either a
        small TRAINING-mode :class:`TransformerLM` (same vocab; pass
        its variables as ``draft_params``) that proposes ``spec_k``
        tokens per decoding row per tick with its own slot-cursor
        cache, or ``"ngram"`` — the self-speculative fallback that
        needs no second model: proposals come from matching the
        stream's suffix n-gram against its own prompt + emitted
        history. The flagship verifies every window in ONE fused
        ``[S, k+1]`` dispatch (the mixed tick's ``valid_lens``
        machinery) and accepts a per-row prefix by rejection sampling:
        greedy streams are the non-speculative engine's token for
        token where the arithmetic is exact (float32: the tests hold
        it; a reduced precision rounds a ``[S, k+1]`` window and a
        ``[S, 1]`` tick differently, and the two streams part at
        near-ties, each as close to the float32 model: PERF.md §6, PR
        41), sampled streams are distributionally exact. Verify
        tokens are charged against the scheduler's
        ``tick_token_budget`` (decodes reserve 1 each, prompt chunks
        are dealt next, leftover widens the windows), so chunked
        prefill and speculation coexist. Rejected suffixes roll back
        as cursor rewinds on both cache layouts; acceptance-length
        variation never changes a compiled shape (fixed ``spec_k``
        padding — zero steady-state recompiles). ``"mtp"`` drafts with
        the served model's OWN multi-token-prediction module (a model
        that declares ``mtp_depth``; ``spec_k`` is that depth): no
        second copy of any weight, the module's cache a leaf of the
        model's own, the draft made on the device behind the
        acceptance, and the loop a tick ahead (see ``glm4_moe_lite_lm``
        below and :func:`_mtp_verify_fn`).
      draft_params: the draft model's trained variables.
      spec_k: draft tokens proposed per row per tick (default 4).
      ngram_max: longest suffix n-gram the ``"ngram"`` drafter matches
        (default 3).
      pipeline: overlap host planning and token streaming with device
        compute (the DOWNPOUR thesis applied to the tick loop: never
        stall either side on the other). ``True`` (the default since
        PR 35; every cell of the benchmark runs it) makes the loop a
        depth-2 software pipeline — tick N+1 is planned from host
        state and dispatched BEFORE tick N's tokens are read back, so
        the device starts the next step while the host streams the
        previous one. The plan knows what the unread tick leaves of a
        row's token budget (``remaining`` less the tokens in flight):
        a row whose budget runs out there is held, not fed, so a
        length finish costs nothing. An eos is not host-known: when
        tick N's tokens land and a row HAD sampled its eos, that row's
        tick-N+1 token is an overrun: dropped before streaming
        (``stats()["overrun_tokens"]``; ``["overrun_pct"]`` is its
        share of every token the ticks sampled), the slot freed and
        refilled on tick N+2 (RNG chains die with the request, so
        greedy AND sampled streams stay bit-identical to the
        alternating loop). Slots and blocks are only freed at
        reconciliation, so plan-ahead can never double-admit against
        an unreconciled finish. The plain decode tick of
        ``prefill_chunk=None`` has no control buffer to hold a row
        with: there a length finish overruns too. Speculative engines
        run a depth-1 pipeline instead (the next plan needs the
        accepted tokens): readback and bookkeeping stay synchronous,
        but emission and telemetry are deferred past the next
        dispatch. ``False`` keeps the strictly alternating loop as the
        bit-parity reference the tests compare against, same policy as
        ``paged_kernel='gather'``. In a profile of a healthy replica
        ``engine.dispatch`` of tick N+1 returns before ``engine.wait``
        of tick N begins, and no gap of the device lies under the
        host's spans while slots are occupied.
      device: pin this engine's device-side state (weights, cache,
        logits, RNG chains) to one specific :class:`jax.Device` — the
        multi-replica pattern, where N single-chip engines in one
        process each own a device and their ticks dispatch
        independently. Default: the process's first local device.
        Mutually exclusive with ``mesh`` (a tensor-parallel engine
        spans its mesh's devices).
      multi_step_k: device-resident multi-step decode. When the engine
        is in ALL-DECODE steady state (every occupied slot decoding;
        no prompt chunk dealt, no host-tier restore queued or in
        flight, no staged control call, no speculative window), run up
        to ``multi_step_k`` decode steps inside ONE dispatch — a
        ``lax.scan`` over the exact k=1 tick body, with sampling,
        KV-cache writes, and EOS detection on device — cutting
        host↔device round trips per token by k×, the same
        amortization solo :meth:`TransformerLM.generate` gets from its
        own scan loop. RNG chains advance once per emitted token and
        a row that hits EOS or its length budget mid-window goes
        quiet on device (no write, no cursor advance, chain frozen),
        so every stream stays bit-identical to the k=1 reference on
        both cache layouts, sync or pipelined, single-chip or TP.
        The moment any non-steady-state condition appears the engine
        falls back to ordinary one-token ticks for that step (counted
        per reason in ``serving_multi_step_fallbacks_total``) — and
        because k is fixed, steady state never recompiles. Default 1:
        fast path off.

    **Models with another cache.** ``model`` may be any registered LM
    whose decode twin (``clone(decode=True, slot_cursor=True)``) keeps
    per-row ``[S]`` int32 cursors in its ``cache`` collection and takes
    ``valid_lens``. ``deepseek_v32_lm`` (a latent ``[S, L, 576]`` leaf
    and an index-key leaf a layer, attention over a learned selection,
    routed experts of which this chip holds a share) is served through
    the same loop, scheduler and tick programs; the weights are held in
    the dtype they are handed (bf16 there). The engine asks such a
    model three things: ``tick_counters`` (sums returned with a tick's
    tokens: ``routed_here``, ``routed_total``,
    ``expert_rows_computed``, ``experts_read``; its
    ``tick_counter_units`` says which of them the host keeps under
    another name and times what: the last as ``expert_weight_bytes``),
    ``kv_positions_fetched`` /
    ``index_topk`` (the host's counts ``key_positions_fetched``,
    ``index_positions_scored``, ``keys_selected``) and
    ``serving_refusals``. For that model the constructor **refuses**,
    with the model's reason: ``paged=True`` (``kvpool`` allocates
    ``[blocks, block, Hk, hd]`` K and V; no latent block exists),
    ``draft=`` of any kind (its multi-token-prediction module is not
    built, and a draft's window would have to pass the indexer's
    selection), ``mesh=`` (all
    heads share one latent: heads are not split), ``multi_step_k > 1``
    (the counters return once a tick), ``prefill_chunk=None`` (the walk
    holds a chunk's scores, not a prompt's) and a model cloned with
    ``cache_dtype="int8"`` (no quantised latent): each would otherwise
    run wrong or not at all.

    ``mimo_v2_lm`` has layers of two kinds in one stack: full layers
    with a ``[S, L, Hk * 192]`` / ``[S, L, Hk * 128]`` K and V, window
    layers with a ring ``[S, R, Hk, 192]`` / ``[S, R, Hk, 128]`` of ``R``
    = 256 positions, one ``[S]`` cursor a layer as everywhere. The
    engine pools, resets and donates both as it does any leaf; it asks
    the model two things more, ``kv_positions_by_kind`` (the counts
    ``full_key_positions``, ``window_key_positions`` of a dispatch) and
    ``cache_bytes_by_kind`` (``stats()["cache_bytes_full"]``,
    ``["cache_bytes_window"]``), and the model declares
    ``packs_live_tokens``. It refuses ``paged``, ``draft``, ``mesh``,
    ``multi_step_k > 1``, ``prefill_chunk=None``, a chunk the ring
    cannot hold behind the window, and ``cache_dtype != "model"``.

    ``solar_open2_lm`` has a cache leaf that is not indexed by position:
    beside its GQA layers' ``[S, L, Hk * 128]`` K and V, each KDA layer
    keeps a recurrent state ``[S, H, 128, 128]`` float32 and the last
    three inputs of its short convolutions ``[S, 3, 3 * H * 128]``. The
    engine pools, donates and parks them as any leaf (parking zeroes the
    ``[S]`` cursors alone: the layer reads state and tail as zero where
    the row's cursor is 0, and leaves both untouched for a row dealt
    nothing). It asks the model nothing new: ``kv_positions_by_kind``
    counts ``state_rows_stepped``, ``chunk_positions_live`` and
    ``chunk_positions_computed`` beside ``full_key_positions``,
    ``cache_bytes_by_kind`` gives ``cache_bytes_state``. A token a tick
    ran ahead past an eos enters the state and cannot be rewound; it is
    harmless because that request is finished and its slot re-entered
    at cursor 0. It refuses ``paged``, ``draft``, ``mesh``,
    ``multi_step_k > 1``, ``prefill_chunk=None`` and ``cache_dtype !=
    "model"``.

    ``glm4_moe_lite_lm`` brings its drafter with it: a
    multi-token-prediction module behind the last layer, whose
    parameters are leaves of the tree the engine holds and whose cache
    is one more latent layer (``latent [S, L, 512]``, ``rope_key [S, 64,
    L]``, a cursor) among the model's own cache leaves. With
    ``draft="mtp"`` every tick is :func:`_mtp_verify_fn`'s: the main
    layers over the window, the acceptance, the module over the same
    positions with each one's next token, one rewind of every cursor,
    the module's among them. The engine asks the model ``mtp_depth``,
    ``__call__(..., head_at=)``, ``draft(...)`` and clones its decode
    module with ``verify_window = spec_k + 1``; what a tick leaves for
    the next (pending token, draft, the draft's distribution, the draft
    chains) is ``_mtp_state``, on the device, so this drafter runs a
    tick ahead (a row is held against the most an unread tick can emit).
    It refuses ``paged``, a drafter other than its module, ``mesh``,
    ``multi_step_k > 1``, ``prefill_chunk=None`` and ``cache_dtype !=
    "model"``.

    Drive it with :meth:`step` (one admit→tick→complete→refill cycle,
    e.g. from a test) or :meth:`serve_forever` (the TCP front-end's
    loop thread). ``submit`` is thread-safe; stepping is single-threaded
    by design.
    """

    def __init__(self, model, params, slots: int = 4,
                 max_len: Optional[int] = None,
                 scheduler: Optional[FIFOScheduler] = None,
                 metrics: Optional[MetricsWriter] = None,
                 registry: Optional[telemetry.MetricRegistry] = None,
                 tracer: Optional[telemetry.Tracer] = None,
                 paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 host_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = DEFAULT_PREFILL_CHUNK,
                 flight=True, flight_capacity: int = 512,
                 postmortem_dir: str = "/tmp",
                 mesh=None, tp_axis: str = "model",
                 paged_kernel: str = "auto",
                 prefill_kernel: str = "auto",
                 draft=None, draft_params=None, spec_k: int = 4,
                 ngram_max: int = 3, device=None,
                 pipeline: bool = True, role: str = "mixed",
                 multi_step_k: int = 1):
        if slots < 1:
            raise ValueError(f"slots must be >= 1; got {slots}")
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"Unknown role '{role}'. Known: mixed (the default — "
                f"serves everything), prefill (compute-optimized "
                f"replica a router sends long prompts to), decode "
                f"(memory-optimized replica that receives migrated KV "
                f"blocks). The role is advertised in stats() and steers "
                f"router pool selection only; engine behavior is shaped "
                f"by the ordinary knobs (tick_token_budget, "
                f"prefill_chunk, prefill_kernel)."
            )
        self.role = role
        self.prefill_kernel = prefill_kernel
        # a model whose cache is not [S, L, Hk, hd] K and V says what of
        # the engine it cannot be served with yet, before anything is
        # allocated (the refusal carries the model's reason)
        refusals = getattr(model, "serving_refusals", None)
        if refusals is not None:
            refusals(paged=paged, draft=draft is not None,
                     draft_kind=(draft if isinstance(draft, str)
                                 else None if draft is None else "model"),
                     mesh=mesh is not None, multi_step=multi_step_k > 1,
                     monolithic_prefill=prefill_chunk is None,
                     prefill_chunk=prefill_chunk)
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (or None for monolithic "
                f"prefill); got {prefill_chunk}"
            )
        self.prefill_chunk = prefill_chunk
        # device-resident multi-step decode: in all-decode steady state
        # the engine runs up to multi_step_k decode steps per dispatch
        # (one lax.scan window) and falls back to ordinary one-token
        # ticks the moment any non-steady-state condition appears —
        # chunk dealt, restore in flight, staged control call,
        # speculative window. 1 (the default) disables the fast path.
        if multi_step_k < 1:
            raise ValueError(
                f"multi_step_k must be >= 1; got {multi_step_k}"
            )
        self.multi_step_k = multi_step_k
        # host-side fallback accounting by reason (the registry counter
        # serving_multi_step_fallbacks_total is the labeled twin)
        self.multi_step_fallbacks: dict = {}
        self.dispatches = 0
        self._admit_seq = 0
        # pipelined loop: dispatched-but-unread ticks (at most one in
        # steady state), the packed-control-buffer reuse cache (an
        # unchanged plan re-dispatches the previous device buffer —
        # zero per-tick uploads in an all-decode steady state), and the
        # dropped-overrun accounting
        self.pipeline = pipeline
        self._pending: deque = deque()
        self._packed_prev: Tuple[Optional[np.ndarray], Any] = (None, None)
        self.overrun_tokens = 0
        # speculative decoding: a drafter proposes up to spec_k tokens
        # per decoding row per tick; the flagship verifies them in one
        # fused window and accepts a prefix by rejection sampling
        self.spec = draft is not None
        self.spec_k = spec_k
        self.ngram_max = ngram_max
        if self.spec:
            if prefill_chunk is None:
                raise ValueError(
                    "speculative decoding rides the fused mixed tick — "
                    "it needs chunked prefill (prefill_chunk is not "
                    "None)"
                )
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1; got {spec_k}")
            if isinstance(draft, str):
                if draft not in ("ngram", "mtp"):
                    raise ValueError(
                        f"Unknown draft '{draft}'. Known: 'ngram' "
                        f"(self-speculative n-gram lookup), 'mtp' (the "
                        f"served model's own multi-token-prediction "
                        f"module), or a small "
                        f"TransformerLM plus draft_params"
                    )
                if draft_params is not None:
                    raise ValueError(
                        f"draft='{draft}' takes no draft_params (it "
                        f"proposes from the stream's own history, or "
                        f"with leaves of the served model's own tree)"
                    )
                if draft == "mtp":
                    depth = getattr(model, "mtp_depth", 0)
                    if not depth:
                        raise ValueError(
                            f"draft='mtp' drafts with the served model's "
                            f"own multi-token-prediction module, and "
                            f"{type(model).__name__} carries none (no "
                            f"mtp_depth): use draft='ngram' or a draft "
                            f"model"
                        )
                    if spec_k != depth or paged or mesh is not None:
                        raise ValueError(
                            f"draft='mtp' makes one draft a module a tick "
                            f"(spec_k={depth} for this model; got "
                            f"{spec_k}), on the slot cache of one chip"
                        )
                self.draft_kind = draft
            else:
                if draft_params is None:
                    raise ValueError(
                        "a draft model needs its trained variables: "
                        "pass draft_params"
                    )
                if draft.vocab_size != model.vocab_size:
                    raise ValueError(
                        f"draft vocab_size={draft.vocab_size} != model "
                        f"vocab_size={model.vocab_size}: proposals must "
                        f"live in the flagship's token space"
                    )
                self.draft_kind = "model"
        else:
            self.draft_kind = None
        # tensor-parallel serving: a 1-D mesh shards the jitted tick
        # bodies (weights + cache) over tp_axis; everything host-side
        # stays single-process
        self.mesh = mesh
        self.tp_axis = tp_axis
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if tp_axis not in sizes:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} have no '{tp_axis}' "
                    f"axis — build the serving mesh as "
                    f"make_mesh({{'{tp_axis}': n}})"
                )
            if any(s > 1 for a, s in sizes.items() if a != tp_axis):
                raise ValueError(
                    f"the serving mesh must be 1-D over '{tp_axis}' "
                    f"(got {sizes}): the engine shards heads only — "
                    f"batch parallelism is the router's job, one engine "
                    f"per replica"
                )
            if getattr(model, "tp_size", 1) != 1:
                raise ValueError(
                    "pass the training-mode (tp_size=1) model; the "
                    "engine clones tensor-parallel decode twins for the "
                    "mesh itself"
                )
            self.tp = sizes[tp_axis]
        else:
            self.tp = 1
        # flight recorder: True = own recorder (the default — its
        # self-measured overhead is reported in stats()["flight"] and
        # bounded by serve_bench's smoke assert), a FlightRecorder to
        # share one, or None/False to disable
        if flight is True:
            self.flight: Optional[FlightRecorder] = FlightRecorder(
                capacity=flight_capacity, postmortem_dir=postmortem_dir
            )
        else:
            self.flight = flight or None
        self._mem = MemoryWatermarks()
        if device is not None and mesh is not None:
            raise ValueError(
                "device= and mesh= are mutually exclusive: a "
                "tensor-parallel engine spans its mesh's devices; "
                "per-replica device pinning is for single-chip engines"
            )
        self._device = device if device is not None else jax.local_devices()[0]
        self._recompile_mark = recompiles.mark()
        self._clock = _DeviceClock()
        self._phase = _PhaseClock("engine.", self._clock.boundary)
        # what the mixed ticks computed against what they were dealt
        self.attended_tokens_total = 0
        self.query_positions_total = 0
        self.attend_query_positions_total = 0
        self.packed_ticks_total = 0
        self.key_positions_fetched_total = 0
        self.cache_positions_total = 0
        self.useful_query_tokens_total = 0
        # what only a model with a learned selection over its cache or
        # with routed experts counts (index_positions_scored,
        # keys_selected; routed_here, routed_total,
        # expert_rows_computed from the device, expert_weight_bytes
        # from its experts_read): name -> total
        self.model_work_totals: dict = {}
        self._flight_ns = 0  # time spent building/recording snapshots
        self._tick_ns = 0    # total tick wall time (plan+device+stream)
        self.model = (model if max_len is None
                      else model.clone(max_len=max_len, parent=None))
        self.slots = slots
        self.paged = paged
        self.registry = registry or telemetry.get_registry()
        self.tracer = tracer or telemetry.get_tracer()
        # control-plane journal: drain/undrain, role flips, weight
        # swaps — served by the `events` op and merged fleet-wide
        self.journal = EventJournal(actor="engine")
        if isinstance(scheduler, dict):
            # a configuration file's way to say it: FIFOScheduler's own
            # arguments (tick_token_budget, max_queue_depth, ...)
            scheduler = FIFOScheduler(tracer=self.tracer,
                                      registry=self.registry, **scheduler)
        self.scheduler = scheduler or FIFOScheduler(
            tracer=self.tracer, registry=self.registry
        )
        # adopt an externally-built scheduler into this engine's
        # telemetry so one trace id space covers queue + slots
        self.scheduler.tracer = self.tracer
        self.scheduler.registry = self.registry
        self.scheduler._wire_metrics()
        self._wire_metrics()
        # the per-tick and per-request JSONL rows go to a writer the
        # caller handed, and nowhere otherwise: a replica holds no list
        # that grows with its ticks. stats()' two percentile keys read
        # the latest observations
        self.metrics = metrics
        self._ttft_recent: deque = deque(maxlen=self.STATS_RECENT)
        self._token_ms_recent: deque = deque(maxlen=self.STATS_RECENT)
        self._params_only = {"params": params["params"]}
        # what update_weights holds a push to: structure, shapes and
        # dtypes as handed, whatever the engine holds after its cast
        self._weights_like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
            self._params_only["params"])
        self.weight_bytes_handed = sum(
            x.nbytes for x in jax.tree.leaves(self._params_only))
        if paged:
            if self.model.max_len % block_size != 0:
                raise ValueError(
                    f"max_len={self.model.max_len} must be a multiple of "
                    f"block_size={block_size}: the gathered per-row view "
                    f"must equal the contiguous cache length exactly "
                    f"(that equality is the bit-parity guarantee)"
                )
            self.block_size = block_size
            self._max_blocks = self.model.max_len // block_size
            if num_blocks is None:
                # worst case every slot at max_len, plus the trash block;
                # raise num_blocks for prefix-cache headroom beyond what
                # finished requests leave behind
                num_blocks = BlockPool.RESERVED + slots * self._max_blocks
            self.host = None
            if host_blocks is not None:
                if host_blocks < 1:
                    raise ValueError(
                        f"host_blocks must be >= 1; got {host_blocks}"
                    )
                if not prefix_cache:
                    raise ValueError(
                        "the host tier spills the radix prefix cache — "
                        "host_blocks requires prefix_cache=True"
                    )
                if prefill_chunk is None:
                    raise ValueError(
                        "host-tier restores ride the chunked mixed "
                        "tick's plan bodies — host_blocks requires "
                        "chunked prefill (prefill_chunk is not None)"
                    )
                self.host = HostBlockPool(host_blocks, block_size,
                                          registry=self.registry)
            self.pool = BlockPool(num_blocks, block_size,
                                  registry=self.registry,
                                  host_tier=self.host)
            self.prefix = (RadixPrefixIndex(block_size)
                           if prefix_cache else None)
            paged_kw = dict(
                decode=True, paged=True, page_block_size=block_size,
                num_pages=num_blocks, paged_kernel=paged_kernel,
                prefill_kernel=prefill_kernel,
                parent=None,
            )
            self._dm_paged = self.model.clone(
                **paged_kw,
                **({"tp_size": self.tp, "tp_axis": tp_axis}
                   if mesh is not None else {}),
            )
            self._layout = _CacheLayout(self._dm_paged, self._max_blocks)
            # cache template is always the GLOBAL (tp=1) layout; under a
            # mesh, device_put + the cache specs slice the KV-head axis
            # (a tp module's init can't trace outside shard_map)
            dm_tpl = (self._dm_paged if mesh is None
                      else self.model.clone(**paged_kw))
            self._cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(
                    # keywords: init's positional slot after tokens is
                    # `train`, not block_tables
                    lambda r, t, bt, sl: dm_tpl.init(
                        r, t, block_tables=bt, seq_lens=sl
                    ),
                    jax.random.PRNGKey(0),
                    jnp.zeros((1, 1), jnp.int32),
                    jnp.zeros((1, self._max_blocks), jnp.int32),
                    jnp.zeros((1,), jnp.int32),
                )["cache"],
            )
            # host-owned per-row state fed to every jitted call; idle
            # rows point at the reserved trash block at length 0
            self._block_tables = np.zeros(
                (slots, self._max_blocks), np.int32
            )
            self._seq_lens = np.zeros((slots,), np.int32)
            # tiered KV cache: flattened-leaf indices of the
            # block-major cache leaves (the demote gather / restore
            # scatter operate on exactly these), the FIFO queue of
            # (handle, dst block) uploads not yet issued, and the
            # handle -> dst map of every queued-or-issued restore (a
            # concurrent admission hitting the same demoted chunk
            # shares the dst instead of uploading twice)
            self._blk_leaf_idx = tuple(
                i for i, leaf in enumerate(jax.tree.leaves(self._cache))
                if leaf.ndim >= 2 and leaf.shape[0] == num_blocks
            )
        else:
            if host_blocks is not None:
                raise ValueError(
                    "the host tier lives under the paged BlockPool — "
                    "host_blocks requires paged=True"
                )
            self.pool = None
            self.prefix = None
            self.host = None
            tp_kw = ({"tp_size": self.tp, "tp_axis": tp_axis}
                     if mesh is not None else {})
            if self.draft_kind == "mtp":
                # rows that feed a pending token and its draft walk the
                # cache with the decoding rows, not as chunks (no mesh
                # here: the constructor refused one above)
                tp_kw = {"verify_window": spec_k + 1}
            self._dm_slot = self.model.clone(
                decode=True, slot_cursor=True,
                prefill_kernel=prefill_kernel, parent=None, **tp_kw
            )
            self._layout = _CacheLayout(self._dm_slot)
            self._dm_one = self.model.clone(decode=True,
                                            prefill_kernel=prefill_kernel,
                                            parent=None, **tp_kw)
            dm_tpl = (self._dm_slot if mesh is None
                      else self.model.clone(decode=True,
                                            slot_cursor=True,
                                            parent=None))
            self._cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(
                    dm_tpl.init, jax.random.PRNGKey(0),
                    jnp.zeros((slots, 1), jnp.int32),
                )["cache"],
            )
        self._dm_draft = None
        self._draft_ctx: Optional[_ShardCtx] = None
        if self.draft_kind == "model":
            # the draft's slot cache mirrors the target's per-row
            # positions exactly (same max_len, same slot count), so its
            # proposals condition on the identical token history; under
            # a mesh it shards like the flagship when its head counts
            # divide, else replicates (draft_param_specs decides)
            draft_tp = 1
            if mesh is not None:
                from distkeras_tpu.parallel.spmd import draft_param_specs

                _, draft_tp = draft_param_specs(
                    {"params": draft_params["params"]},
                    num_heads=draft.num_heads,
                    num_kv_heads=draft.num_kv_heads,
                    tp_size=self.tp, tp_axis=tp_axis,
                )
            self.draft_model = draft.clone(max_len=self.model.max_len,
                                           parent=None)
            draft_kw = ({"tp_size": draft_tp, "tp_axis": tp_axis}
                        if draft_tp > 1 else {})
            self._dm_draft = self.draft_model.clone(
                decode=True, slot_cursor=True, parent=None, **draft_kw
            )
            dm_tpl = (self._dm_draft if draft_tp == 1
                      else self.draft_model.clone(
                          decode=True, slot_cursor=True, parent=None))
            self._draft_params_only = {"params": draft_params["params"]}
            self._draft_cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(
                    dm_tpl.init, jax.random.PRNGKey(0),
                    jnp.zeros((slots, 1), jnp.int32),
                )["cache"],
            )
            self._draft_tp = draft_tp
        self._draft_rngs = jnp.zeros((slots, 2), jnp.uint32)
        # draft="mtp": what the verify tick leaves on the device for the
        # next one (each row's pending token, its draft, the draft's
        # distribution, the draft chains; see _mtp_verify_fn)
        self._mtp_state = None
        if self.draft_kind == "mtp":
            self._mtp_state = (
                jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots, spec_k), jnp.int32),
                jnp.zeros((slots, spec_k, self.model.vocab_size),
                          jnp.float32),
                self._draft_rngs)
        self._last_logits = jnp.zeros(
            (slots, self.model.vocab_size), jnp.float32
        )
        self._rngs = jnp.zeros((slots, 2), jnp.uint32)
        if device is not None:
            # commit every device-side buffer to the pinned device: the
            # jitted ticks follow their committed inputs, so N replica
            # engines in one process dispatch onto N distinct devices
            # (host numpy args — fed tokens, block tables — are
            # uncommitted and follow along)
            self._params_only = jax.device_put(self._params_only, device)
            self._cache = jax.device_put(self._cache, device)
            self._last_logits = jax.device_put(self._last_logits, device)
            self._rngs = jax.device_put(self._rngs, device)
            self._draft_rngs = jax.device_put(self._draft_rngs, device)
            if self._mtp_state is not None:
                self._mtp_state = jax.device_put(self._mtp_state, device)
            if self._dm_draft is not None:
                self._draft_params_only = jax.device_put(
                    self._draft_params_only, device)
                self._draft_cache = jax.device_put(self._draft_cache,
                                                   device)
        self._ctx: Optional[_ShardCtx] = None
        if mesh is not None:
            self._init_mesh_ctx()
        # hold the model's own first-use casts, made where each leaf now
        # lies (`params` in the class docstring); the handed leaves they
        # replace are the caller's alone from here on
        self._params_only = compute_params(self.model, self._params_only)
        if self._dm_draft is not None:
            self._draft_params_only = compute_params(
                self.draft_model, self._draft_params_only)
        self.weight_bytes_held = sum(
            x.nbytes for x in jax.tree.leaves(self._params_only))
        # a model whose layers differ in kind says what each kind's
        # cache leaves hold: cache_bytes_<kind> in stats() and the
        # flight records
        by_kind = getattr(self.model, "cache_bytes_by_kind", None)
        self._cache_bytes = ({} if by_kind is None else {
            f"cache_bytes_{kind}": n
            for kind, n in by_kind(self._cache).items()})
        self._slots: List[Optional[_SlotState]] = [None] * slots
        # graceful drain: begin_drain() closes admissions (new submits
        # raise DrainingError) while queued + in-flight requests finish
        self.draining = False
        # counters (host-side observability; per-engine, unlike the
        # process-cumulative registry series)
        self.ticks = 0
        self.requests_completed = 0
        self.tokens_generated = 0
        self.prompt_tokens = 0
        self.prefix_hit_tokens = 0
        self._occ_sum = 0
        # speculative decoding accounting (per-engine; the registry
        # counters are the process-cumulative twins)
        self.draft_tokens_proposed = 0
        self.draft_tokens_accepted = 0
        # tiered KV cache accounting (per-engine; the HostBlockPool
        # owns the registry twins) + the restore pipeline state
        self._restore_queue: deque = deque()
        self._inflight_restores: dict = {}
        self.demotions = 0
        self.restores = 0
        self._tick_demoted = 0
        self._tick_restored = 0
        # KV-block migration (disaggregated serving): control calls
        # marshalled onto the engine loop thread (export/import touch
        # the lock-free engine-thread-only pool/prefix/cache state),
        # plus per-engine and per-tick transfer accounting
        self._ctrl: deque = deque()
        self.kv_blocks_exported = 0
        self.kv_blocks_imported = 0
        self._tick_exported = 0
        self._tick_imported = 0
        # live weight updates: a monotonically increasing version
        # stamped into stats(), trace spans, and flight snapshots —
        # every streamed token is attributable to the weight set that
        # produced it. update_weights (engine-thread-only; the
        # push_weights wire op marshals through call_in_loop) swaps
        # the double-buffered params tree between ticks.
        self.weight_version = 1
        self.weight_swaps = 0
        self._m_weight_version.set(1)

    def _init_mesh_ctx(self):
        """Shard the device-side engine state onto the mesh and build
        the hashable :class:`_ShardCtx` the tick builders key on:
        weights per ``lm_param_specs`` (Q/KV column-sharded, out-proj
        row-sharded — one psum per block), the cache's KV-head axis per
        ``serving_cache_specs``, logits/RNG chains replicated. For the
        monolithic slot prefill, precompute the per-shard shapes of its
        B=1 scratch cache (its in-body eval_shape can't trace a tp
        module outside shard_map)."""
        from distkeras_tpu.parallel.spmd import (
            lm_param_specs,
            serving_cache_specs,
        )

        mesh, axis = self.mesh, self.tp_axis
        is_p = lambda x: isinstance(x, P)  # noqa: E731

        def named(spec_tree):
            return jax.tree.map(lambda s: NamedSharding(mesh, s),
                                spec_tree, is_leaf=is_p)

        pspec = lm_param_specs(self._params_only, tp_axis=axis)
        cspec = serving_cache_specs(self._cache, tp_axis=axis)
        # kept for live weight updates: a pushed tree re-shards onto
        # the mesh with exactly the serving layout (reshard-on-upload,
        # same pattern as the tiered-cache restore path)
        self._param_shardings = named(pspec)
        self._params_only = jax.device_put(self._params_only,
                                           named(pspec))
        self._cache = jax.device_put(self._cache, named(cspec))
        rep = NamedSharding(mesh, P())
        self._last_logits = jax.device_put(self._last_logits, rep)
        self._rngs = jax.device_put(self._rngs, rep)
        cache1 = None
        if not self.paged:
            dm_one_tpl = self.model.clone(decode=True, parent=None)
            c1 = jax.eval_shape(
                dm_one_tpl.init, jax.random.PRNGKey(0),
                jnp.zeros((1, 1), jnp.int32),
            )["cache"]
            c1spec = serving_cache_specs(c1, tp_axis=axis)
            leaves, treedef = jax.tree.flatten(c1)
            spec_leaves = jax.tree.flatten(c1spec, is_leaf=is_p)[0]

            def local(shape, spec):
                out = list(shape)
                for i, name in enumerate(spec):
                    if name == axis:
                        out[i] //= self.tp
                return tuple(out)

            cache1 = (treedef, tuple(
                (local(l.shape, s), np.dtype(l.dtype))
                for l, s in zip(leaves, spec_leaves)
            ))
        self._ctx = _ShardCtx(
            mesh=mesh, axis=axis,
            pspec=_freeze(pspec, is_leaf=is_p),
            cspec=_freeze(cspec, is_leaf=is_p),
            cache1=cache1,
        )
        if self._dm_draft is not None:
            from distkeras_tpu.parallel.spmd import draft_param_specs

            dpspec, dtp = draft_param_specs(
                self._draft_params_only,
                num_heads=self.draft_model.num_heads,
                num_kv_heads=self.draft_model.num_kv_heads,
                tp_size=self.tp, tp_axis=axis,
            )
            # sharded draft: cache KV-head axis sliced like the
            # flagship's; replicated draft: every leaf P() — each shard
            # runs the whole drafter and proposes identical tokens
            dcspec = (serving_cache_specs(self._draft_cache,
                                          tp_axis=axis)
                      if dtp > 1 else
                      jax.tree.map(lambda _: P(), self._draft_cache))
            self._draft_params_only = jax.device_put(
                self._draft_params_only, named(dpspec))
            self._draft_cache = jax.device_put(self._draft_cache,
                                               named(dcspec))
            self._draft_rngs = jax.device_put(self._draft_rngs, rep)
            self._draft_ctx = _ShardCtx(
                mesh=mesh, axis=axis,
                pspec=_freeze(dpspec, is_leaf=is_p),
                cspec=_freeze(dcspec, is_leaf=is_p),
            )

    def _wire_metrics(self):
        """Register this engine's metric handles (get-or-create: many
        engines on one registry share the series)."""
        reg = self.registry
        self._m_ticks = reg.counter(
            "serving_ticks_total", "decode ticks executed")
        self._m_tokens = reg.counter(
            "serving_tokens_total", "tokens sampled and emitted")
        self._m_requests = reg.counter(
            "serving_requests_total",
            "requests finished, by finish reason", labelnames=("reason",))
        self._m_occupancy = reg.gauge(
            "serving_slot_occupancy", "decode slots holding a request")
        self._m_tick_ms = reg.histogram(
            "serving_token_ms",
            "per-token latency: one decode tick, host-observed (ms)")
        self._m_ttft_ms = reg.histogram(
            "serving_ttft_ms", "submit to first token (ms)")
        self._m_prefill_ms = reg.histogram(
            "serving_prefill_ms", "per-slot prefill dispatch (ms)")
        self._m_prefill_frac = reg.histogram(
            "serving_prefill_fraction",
            "per tick: prefill tokens / (prefill + decode tokens) "
            "(chunked), or prefill dispatches / dispatches (monolithic)",
            buckets=telemetry.FRACTION_BUCKETS)
        self._m_itl_ms = reg.histogram(
            "serving_itl_ms",
            "inter-token latency: gap between consecutive tokens of one "
            "stream, host-observed (ms)")
        self._m_decode_stalls = reg.counter(
            "serving_decode_stalls_total",
            "prefill dispatches that ran while decoding slots sat "
            "waiting (monolithic prefill only; chunked prefill rides "
            "the tick and never stalls a decode)")
        self._m_decode_tps = reg.gauge(
            "serving_decode_tokens_per_sec",
            "tokens emitted by the latest tick over its wall time")
        # pipelined loop (PR 10): how long the host actually BLOCKED on
        # the device per tick (sync mode: the whole compute; pipelined:
        # what overlap could not hide), and tokens computed for rows
        # that had already finished when their tick was reconciled
        self._m_device_wait = reg.histogram(
            "serving_device_wait_ms",
            "host time blocked on device readback per tick (ms) — the "
            "overlap headroom sync mode wastes and pipeline=True hides")
        self._m_overrun = reg.counter(
            "serving_overrun_tokens_total",
            "optimistically computed tokens dropped at reconciliation "
            "because their row had finished (pipeline=True late EOS)")
        self._m_prefix_hit = reg.counter(
            "serving_prefix_hit_tokens_total",
            "prompt tokens served from the radix prefix cache "
            "(prefill skipped)")
        self._m_prompt_tokens = reg.counter(
            "serving_prompt_tokens_total",
            "prompt tokens across admitted requests (hit + prefilled)")
        # tiered KV cache (host-RAM spill under the block pool): how
        # long a RESTORING row waited from admission until its last
        # demoted block was resident again — the latency the pipelined
        # restore overlap exists to hide behind in-flight ticks
        self._m_restore_wait = reg.histogram(
            "serving_restore_wait_ms",
            "RESTORING-row admission to last host-tier block resident "
            "(ms)")
        # runtime introspection (PR 5): recompiles are process-global
        # (jit trace caches are), so the gauge mirrors the shared
        # counter; memory gauges are sampled every few ticks
        self._m_recompiles = reg.gauge(
            "jax_recompiles",
            "process-total jit traces of the serving tick/prefill "
            "functions (steady-state growth is a bug)")
        self._m_rss = reg.gauge(
            "process_rss_bytes", "host resident set size")
        self._m_device_mem = reg.gauge(
            "device_bytes_in_use",
            "device allocator bytes in use (backends with memory_stats)")
        self._m_device_peak = reg.gauge(
            "device_peak_bytes_in_use",
            "device allocator high-water mark")
        self._m_oldest_wait = reg.gauge(
            "serving_queue_oldest_wait_s",
            "age of the oldest queued request (admission latency SLO)")
        self._m_crashes = reg.counter(
            "serving_engine_crashes_total",
            "exceptions escaping step() (each dumps a flight postmortem)")
        # speculative decoding (PR 7): proposals entering verify
        # windows, survivors of rejection sampling, and the per-row
        # accepted-prefix-length distribution
        self._m_draft_tokens = reg.counter(
            "serving_draft_tokens_total",
            "speculative draft tokens entering verify windows")
        self._m_accepted_tokens = reg.counter(
            "serving_accepted_tokens_total",
            "draft tokens accepted by rejection sampling")
        self._m_accept_len = reg.histogram(
            "serving_accept_len",
            "accepted draft prefix length per speculating row per tick",
            buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16))
        # per-request critical-path attribution (PR 11): where each
        # finished request's wall time went. The engine observes the
        # phases it can see (queue wait, prefill, decode host side,
        # device compute share); the TCP pump adds the post-decode
        # delivery tail as phase="stream" and the router its routing
        # overhead as phase="router" — one family, one label
        self._m_critical = reg.histogram(
            "serving_request_critical_path_ms",
            "per-request time attribution by critical-path phase (ms)",
            labelnames=("phase",))
        self._m_cp = {ph: self._m_critical.labels(phase=ph)
                      for ph in ("queue", "prefill", "decode", "device")}
        # QoS classes (PR 18): per-tier latency histograms and
        # critical-path attribution, so the interactive tier's SLO can
        # be monitored (and alerted on) independently of how badly the
        # batch tier is being degraded to protect it. New families
        # rather than a tier label on the unlabeled serving_ttft_ms /
        # serving_itl_ms — existing dashboards and SLO rules keep
        # reading the fleet-wide series unchanged.
        self._m_qos_ttft = reg.histogram(
            "serving_qos_ttft_ms",
            "submit to first token by QoS tier (ms)",
            labelnames=("tier",))
        self._m_qos_itl = reg.histogram(
            "serving_qos_itl_ms",
            "inter-token latency by QoS tier (ms)",
            labelnames=("tier",))
        self._m_qos_critical = reg.histogram(
            "serving_qos_critical_path_ms",
            "per-request critical-path attribution by QoS tier (ms)",
            labelnames=("tier", "phase"))
        # device-resident multi-step decode (PR 19): dispatch-level
        # accounting. tokens/dispatch is the amortization the k-step
        # window buys (a flat 1 means multi-step is off or the engine
        # never reaches all-decode steady state); the fallback counter
        # says WHY windows are not being granted
        self._m_dispatches = reg.counter(
            "serving_dispatches_total",
            "tick dispatches (a k-step multi window counts once)")
        self._m_tokens_per_dispatch = reg.histogram(
            "serving_tokens_per_dispatch",
            "tokens emitted per tick dispatch (multi-step windows "
            "amortize the host round trip over up to k tokens)",
            buckets=(0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32))
        self._m_multi_fallbacks = reg.counter(
            "serving_multi_step_fallbacks_total",
            "planned ticks that fell back to k=1, by the "
            "non-steady-state condition that forced it",
            labelnames=("reason",))
        # live weight updates (the train→serve loop): the currently
        # served weight version, swap count, and how long each atomic
        # hot swap took (validation + staged device upload + rebind)
        self._m_weight_version = reg.gauge(
            "serving_weight_version",
            "monotonically increasing version of the live weights "
            "(bumped by every push_weights swap)")
        self._m_weight_swaps = reg.counter(
            "serving_weight_swaps_total",
            "atomic weight hot swaps applied at the tick boundary")
        self._m_weight_swap_ms = reg.histogram(
            "serving_weight_swap_ms",
            "one weight swap: validation, staged host→device upload "
            "dispatch, and the params rebind (ms)")

    # -- submission ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, temperature: float = 0.0,
               seed: int = 0, eos_id: Optional[int] = None,
               top_k: Optional[int] = None, top_p: Optional[float] = None,
               deadline_s: Optional[float] = None,
               tier: str = "interactive",
               trace_id: Optional[int] = None,
               parent_span: Optional[str] = None) -> Request:
        """Queue one request; returns it (consume ``request.stream``).
        ``tier`` is the QoS class (one of
        :data:`~distkeras_tpu.serving.scheduler.QOS_TIERS`):
        interactive requests are admitted and dealt prefill budget
        before batch ones, and land in per-tier latency histograms.
        ``trace_id`` joins the request to an upstream-propagated
        telemetry trace (the TCP front-end forwards the wire ``trace``
        field here, so one id follows a request across processes);
        omitted, the scheduler mints a fresh fleet-unique id.
        ``parent_span`` names the upstream span that submitted this
        request (stamped on the queued span as the cross-process link).
        Raises :class:`QueueFullError` under backpressure,
        :class:`DrainingError` after :meth:`begin_drain`, and
        ``ValueError`` for requests that can never fit the cache."""
        if self.draining:
            raise DrainingError(
                "engine is draining: admissions are closed, in-flight "
                "streams are finishing"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}"
            )
        if prompt.size + max_new_tokens > self.model.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len={self.model.max_len} "
                f"(the per-slot KV-cache length)"
            )
        if top_k is not None:
            if top_k < 1:
                raise ValueError(f"top_k must be >= 1; got {top_k}")
            top_k = min(top_k, self.model.vocab_size)
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1]; got {top_p}")
        if tier not in QOS_TIERS:
            raise ValueError(
                f"unknown QoS tier {tier!r}; expected one of {QOS_TIERS}"
            )
        req = Request(
            prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, seed=seed, eos_id=eos_id,
            top_k=top_k, top_p=top_p, deadline_s=deadline_s,
            tier=tier, trace_id=trace_id, parent_span=parent_span,
        )
        return self.scheduler.submit(req)

    # -- the engine loop ----------------------------------------------------

    @property
    def slot_requests(self) -> List[Optional[int]]:
        """Request id per slot (None = free) — test/observability hook."""
        return [st.req.rid if st else None for st in self._slots]

    def step(self) -> bool:
        """One scheduler iteration: admit into free slots, plan and
        dispatch the next tick over the pool (mixed prefill/decode when
        chunked), then read the tick before it, emit its tokens and
        free finished slots (the next call's admit refills them). With
        ``pipeline=False``: admit, run one tick, emit, free, and refill
        in the same call. Returns False when there is nothing to do.

        An exception escaping the cycle dumps the flight recorder to a
        postmortem JSONL (``report --flight`` renders it) before
        re-raising — the crash takes the engine down with its last
        ``flight_capacity`` ticks of state on disk, not in the void."""
        try:
            return self._step()
        except Exception as e:
            self._m_crashes.inc()
            if self.flight is not None:
                path = self.flight.dump_postmortem(
                    "crash", error=f"{type(e).__name__}: {e}",
                    tick=self.ticks,
                )
                if path:
                    print(
                        f"ServingEngine: step() crashed at tick "
                        f"{self.ticks}; flight postmortem: {path}",
                        file=sys.stderr,
                    )
            raise

    def _admit_phase(self) -> int:
        with self._phase("admit", tick=self.ticks + 1):
            return self._admit()

    def _step(self) -> bool:
        # ctrl, admit and idle brackets carry the number of the tick
        # whose record closes the period they fall in
        with self._phase("ctrl", tick=self.ticks + 1):
            self._drain_ctrl()
        if self.pipeline:
            return self._pipelined_step()
        n_prefills = self._admit_phase()
        occupied = any(st is not None for st in self._slots)
        if occupied:
            k = self._multi_gate()
            if k > 1:
                self._reconcile(self._plan_dispatch_multi(k))
            elif self.spec:
                self._spec_tick()
            elif self.prefill_chunk is not None:
                self._mixed_tick()
            else:
                self._decode_tick()
            # EOS'd / exhausted slots were freed while processing the
            # tick's tokens: refill them NOW so the next tick decodes
            # their replacement requests (same-tick refill)
            n_prefills += self._admit_phase()
            if self.prefill_chunk is None:
                # share of this step's device dispatches that were
                # prefill passes (decode-latency pressure from arrival
                # bursts); the chunked path observes a per-tick TOKEN
                # fraction inside _mixed_tick instead
                self._m_prefill_frac.observe(n_prefills / (n_prefills + 1))
        return occupied or self.scheduler.depth() > 0

    def _pipelined_step(self) -> bool:
        """One pipelined scheduler iteration. Non-speculative engines
        run depth-2: admit, plan tick N+1 OPTIMISTICALLY (every planned
        row is assumed to continue — finishes in the still-unread tick
        N are unknown, but for a row whose token budget N uses up:
        that one is held), dispatch it, and only then reconcile tick N —
        materialize its tokens (the device is already running N+1),
        stream them, drop overruns for rows that turn out to have
        finished earlier, and free/complete slots (refilled by the next
        step's admit, i.e. on tick N+2). Speculative engines run
        depth-1: the next plan NEEDS the accepted tokens (pending
        token, n-gram history), so reconciliation runs first, but token
        emission and telemetry are deferred until after the next
        dispatch — the device computes tick N+1 while the host streams
        tick N. An engine that drafts with the served model's own module
        (``draft="mtp"``) runs depth-2 like a non-speculative one: the
        pending token and the draft are the device's, so nothing of tick
        N is needed to plan N+1 but how many tokens it may emit, and a
        row is held against the most it can (every draft accepted)."""
        if self.spec and self.draft_kind != "mtp":
            defer: list = []
            while self._pending:
                self._reconcile_spec(self._pending.popleft(), defer)
            self._admit_phase()
            occupied = any(st is not None for st in self._slots)
            if occupied:
                self._multi_gate()  # fallback accounting only ("spec")
                self._pending.append(self._plan_dispatch_spec())
            if defer:
                # the reconciled tick's tokens reach their consumers
                # only now, inside the next tick's period
                with self._phase("stream", tick=self.ticks, deferred=1):
                    self._flush_emissions(defer)
            return (occupied or self.scheduler.depth() > 0
                    or bool(self._pending))
        self._admit_phase()
        occupied = any(st is not None for st in self._slots)
        # a row whose budget the unread tick uses up has nothing left to
        # be fed: where every occupied row is such a one there is no
        # tick to run ahead with
        ahead = any(st is not None and st.unplanned > 0
                    for st in self._slots)
        if ahead:
            k = self._multi_gate()
            if k > 1:
                rec = self._plan_dispatch_multi(k)
            elif self.spec:
                rec = self._plan_dispatch_spec()
            elif self.prefill_chunk is not None:
                rec = self._plan_dispatch_mixed()
            else:
                rec = self._plan_dispatch_decode()
            self._pending.append(rec)
        # keep exactly one tick unreconciled while one was dispatched
        # (the pipeline depth); flush everything once nothing was, so the
        # last streams always complete
        keep = 1 if ahead else 0
        while len(self._pending) > keep:
            rec = self._pending.popleft()
            if self.spec:
                self._reconcile_spec(rec, None)
            else:
                self._reconcile(rec)
        return (occupied or self.scheduler.depth() > 0
                or bool(self._pending))

    def serve_forever(self, stop: threading.Event,
                      idle_sleep: float = 0.002):
        """Step until ``stop`` is set, dozing briefly when idle."""
        while not stop.is_set():
            if not self.step():
                with self._phase("idle", tick=self.ticks + 1):
                    stop.wait(idle_sleep)

    def drain(self, timeout: float = 120.0):
        """Step until queue and slots are empty (bench/test helper)."""
        deadline = time.monotonic() + timeout
        while self.step():
            if time.monotonic() > deadline:
                raise TimeoutError("engine did not drain in time")

    def abandon_streams(self, reason: str = "error"):
        """End the stream of every request still queued or in a slot.
        For a loop that has stopped for good (:meth:`LMServer.stop`): it
        will emit no further token, and a consumer left waiting on such
        a stream (the server's pump thread) would wait for ever, keeping
        the server, this engine and its device-side cache alive."""
        for req in self.scheduler.abandon():
            req.stream._finish(reason)
        for slot, st in enumerate(self._slots):
            if st is not None:
                self._slots[slot] = None
                st.req.stream._finish(reason)

    def begin_drain(self):
        """Close admissions for a graceful shutdown: subsequent
        :meth:`submit` calls raise :class:`DrainingError`, while queued
        and in-flight requests keep streaming to completion under the
        normal loop. Progress is visible in :meth:`stats`:
        ``draining`` flips True here, ``drained`` once the queue and
        every slot are empty. Idempotent; served over TCP as the
        ``drain`` op (:meth:`ServingClient.drain`)."""
        if not self.draining:
            self.journal.append("drain",
                                queued=self.scheduler.depth())
        self.draining = True

    def end_drain(self):
        """Reopen admissions after :meth:`begin_drain` — the undrain
        half of the rolling-update primitive (drain → push weights →
        undrain). Idempotent; served over TCP as the ``drain`` op's
        ``undrain`` field (:meth:`ServingClient.undrain`)."""
        if self.draining:
            self.journal.append("undrain")
        self.draining = False

    def set_role(self, role: str) -> str:
        """Reconfigure the replica's advertised specialization (the
        fleet controller's rebalancing primitive: drain → ``set_role``
        → undrain flips a spare mixed replica into the pool that is
        burning its SLO). Engine-thread-only, like
        :meth:`update_weights` — TCP handler threads marshal through
        :meth:`call_in_loop` (the ``reconfigure`` wire op does), so
        the flip lands between ticks. The role only gates how the
        router classifies the replica and which admissions it sends;
        the compiled tick functions are role-independent, so a flip
        can never cause a steady-state recompile. Callers should flip
        only a drained replica — in-flight mixed work on a
        newly-"prefill" replica still finishes correctly, but the
        router's pool accounting is cleanest across a drain."""
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"unknown role {role!r}: expected 'mixed', 'prefill', "
                f"or 'decode'"
            )
        if role != self.role:
            self.journal.append("reconfigure", target=role,
                                previous=self.role)
        self.role = role
        return role

    @property
    def drained(self) -> bool:
        """True once a draining engine has finished all accepted work
        (no queued requests, every slot free)."""
        return (self.draining and self.scheduler.depth() == 0
                and all(st is None for st in self._slots))

    def update_weights(self, variables, version: Optional[int] = None,
                       ) -> dict:
        """Atomic live weight swap, applied at the tick boundary.

        Engine-thread-only (like :meth:`export_blocks`): TCP handler
        threads marshal through :meth:`call_in_loop` — the
        ``push_weights`` wire op does — so the swap always lands
        *between* ticks with no locks anywhere near the hot path. In
        pipelined mode that boundary is the top of the next step: the
        in-flight tick was dispatched with a reference to the old tree
        and completes on it untouched (old-version completion is the
        documented invariant); the next dispatch picks up the new
        tree. The swap itself is double-buffered — the pushed host
        tree is staged onto the device (re-sharded onto the mesh per
        the serving param specs under tensor parallelism, pinned to
        the replica's device otherwise) while the old tree keeps
        serving, then one host pointer rebind makes it live. Ticks
        are compiled over the params *shapes*, which validation pins
        equal, so a swap can never cause a steady-state recompile.

        ``variables`` is the model's variables dict (``{"params":
        ...}``; a bare params tree is wrapped). Structure, shapes, and
        dtypes must match the weights **as handed at construction**
        exactly (a trainer's ``float32`` tree stays the thing to push,
        whatever the engine holds) — the first mismatched leaf raises a
        typed :class:`~distkeras_tpu.serving.WeightPushError` *before*
        anything is touched. The staged tree then gets the cast the
        constructor made (``compute_params``, on the device), so the
        rebound tree has the held tree's shapes and dtypes; until the
        rebind the device holds the old tree, the staged push and its
        casts. A draft model's weights are not updated (push the
        flagship only; restart to change the drafter).

        ``version`` stamps the new weights (a checkpoint step, a PS
        commit count); the engine keeps its version monotonic — a
        stale or absent version still bumps by one, so every swap is
        observable. Returns ``{"version", "swap_ms"}``."""
        t0 = time.perf_counter()
        if not (isinstance(variables, dict) and "params" in variables):
            variables = {"params": variables}
        validate_like(self._weights_like, variables["params"])
        new = {"params": variables["params"]}
        if self.mesh is not None:
            new = jax.device_put(new, self._param_shardings)
        else:
            new = jax.device_put(new, self._device)
        new = compute_params(self.model, new)
        # the rebind IS the swap: in-flight dispatches hold their own
        # reference to the old tree (params are never donated), so
        # they complete on the old version while new dispatches read
        # the new one
        self._params_only = new
        if version is not None and int(version) > self.weight_version:
            self.weight_version = int(version)
        else:
            self.weight_version += 1
        self.weight_swaps += 1
        swap_ms = (time.perf_counter() - t0) * 1e3
        self._m_weight_version.set(self.weight_version)
        self._m_weight_swaps.inc()
        self._m_weight_swap_ms.observe(swap_ms)
        self.tracer.record(0, "serving.weight_swap", time.monotonic(),
                           0.0, wv=self.weight_version,
                           swap_ms=round(swap_ms, 3))
        self.journal.append("weight_push",
                            version=self.weight_version,
                            swap_ms=round(swap_ms, 3))
        return {"version": self.weight_version,
                "swap_ms": round(swap_ms, 3)}

    def watchdog(self, timeout_s: float = 30.0,
                 interval_s: Optional[float] = None) -> StallWatchdog:
        """A :class:`StallWatchdog` wired to this engine: when the tick
        counter stops advancing for ``timeout_s`` while work is pending
        (occupied slots or queued requests), it dumps a flight
        postmortem — the failure mode threshold alerts can't see,
        because a wedged engine updates no metric. The caller owns the
        lifecycle (``.start()`` / ``.stop()``); :class:`LMServer` does
        this when given ``watchdog_timeout_s``."""
        return StallWatchdog(
            progress=lambda: self.ticks,
            busy=lambda: (any(st is not None for st in self._slots)
                          or self.scheduler.depth() > 0),
            timeout_s=timeout_s, interval_s=interval_s,
            flight=self.flight, registry=self.registry,
            tracer=self.tracer,
        )

    def mark_steady(self):
        """Declare warmup over: snapshot the process-global recompile
        counts. Any nonzero :meth:`recompiles_since_mark` afterwards
        means a jitted serving function re-traced in steady state — a
        latency bug (``serve_bench --smoke`` asserts the dict is
        empty). The device clock's sums (``stats()["device_*"]``) count
        from the next dispatch on; the flight ring is left alone."""
        self._recompile_mark = recompiles.mark()
        self._clock.mark()

    def recompiles_since_mark(self) -> dict:
        """Per-function jit traces since :meth:`mark_steady` (or engine
        construction). Empty dict = clean steady state."""
        return recompiles.since(self._recompile_mark)

    # -- internals ----------------------------------------------------------

    def _admit(self) -> int:
        free = [i for i, st in enumerate(self._slots) if st is None]
        if not free:
            return 0
        admissible = None
        if self.paged:
            # free-block-aware admission: a request only enters a slot
            # when its WORST-CASE block need (full prompt + full token
            # budget, minus prefix blocks pinned by live refs) fits in
            # free + evictable blocks. Without this, a large admission
            # could force mid-decode eviction of blocks a live sequence
            # still needs — admission is the only safe place to say no.
            # `reserved` accumulates within one pop so a batch of
            # admissions can't jointly overcommit.
            reserved = [0]

            def admissible(req: Request) -> bool:
                need, avail = self._paged_headroom(req)
                if avail - reserved[0] < need:
                    return False
                reserved[0] += need
                return True

        admitted, expired = self.scheduler.pop_admissible(
            len(free), admissible=admissible
        )
        if self.metrics is not None:
            for req in expired:
                # span chain, finish-reason counter, and the stream
                # sentinel are recorded by the scheduler (expiry is
                # visible in trace dumps even if no engine ever pops);
                # the engine adds only its per-request JSONL summary
                self.metrics.summary(
                    "request", rid=req.rid, reason="expired", tokens=0,
                    queued_ms=round((req.done_t - req.submit_t) * 1e3, 3),
                )
        for req in admitted:
            self._prefill_into(free.pop(0), req)
        return len(admitted)

    # -- paged internals ----------------------------------------------------

    def _blocks_for(self, req: Request) -> int:
        """Worst-case logical blocks a request can occupy: every prompt
        and generated token position, rounded up to whole blocks."""
        return -(-(int(req.prompt.size) + req.max_new_tokens)
                 // self.block_size)

    def _paged_headroom(self, req: Request):
        """(need, avail) for admission: fresh blocks the request must be
        able to allocate (prefix hits only count as savings while their
        blocks are pinned by live references — an unreferenced cached
        block could be evicted by a peer admission before this request
        reaches it; a HOST hit saves nothing, its restore destination
        is a fresh block, except where an in-flight restore of the same
        chunk already owns a live dst this request will share), and the
        blocks obtainable without touching live data (free +
        unreferenced cached, excluding this request's own hit chain)."""
        total = self._blocks_for(req)
        if self.prefix is None:
            return total, self.pool.free_count()
        m = self.prefix.match(req.prompt)
        hit_live = sum(1 for b in m.blocks if self.pool.ref[b] > 0)
        reused = sum(1 for h in m.host if h in self._inflight_restores)
        avail = self.pool.free_count() + self.prefix.evictable_count(
            self.pool.ref, exclude=m.blocks
        )
        return total - hit_live - reused, avail

    def _alloc_blocks(self, n: int, keep=()) -> List[int]:
        """Allocate ``n`` blocks, evicting LRU unreferenced prefix
        blocks as needed (``keep`` protects a hit chain about to be
        reused). With a host tier the eviction DEMOTES: the victim's
        contents move to pinned host memory and its radix node is
        re-keyed ``device -> host``, so the prefix stays matchable.
        Admission guarantees this succeeds for admitted requests;
        OutOfBlocksError here means admission was bypassed."""
        while self.pool.free_count() < n and self.prefix is not None:
            # batch one round of victims (bottom-up peeking can't climb
            # past a still-registered device child, so a round picks
            # sibling leaves; the outer loop climbs after they're gone)
            need = n - self.pool.free_count()
            victims: List[int] = []
            ex = set(keep)
            while len(victims) < need:
                blk = self.prefix.peek_evictable(self.pool.ref,
                                                 exclude=ex)
                if blk is None:
                    break
                victims.append(blk)
                ex.add(blk)
            if not victims:
                break
            if self.host is not None:
                self._demote_blocks(victims)
            else:
                for blk in victims:
                    self.prefix.remove_block(blk)
            for blk in victims:
                self.pool.evict(blk)
        return self.pool.alloc(n)

    def _demote_blocks(self, blks: List[int]):
        """Demote a round of about-to-be-evicted prefix-cached blocks:
        gather each one's K/V (+ int8 scales) off the device —
        unsharded, whatever the mesh — memcpy into the host pool, and
        re-key the radix nodes to the returned handles; the caller then
        frees the device blocks (:meth:`BlockPool.evict` returns each
        id, pinning the demotion to exactly the block released). Off
        the hot path: runs only when an allocation must reclaim
        (admission), never per tick — and ALL gathers dispatch
        asynchronously before the first host copy blocks, so a round
        pays one device round trip, not one per block. The host pool
        may LRU-evict older entries to make room — their radix subtrees
        unlink, cascading entry discards — or refuse when everything it
        holds is pinned by in-flight restores, in which case the
        demotion degrades to the tier-less plain eviction (bounded host
        footprint beats an unbounded one)."""
        gather = _gather_block_fn(self._blk_leaf_idx)
        outs = [gather(self._cache, jnp.int32(blk)) for blk in blks]
        for blk, out in zip(blks, outs):
            leaves = [np.asarray(x) for x in out]
            handle, lru_evicted = self.host.put(leaves)
            for h in lru_evicted:
                for hh in self.prefix.drop_host(h):
                    self.host.discard(hh)
            if handle is None:
                for hh in self.prefix.remove_block(blk):
                    self.host.discard(hh)
                continue
            self.prefix.demote(blk, handle)
            self.demotions += 1
            self._tick_demoted += 1

    def _prefill_into(self, slot: int, req: Request):
        now = time.monotonic()
        req.admit_t = now
        self.tracer.record(req.trace_id, "queued", req.submit_t,
                           (now - req.submit_t) * 1e3,
                           parent=req.parent_span,
                           wv=self.weight_version)
        if self.prefill_chunk is not None:
            self._chunked_enter(slot, req, now)
            return
        if self.paged:
            self._paged_prefill_into(slot, req, now)
            return
        if any(st is not None and st.decoding for st in self._slots):
            # this monolithic whole-prompt dispatch runs between ticks:
            # every live decode stream waits it out (the ITL spike
            # chunked prefill exists to remove)
            self._m_decode_stalls.inc()
        prefill = _prefill_fn(self._dm_one, self._ctx)
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        t0 = time.perf_counter()
        self._cache, self._last_logits = prefill(
            self._params_only, self._cache, self._last_logits,
            prompt, jnp.int32(slot),
        )
        self._rngs = self._rngs.at[slot].set(jax.random.PRNGKey(req.seed))
        self._slots[slot] = _SlotState(req=req,
                                       remaining=req.max_new_tokens)
        self.prompt_tokens += int(req.prompt.size)
        self._m_prompt_tokens.inc(int(req.prompt.size))
        # dispatch time only — no forced sync here; the tick's own
        # host fetch is the hot path's one synchronization point
        prefill_ms = (time.perf_counter() - t0) * 1e3
        req.prefill_done_t = time.monotonic()
        self.tracer.record(req.trace_id, "prefill", now, prefill_ms,
                           slot=slot, prompt_tokens=int(req.prompt.size),
                           wv=self.weight_version)
        self._m_prefill_ms.observe(prefill_ms)

    def _paged_attach_blocks(self, req: Request):
        """Shared paged admission bookkeeping: radix-match the prompt,
        reuse the matched device-resident prefix blocks (refcount bump,
        zero prefill), queue restore uploads for the matched
        HOST-resident chunks (each gets a fresh destination block the
        row owns — or shares the dst of an already-in-flight restore of
        the same chunk), copy-on-write a partially-shared block if the
        prompt diverges mid-block on a device frontier, allocate the
        rest. Returns ``(chain, cached, restoring)`` — the row's
        physical block chain, how many leading prompt tokens are served
        by the cache (device + host hits + COW), and the ordered
        ``(handle, token_offset)`` restore list (empty = the row may
        prefill immediately; non-empty = RESTORING until the uploads
        land)."""
        bs = self.block_size
        # `is not None`, NOT truthiness: __len__ counts device nodes
        # only, so an index whose entries are all host-resident (fully
        # demoted tier, or a fresh KV import into the host pool) is
        # falsy — the old check silently skipped its hits
        m = (self.prefix.match(req.prompt) if self.prefix is not None
             else None)
        shared = list(m.blocks) if m else []
        host_hits = list(m.host) if m else []
        total = self._blocks_for(req)
        # pin the host entries FIRST: the allocation below may demote
        # more blocks, and the host pool's LRU must not evict an entry
        # this admission is about to restore from
        reuse = {}
        for h in host_hits:
            if h in self._inflight_restores:
                reuse[h] = self._inflight_restores[h]
            else:
                self.host.pin(h)
            self.host.touch(h)
        keep = shared + list(reuse.values())
        # (len(shared)+len(host))*bs <= Tp-1 < total*bs, so at least
        # one fresh block beyond the hit chain
        fresh = self._alloc_blocks(
            total - len(shared) - len(reuse), keep=keep
        )
        fi = 0
        chain = list(shared)
        restoring: List[tuple] = []
        for i, h in enumerate(host_hits):
            dst = reuse.get(h)
            if dst is None:
                dst = fresh[fi]
                fi += 1
                self._inflight_restores[h] = dst
                self._restore_queue.append((h, dst))
            chain.append(dst)
            restoring.append((h, (len(shared) + i) * bs))
        chain += fresh[fi:]
        self.pool.incref(chain)
        cached = (len(shared) + len(host_hits)) * bs
        if m is not None and m.cow is not None:
            # the prompt shares j tokens of a cached block, then
            # diverges: copy that block into this row's first fresh
            # block — the row's writes land in its own copy, the shared
            # original stays immutable under other tables. (COW is only
            # offered from a device frontier, so host_hits is empty and
            # fresh[0] is the first block past the shared chain.)
            src, j = m.cow
            self._cache = _copy_block(
                self._cache, jnp.int32(src), jnp.int32(fresh[0])
            )
            cached += j
        return chain, cached, restoring

    def _paged_prefill_into(self, slot: int, req: Request, now: float):
        """Admit one request into a paged slot (monolithic mode):
        attach its block chain, then prefill ONLY the uncached suffix
        at B=1 through the shared block pool."""
        if any(st is not None and st.decoding for st in self._slots):
            self._m_decode_stalls.inc()
        Tp = int(req.prompt.size)
        # monolithic mode never has a host tier (the constructor gates
        # host_blocks on chunked prefill), so restoring is always empty
        chain, cached, _ = self._paged_attach_blocks(req)
        suffix = jnp.asarray(req.prompt[cached:], jnp.int32)[None]
        table = np.zeros((1, self._max_blocks), np.int32)
        table[0, :len(chain)] = chain
        prefill = _paged_prefill_fn(self._dm_paged, self._ctx)
        t0 = time.perf_counter()
        self._cache, self._last_logits = prefill(
            self._params_only, self._cache, self._last_logits,
            suffix, jnp.asarray(table),
            jnp.asarray([cached], jnp.int32), jnp.int32(slot),
        )
        self._rngs = self._rngs.at[slot].set(jax.random.PRNGKey(req.seed))
        # copy-and-rebind (never mutate in place): the previous tick's
        # jnp.asarray of these buffers may still alias them on-device
        tables = self._block_tables.copy()
        tables[slot, :] = 0
        tables[slot, :len(chain)] = chain
        self._block_tables = tables
        lens = self._seq_lens.copy()
        lens[slot] = Tp
        self._seq_lens = lens
        self._slots[slot] = _SlotState(
            req=req, remaining=req.max_new_tokens, blocks=chain,
            cached_tokens=cached,
        )
        self.prompt_tokens += Tp
        self.prefix_hit_tokens += cached
        self._m_prompt_tokens.inc(Tp)
        self._m_prefix_hit.inc(cached)
        prefill_ms = (time.perf_counter() - t0) * 1e3
        req.prefill_done_t = time.monotonic()
        self.tracer.record(req.trace_id, "prefill", now, prefill_ms,
                           slot=slot, prompt_tokens=Tp,
                           cached_tokens=cached, blocks=len(chain),
                           wv=self.weight_version)
        self._m_prefill_ms.observe(prefill_ms)

    # -- chunked prefill (the fused mixed tick) -----------------------------

    def _chunked_enter(self, slot: int, req: Request, now: float):
        """Admit one request into a slot WITHOUT any prefill dispatch:
        the prompt is queued on the slot state (``pending``) and streams
        through the next mixed ticks chunk-by-chunk under the
        scheduler's token budget. Prefix-cache hits still skip the
        shared span — only the suffix goes through chunks."""
        Tp = int(req.prompt.size)
        cached = 0
        restoring: List[tuple] = []
        if self.paged:
            chain, cached, restoring = self._paged_attach_blocks(req)
            tables = self._block_tables.copy()
            tables[slot, :] = 0
            tables[slot, :len(chain)] = chain
            self._block_tables = tables
            # copy-and-rebind (aliasing hazard, see _CacheLayout.advance):
            # the row starts at the cached span; chunks advance it
            lens = self._seq_lens.copy()
            lens[slot] = cached
            self._seq_lens = lens
            self._rngs = _seed_slot(self._rngs, np.int32(slot),
                                    np.int64(req.seed))
        else:
            chain = None
            # one enqueue behind the tick in flight (the cache is its
            # output): the loop a tick ahead admits while the device runs
            self._cache, self._rngs = _enter_slot(
                self._cache, self._rngs, np.int32(slot),
                np.int64(req.seed))
        st = _SlotState(
            req=req, remaining=req.max_new_tokens, blocks=chain,
            cached_tokens=cached, cursor=cached,
            pending=np.asarray(req.prompt[cached:], np.int32),
            decoding=False, restoring=restoring or None,
            admit_seq=self._admit_seq, admit_t=now,
        )
        if self.spec:
            # speculative state: the drafter conditions on the FULL
            # prompt (a radix prefix hit skips target prefill for the
            # shared span, but neither the n-gram history nor the
            # draft model's private cache has seen it)
            if self.draft_kind == "ngram":
                st.history = np.asarray(req.prompt, np.int32).copy()
            elif self.draft_kind == "mtp":
                # the module's cache is a leaf of the cache _enter_slot
                # just parked; only a sampled row's draft chain is new
                if req.temperature != 0.0:
                    *held, chains = self._mtp_state
                    self._mtp_state = (*held, chains.at[slot].set(
                        jax.random.fold_in(
                            jax.random.PRNGKey(req.seed), 1)))
            else:
                st.draft_queue = np.asarray(req.prompt, np.int32).copy()
                self._draft_cache = _reset_slot_cursors(
                    self._draft_cache, jnp.int32(slot))
                self._draft_rngs = self._draft_rngs.at[slot].set(
                    jax.random.fold_in(jax.random.PRNGKey(req.seed), 1))
        self._slots[slot] = st
        self._admit_seq += 1
        self.prompt_tokens += Tp
        self._m_prompt_tokens.inc(Tp)
        if self.paged:
            self.prefix_hit_tokens += cached
            self._m_prefix_hit.inc(cached)

    # -- tiered KV cache (host-RAM spill restores) --------------------------

    def _issue_restores(self):
        """Upload up to ``restore_budget`` queued host-tier blocks back
        into the device pool in ONE batched scatter dispatch. Called
        from the plan bodies, BEFORE the tick's compute is dispatched:
        the upload is asynchronous, overlaps whatever is still in
        flight (the pipelined loop's whole point), and the cache data
        dependency orders it ahead of every later tick — nothing here
        reads a device value back, so the plan stays sync-free. Rows
        whose last awaited block lands flip RESTORING → PREFILLING (and
        only then start charging the scheduler's token budget); the
        handle's radix node is promoted back to device residency at its
        destination block, so concurrent requests share the restored
        copy like any other cached prefix. A handle whose host entry
        vanished (the defensive race) falls back to seeded replay:
        :meth:`_restore_fallback` rewinds the waiting rows to recompute
        the span — deterministic prefill writes the identical bytes
        into the identical blocks."""
        n = self.scheduler.plan_restore(len(self._restore_queue))
        if n <= 0:
            return
        R = self.scheduler.restore_budget
        dsts = np.zeros((R,), np.int32)  # pad -> block 0 (trash)
        stacked = None
        done: List[tuple] = []
        while self._restore_queue and len(done) < n:
            h, dst = self._restore_queue.popleft()
            leaves = self.host.take(h)
            if leaves is None:
                self._restore_fallback(h)
                continue
            if stacked is None:
                stacked = [np.zeros((R,) + a.shape, a.dtype)
                           for a in leaves]
            for j, a in enumerate(leaves):
                stacked[j][len(done)] = a
            dsts[len(done)] = dst
            done.append((h, dst))
        if not done:
            return
        restore_f = _restore_blocks_fn(self._blk_leaf_idx)
        self._cache = restore_f(self._cache, stacked,
                                jnp.asarray(dsts))
        now = time.monotonic()
        for h, dst in done:
            del self._inflight_restores[h]
            self.prefix.promote(h, dst)
        self.restores += len(done)
        self._tick_restored += len(done)
        for st in self._slots:
            if st is None or st.restoring is None:
                continue
            still = [(h, off) for h, off in st.restoring
                     if h in self._inflight_restores]
            if len(still) == len(st.restoring):
                continue
            if still:
                st.restoring = still
                continue
            # every block resident: the row becomes an ordinary
            # PREFILLING admission (its pending suffix enters the
            # budget deal next plan); restore latency ends here
            st.restoring = None
            self._m_restore_wait.observe((now - st.admit_t) * 1e3)

    def _restore_fallback(self, handle: int):
        """A queued restore's host entry is gone (the tier lost a race
        with its own LRU eviction — its radix node is already
        unlinked): seeded replay. Every row waiting on the handle is
        rewound to recompute from that chunk's token offset on — its
        pending queue regrows and the ordinary chunked prefill rewrites
        the SAME chain blocks at the same absolute positions, so a peer
        row still restoring a LATER shared chunk into one of those
        blocks observes bit-identical bytes either way (deterministic
        compute). Later chunks the row awaited are dropped from its
        wait list too: the recompute covers them, and their own queued
        restores — if other rows still want them — proceed
        independently. The engine-side prefix-hit attribution is
        corrected; the monotonic registry counter keeps its
        at-admission count (documented slack on a defensive path)."""
        self._inflight_restores.pop(handle, None)
        lens = None
        for s, st in enumerate(self._slots):
            if st is None or st.restoring is None:
                continue
            offs = [off for h, off in st.restoring if h == handle]
            if not offs:
                continue
            new_cached = offs[0]
            self.prefix_hit_tokens -= st.cached_tokens - new_cached
            st.cached_tokens = new_cached
            st.pending = st.req.prompt[new_cached:]
            st.restoring = [(h, off) for h, off in st.restoring
                            if off < new_cached] or None
            if lens is None:
                # copy-and-rebind (see _CacheLayout.advance)
                lens = self._seq_lens.copy()
            lens[s] = new_cached
        if lens is not None:
            self._seq_lens = lens

    # -- KV-block migration (disaggregated serving) --------------------------

    def _drain_ctrl(self):
        """Service queued control calls (KV export/import from server
        handler threads) at the top of each step: the pool, radix
        index, and cache rebinding are engine-thread-only by design, so
        cross-thread work is marshalled here instead of locked."""
        while self._ctrl:
            try:
                fn, ev, box = self._ctrl.popleft()
            except IndexError:  # pragma: no cover - single consumer
                break
            try:
                box["val"] = fn()
            except BaseException as e:
                box["err"] = e
            finally:
                ev.set()

    def call_in_loop(self, fn, timeout: float = 60.0):
        """Run ``fn()`` on the engine loop thread between ticks and
        return its result (exceptions propagate). The thread-safe entry
        point for :meth:`export_blocks` / :meth:`import_blocks` from
        TCP handler threads; requires the loop (``serve_forever``) — or
        a test driving :meth:`step` — to be running."""
        ev = threading.Event()
        box: dict = {}
        self._ctrl.append((fn, ev, box))
        if not ev.wait(timeout):
            raise TimeoutError(
                f"engine loop did not service the control call within "
                f"{timeout}s (is serve_forever running?)"
            )
        if "err" in box:
            raise box["err"]
        return box.get("val")

    def export_blocks(self, prompt) -> dict:
        """Serialize the cached KV blocks covering ``prompt``'s prefix
        for migration to another replica (the ``export_kv`` wire op;
        engine-thread-only — handler threads go through
        :meth:`call_in_loop`). The radix match yields the device chain
        plus any host-tier suffix; device blocks are gathered with the
        tier's batched :func:`_gather_block_fn` (ALL gathers dispatch
        before the first host copy blocks — one device round trip),
        host chunks are served straight from the spill tier. Contents
        are UNSHARDED whatever the mesh (the gather assembles the
        global view), so a tp=4 prefill replica can feed a tp=1 decode
        replica. Returns ``{"tokens": covered, "blocks": [[leaf
        arrays...] per block]}`` — ``tokens`` is 0 when nothing is
        cached (the caller's seeded-replay fallback prefills from
        scratch; losing the race with eviction is a slow path, never an
        error)."""
        if not self.paged or self.prefix is None:
            return {"tokens": 0, "blocks": []}
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        m = self.prefix.match(prompt)
        gather = _gather_block_fn(self._blk_leaf_idx)
        outs = [gather(self._cache, jnp.int32(b)) for b in m.blocks]
        blocks = [[np.asarray(x) for x in out] for out in outs]
        for h in m.host:
            leaves = (self.host.peek(h) if self.host is not None
                      else None)
            if leaves is None:
                break  # entry evicted under us: export the prefix we have
            blocks.append([np.asarray(a) for a in leaves])
        n = len(blocks)
        self.kv_blocks_exported += n
        self._tick_exported += n
        return {"tokens": n * self.block_size, "blocks": blocks}

    def import_blocks(self, prompt, blocks) -> dict:
        """Install migrated KV blocks for ``prompt``'s prefix (the
        ``import_kv`` wire op; engine-thread-only — handler threads go
        through :meth:`call_in_loop`). With a host tier the contents
        land in the spill pool and the chunks register as HOST-resident
        radix nodes — the first hit admits RESTORING and swaps them in
        through the ordinary pipelined-overlap restore path. Without
        one they scatter straight into freshly allocated device blocks
        (the tier's fixed-width batched :func:`_restore_blocks_fn`,
        re-sharding onto any mesh) and register as ordinary cached
        prefix blocks. Either way the next admission of this prompt
        hits the prefix cache and prefills only the tail — migrated
        streams stay bit-identical to a local run. Chunks already
        cached keep their resident copy; device import never evicts
        live data (it imports at most what free + evictable blocks
        allow). Returns ``{"imported": k, "tokens": k * block_size,
        "mode": "host" | "device"}``."""
        if not self.paged or self.prefix is None:
            raise ValueError(
                "KV import needs a paged engine with the prefix cache "
                "(paged=True, prefix_cache=True)"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        bs = self.block_size
        n = min(len(blocks), int(prompt.size) // bs)
        tpl = jax.tree.leaves(self._cache)
        want = [tuple(tpl[li].shape[1:]) for li in self._blk_leaf_idx]
        for bl in blocks[:n]:
            if (len(bl) != len(want)
                    or any(tuple(np.shape(a)) != w
                           for a, w in zip(bl, want))):
                raise ValueError(
                    f"imported block leaves do not match this engine's "
                    f"paged cache layout (want {len(want)} leaves of "
                    f"shapes {want})"
                )
        if n == 0:
            return {"imported": 0, "tokens": 0, "mode": "none"}
        if self.host is not None:
            handles: List[int] = []
            for leaves in blocks[:n]:
                h, lru_evicted = self.host.put(
                    [np.asarray(a) for a in leaves])
                for he in lru_evicted:
                    for hh in self.prefix.drop_host(he):
                        self.host.discard(hh)
                if h is None:
                    break  # tier full of pinned entries: partial import
                handles.append(h)
            reg = set(self.prefix.insert_host(
                prompt[:len(handles) * bs], handles))
            for h in handles:
                if h not in reg:
                    self.host.discard(h)  # chunk already cached
            k, mode = len(handles), "host"
        else:
            avail = (self.pool.free_count()
                     + self.prefix.evictable_count(self.pool.ref))
            k = min(n, avail)
            if k == 0:
                return {"imported": 0, "tokens": 0, "mode": "device"}
            fresh = self._alloc_blocks(k)
            R = self.scheduler.restore_budget
            restore_f = _restore_blocks_fn(self._blk_leaf_idx)
            i = 0
            while i < k:
                take = min(R, k - i)
                stacked = None
                dsts = np.zeros((R,), np.int32)  # pad -> trash block 0
                for j in range(take):
                    leaves = [np.asarray(a) for a in blocks[i + j]]
                    if stacked is None:
                        stacked = [np.zeros((R,) + a.shape, a.dtype)
                                   for a in leaves]
                    for li, a in enumerate(leaves):
                        stacked[li][j] = a
                    dsts[j] = fresh[i + j]
                self._cache = restore_f(self._cache, stacked,
                                        jnp.asarray(dsts))
                i += take
            registered = set(self.prefix.insert(prompt[:k * bs], fresh))
            dup = [b for b in fresh if b not in registered]
            if dup:
                # chunks another request cached first: the resident
                # copy wins, the duplicate frees (concurrent-miss rule)
                self.pool.free(dup)
            mode = "device"
        self.kv_blocks_imported += k
        self._tick_imported += k
        return {"imported": k, "tokens": k * bs, "mode": mode}

    def _kv_fetched(self, starts, valid, C: int) -> int:
        """K/V positions the attend of one ``[S, C]`` mixed tick copies
        in, all ``S`` rows of it: each row's walk from its cursor
        (``starts``) to the last of its ``valid`` tokens, in whole KV
        tiles, where the slot cache's attend resolves to the
        cursor-bounded kernel (``prefill_kernel``, by backend and
        shape); every position of every row where it is the dense
        attend, reads a dequantised int8 cache, or reads the paged
        layout's gathered view. Host arithmetic on cursors the plan
        already holds."""
        m = self.model
        # (the decode module: it knows the verify window it was cloned
        # with)
        fetched = getattr(self._layout.dm, "kv_positions_fetched", None)
        if fetched is not None:  # the model's own walk over its cache
            return fetched(starts, valid, C)
        L = m.max_len
        H = m.num_heads // self.tp
        Hk = (m.num_kv_heads or m.num_heads) // self.tp
        if (not self.paged and m.cache_dtype == "model"
                and splash_prefill.resolves_to_kernel(
                    self.prefill_kernel, C, H // Hk,
                    m.d_model // m.num_heads, L, Hk)):
            return splash_prefill.fetched_positions(starts, valid, L)
        return self.slots * L

    def _live_count(self, C: int, dealt: int) -> Optional[int]:
        """The count a ``[S, C]`` mixed tick that was ``dealt`` so many
        tokens is packed to (:func:`_packed_count`), or None for the
        full-width program: where the decode module does not declare
        ``packs_live_tokens``, where packing would leave nothing out (a
        ``[S, 1]`` tick; a budget that covers every row's chunk), and
        where this plan overran the count (a scheduler of the user's
        that deals more than its ``tick_token_budget``). A module that
        packs by blocks (``live_block_rows``) is handed ``S * C``
        whatever the budget: it finds the blocks in use on the device."""
        dm = self._layout.dm
        if not getattr(dm, "packs_live_tokens", False):
            return None
        if hasattr(dm, "live_block_rows"):
            return self.slots * C if C > 1 else None
        budget = getattr(self.scheduler, "tick_token_budget", None)
        if budget is None:
            return None
        N = _packed_count(budget, self.slots, C)
        return N if N < self.slots * C and dealt <= N else None

    def _mixed_tick(self):
        """One fused mixed prefill/decode tick, sync mode: plan and
        dispatch, then reconcile immediately (the strictly alternating
        reference loop). ``pipeline=True`` calls the same two halves
        with the NEXT dispatch between them."""
        self._reconcile(self._plan_dispatch_mixed())

    def _upload(self, packed: np.ndarray):
        """One packed control-buffer transfer per tick — and zero when
        the plan is unchanged from the previous tick (the all-decode
        slot-mode steady state): the previous device buffer is
        re-dispatched outright. Safe because the packed buffer is never
        donated and each tick's host array is freshly built (the old
        copy-and-rebind aliasing discipline still guards the raw
        tables/lens arrays used by the monolithic prefill paths)."""
        prev_host, prev_dev = self._packed_prev
        if (prev_host is not None and prev_host.shape == packed.shape
                and np.array_equal(prev_host, packed)):
            return prev_dev
        dev = jnp.asarray(packed)
        self._packed_prev = (packed, dev)
        return dev

    def _plan_dispatch_mixed(self) -> _InflightTick:
        """Plan one mixed tick from host state only — deal the token
        budget (decodes first, then prompt chunks in admission order),
        advance each prefilling row's pending queue and flip rows whose
        last chunk is being fed to DECODING (all host-known) — then
        dispatch ONE ``[S, C]`` valid-length dispatch without touching
        the device results. When no prefill token was dealt the shape
        shrinks to the plain ``[S, 1]`` decode tick; when one was, the
        per-token layers run over the dealt tokens packed to one
        compiled count where :meth:`_live_count` gives one. Returns the
        in-flight record :meth:`_reconcile` later materializes.
        RESTORING rows (host-tier uploads still in flight) are planned
        as idle — valid 0, no budget charge, RNG untouched; their
        restore uploads are issued here, BEFORE the tick's dispatch, so
        the transfer overlaps the in-flight compute."""
        tick_no = self.ticks + len(self._pending) + 1
        with self._phase("plan", tick=tick_no) as plan:
            if self.host is not None:
                self._issue_restores()
            S = self.slots
            cfgs = tuple(
                (st.req.temperature, st.req.top_k, st.req.top_p)
                if st else _IDLE_CFG
                for st in self._slots
            )
            # a decoding row whose budget the unread tick uses up is held
            # (its length finish is known before its last token is read)
            n_dec = sum(1 for st in self._slots
                        if st and st.decoding and st.unplanned > 0)
            pre = sorted(
                ((s, st) for s, st in enumerate(self._slots)
                 if st and not st.decoding and st.restoring is None),
                key=lambda p: p[1].admit_seq,
            )
            takes = self.scheduler.plan_prefill(
                n_dec, [len(st.pending) for _, st in pre], self.prefill_chunk,
                tiers=[st.req.tier for _, st in pre],
            )
            fed_tokens = sum(takes)
            C = self.prefill_chunk if fed_tokens else 1
            fed = np.zeros((S, C), np.int32)
            valid = np.zeros((S,), np.int32)
            sample_mask = np.zeros((S,), np.int32)
            rows: List[Optional[tuple]] = [None] * S
            # work the model requires of this tick, against the query
            # positions the dispatch computes whatever was dealt (S x C,
            # or the packed count): (query, key) pairs attended and K/V
            # positions read, live rows only
            attended = key_positions = 0
            # the cursor every live row's attend starts from: what bounds
            # the K/V the dispatch copies in
            starts = np.fromiter((st.cursor if st else 0
                                  for st in self._slots), np.int64, S)
            for s, st in enumerate(self._slots):
                if st is None:
                    # idle rows sample greedily into the void and feed
                    # nothing (valid 0): the row writes no K/V, its parked
                    # cursor holds, and its attend walks no cache
                    sample_mask[s] = 1
                elif st.decoding and st.unplanned > 0:
                    valid[s] = 1
                    sample_mask[s] = 1
                    rows[s] = ("dec", st, 1)
                    st.cursor += 1
                    st.inflight += 1
                    attended += st.cursor
                    key_positions += st.cursor
                # else: PREFILLING rows are dealt below; RESTORING rows
                # and rows held for the read of their last token stay at
                # valid 0 / sample 0 — the row writes nothing, its cursor
                # holds, and its RNG chain is untouched
            for (s, st), take in zip(pre, takes):
                flipped = False
                if take > 0:
                    fed[s, :take] = st.pending[:take]
                    valid[s] = take
                    st.pending = st.pending[take:]
                    # query i of the chunk attends the cached span and the
                    # chunk up to itself
                    attended += take * st.cursor + take * (take + 1) // 2
                    st.cursor += take
                    key_positions += st.cursor
                    if st.pending.size == 0:
                        # last chunk dealt: this dispatch leaves the
                        # prompt-final logits at the row's last valid token
                        # — the NEXT tick samples its first token
                        st.decoding = True
                        flipped = True
                # take == 0: starved this tick — valid stays 0, the row
                # writes nothing and its cursor holds
                rows[s] = ("pre", st, take, flipped)
            # every row advances by what the dispatch consumes of it: 1
            # where it decodes, its chunk where it prefills, 0 otherwise
            packed = self._layout.pack(self, (fed, valid, sample_mask),
                                       advance=valid)
        dealt = n_dec + fed_tokens
        live = self._live_count(C, dealt)
        work = {"attended_tokens": attended,
                "key_positions": key_positions,
                "key_positions_fetched": self._kv_fetched(starts, valid, C),
                "cache_positions": S * self.model.max_len,
                # the positions the per-token layers run over, and the
                # attend's beside them: they differ on a packed tick
                "query_positions": live or S * C,
                "attend_query_positions": S * C}
        block = getattr(self._layout.dm, "live_block_rows", None)
        if live is not None and block is not None:
            # a module that packs by blocks runs those that hold a token
            work["live_blocks"] = -(-dealt // block(live))
            work["query_positions"] = work["live_blocks"] * block(live)
        by_kind = getattr(self.model, "kv_positions_by_kind", None)
        if by_kind is not None:
            # layers of more than one kind: what each kind's attends
            # copy in, summed over the layers of the kind
            work.update(by_kind(starts, valid, C))
        topk = getattr(self.model, "index_topk", None)
        if topk is not None:
            # a learned selection over the cache: positions the indexer
            # scores for the live queries (query t scores its t + 1), and
            # the positions their attend is then allowed, min(t + 1,
            # topk) a query
            seen = (starts[:, None] + 1 + np.arange(C))[
                np.arange(C) < valid[:, None]]
            work["index_positions_scored"] = int(seen.sum())
            work["keys_selected"] = int(np.minimum(seen, topk).sum())
        return self._dispatch(tick_no, plan, cfgs, packed, rows,
                              n_dec=n_dec, fed_tokens=fed_tokens, chunk=C,
                              live=live, work=work)

    def _dispatch(self, tick_no: int, plan: _Phase, cfgs, packed, rows, *,
                  n_dec: int, fed_tokens: int = 0,
                  chunk: Optional[int] = None,
                  live: Optional[int] = None,
                  multi_k: Optional[int] = None,
                  work: Optional[dict] = None, drafts=None,
                  spec_rows=None, **spec_rec) -> _InflightTick:
        """The one place a planned tick reaches the device: upload its
        control buffer (``packed``; None is the slot layout's plain
        decode tick, which takes none and uploads nothing), look the
        program up by what was planned — a verify window where
        ``spec_rows`` is given (``chunk`` wide, with ``drafts`` the
        host's ``[S, k]`` proposals unless a draft model makes them on
        the device), a ``multi_k``-step window, a mixed tick ``chunk``
        wide (packed to ``live`` tokens where the plan says so), else
        the plain decode tick — call it, and return the
        in-flight record of its outputs. The donated cache, logits and
        RNG chains are rebound by the very statement that donates them,
        here and nowhere else (the donation-safety pass holds this
        function to it)."""
        span = {"n_dec": n_dec, "fed_tokens": fed_tokens}
        if chunk is not None:
            span["chunk"] = chunk
        if multi_k is not None:
            span["multi_k"] = multi_k
        if work is not None:
            span.update(work)
        with self._phase("upload", tick=tick_no) as upload:
            operands = [] if packed is None else [self._upload(packed)]
            if spec_rows is not None and self.draft_kind == "ngram":
                operands.append(jnp.asarray(drafts))
        with self._phase("dispatch", tick=tick_no, **span) as dispatch:
            self._clock.dispatching()
            if spec_rows is not None and self.draft_kind == "mtp":
                # the drafts are on the device, where the last verify
                # tick's module left them
                operands.append(self._mtp_state)
                tick = _mtp_verify_fn(self._layout, cfgs, chunk, self._ctx,
                                      live)
            elif spec_rows is not None:
                if self.draft_kind == "model":
                    q_probs, draft_dev = self._run_draft(cfgs, spec_rows)
                    operands += [draft_dev, q_probs]
                else:
                    operands.append(jnp.zeros((1,), jnp.float32))
                tick = _spec_verify_fn(self._layout, cfgs, chunk,
                                       self.spec_k,
                                       self.draft_kind == "ngram",
                                       self._ctx)
            elif multi_k is not None:
                tick = _multi_tick_fn(self._layout, cfgs, multi_k,
                                      self._ctx)
            elif chunk is not None:
                tick = _mixed_tick_fn(self._layout, cfgs, chunk, self._ctx,
                                      live)
            else:
                tick = _tick_fn(self._layout, cfgs, self._ctx)
            # ``acc``: a verify window's accepted-prefix lengths, a
            # multi-step window's per-row counts
            (self._cache, self._last_logits, toks, *acc,
             self._rngs) = tick(
                self._params_only, self._cache, self._last_logits,
                self._rngs, *operands,
            )
            if spec_rows is not None and self.draft_kind == "mtp":
                # what the module left on the device for the next tick
                self._mtp_state = acc.pop()
        rec = _InflightTick(
            toks=toks, rows=rows, tick=tick_no, plan_ms=plan.ms,
            upload_ms=upload.ms, dispatch_ms=upload.ms + dispatch.ms,
            n_dec=n_dec, fed_tokens=fed_tokens, chunk=chunk, work=work,
            multi_k=multi_k, acc=acc[0] if acc else None,
            program=("spec" if spec_rows is not None
                     else "multi" if multi_k is not None
                     else "mixed" if fed_tokens else "decode"),
            **spec_rec,
        )
        self._clock.dispatched(rec)
        return rec

    def _reconcile(self, rec: _InflightTick):
        """Materialize one dispatched tick and settle the host side:
        block on its token readback (in pipelined mode the device is
        already running the NEXT tick, so this wait shrinks by whatever
        the overlap hid), stream each planned row's token, complete
        EOS'd/exhausted rows, drop overrun tokens whose row finished in
        an earlier reconcile, and record telemetry + the flight
        snapshot."""
        with self._phase("wait", tick=rec.tick) as wait:
            self._clock.read_begins(rec)
            # forces completion of the tick
            toks_host = np.asarray(rec.toks)
            clock = self._clock.read_ends(rec)
            counts_host = (np.asarray(rec.acc) if rec.multi_k is not None
                           else None)
            counters = getattr(self.model, "tick_counters", ())
            if counters and rec.work is not None:
                # what the model counted on the device rides behind the
                # S tokens (see _mixed_tick_fn)
                rec.work.update(_counter_work(
                    self.model, toks_host[self.slots:].tolist()))
            # the device buffers are freed here, on the read's side of
            # the boundary (a millisecond of this thread's time on a
            # v5e, PR 25), not wherever the record happens to die
            rec.toks = rec.acc = None
        wait_ms = wait.ms
        with self._phase("stream", tick=rec.tick) as stream:
            self.ticks += 1
            occupancy = sum(st is not None for st in self._slots)
            self._occ_sum += occupancy
            now = time.monotonic()
            # what the flight record has called device_ms since the
            # alternating loop: the dispatch call plus what the overlap
            # left of the read. The device's own time for the tick is
            # the clock's
            device_ms = rec.dispatch_ms + wait_ms
            device_tick_ms = clock[0]
            k = rec.multi_k or 1
            # multi-step windows: one readback carries up to k tokens per
            # row, each produced one scan step apart — attribute per-token
            # timestamps across the window's device span so the per-tier
            # ITL histograms see k gaps of ~device_tick_ms/k, not one lump
            # and k-1 zeros (no k-wide ITL spikes in the QoS stats)
            step_s = (device_tick_ms / 1e3) / k
            window_t0 = now - (k - 1) * step_s
            emitted = 0
            overrun = 0
            for s, row in enumerate(rec.rows):
                if row is None:
                    continue
                st = row[1]
                if self._slots[s] is not st:
                    # late finish: this row's request completed while the
                    # tick was in flight (reconciled out of an earlier
                    # record) — its optimistically computed token is an
                    # overrun, dropped before any consumer sees it. RNG
                    # parity holds because the chain died with the request
                    # (the refill reseeds the slot's key).
                    if row[0] == "dec":
                        overrun += (1 if counts_host is None
                                    else int(counts_host[s]))
                    continue
                if row[0] == "pre":
                    if row[3]:  # the prompt's last chunk landed this tick
                        req = st.req
                        req.prefill_done_t = now
                        prefill_ms = (now - st.admit_t) * 1e3
                        self.tracer.record(
                            req.trace_id, "prefill", st.admit_t,
                            prefill_ms, slot=s,
                            prompt_tokens=int(req.prompt.size),
                            cached_tokens=st.cached_tokens,
                            chunk=self.prefill_chunk,
                            wv=self.weight_version,
                        )
                        self._m_prefill_ms.observe(prefill_ms)
                    continue
                st.inflight -= row[2]
                if counts_host is None:
                    e, _ = self._stream_row(s, st, [int(toks_host[s])], now)
                else:
                    # the on-device stop mask already froze the row at its
                    # EOS (or at lim); n is exactly the tokens it emitted.
                    # _stream_row's own trim still applies — a pipelined
                    # window planned against a stale `remaining` can carry
                    # more device tokens than the row has budget left, the
                    # same optimism the late-EOS path drops — and the
                    # trimmed tail counts as overrun
                    n = int(counts_host[s])
                    times = [window_t0 + j * step_s for j in range(n)]
                    e, _ = self._stream_row(
                        s, st, toks_host[s, :n].tolist(), now, times=times)
                    overrun += n - e
                emitted += e
            if overrun:
                self.overrun_tokens += overrun
                self._m_overrun.inc(overrun)
            queue_depth = self.scheduler.depth()
            self._m_ticks.inc()
            self._m_tokens.inc(emitted)
            self._m_occupancy.set(sum(st is not None for st in self._slots))
            self._m_device_wait.observe(wait_ms)
            self.dispatches += 1
            self._m_dispatches.inc()
            self._m_tokens_per_dispatch.observe(emitted)
            if rec.chunk is not None and rec.fed_tokens + rec.n_dec > 0:
                self._m_prefill_frac.observe(
                    rec.fed_tokens / (rec.fed_tokens + rec.n_dec))
            # serving_token_ms stays a PER-TOKEN series: a k-step window's
            # device span covers k sampled tokens per live row
            self._observe_token_ms(
                device_tick_ms, k, emitted, occupancy, queue_depth,
                **({"prefill_tokens": rec.fed_tokens}
                   if rec.chunk is not None else {}))
        self._record_tick(
            rec, device_ms=device_ms, clock=clock, stream_ms=stream.ms,
            emitted=emitted, occupancy=occupancy,
            queue_depth=queue_depth, device_wait_ms=wait_ms,
            overrun=overrun,
        )

    def _observe_token_ms(self, device_tick_ms: float, k: int, emitted: int,
                          occupancy: int, queue_depth: int, **log_kw):
        """One tick's device time (the device clock's) a token, ``k``
        of them a row in a multi-step window: into the registry, the
        latest tick's token rate, what :meth:`stats` keeps, and the
        per-tick row of a writer the caller handed."""
        token_ms = device_tick_ms / k
        self._m_tick_ms.observe(token_ms)
        if device_tick_ms > 0:
            self._m_decode_tps.set(
                round(emitted / (device_tick_ms / 1e3), 3))
        self._token_ms_recent.append(round(token_ms, 3))
        if self.metrics is not None:
            self.metrics.log(
                step=self.ticks, occupancy=occupancy,
                queue_depth=queue_depth, token_ms=round(token_ms, 3),
                **log_kw)

    def _stream_row(self, s: int, st: _SlotState, toks_row, now,
                    defer: Optional[list] = None, times=None):
        """Emit one row's tick tokens to its consumer stream, stopping
        at EOS or budget exhaustion (which completes the slot). Shared
        by every tick path. ``defer`` switches to the pipelined-spec
        discipline: bookkeeping (remaining, n_emitted, completion,
        slot freeing) happens NOW — the next plan needs it — while the
        consumer-visible emission (stream puts, TTFT/ITL marks, the
        finish sentinel) is queued for :meth:`_flush_emissions` after
        the next dispatch. ``times`` (multi-step windows) carries one
        timestamp per token so latency histograms see the window's
        per-token cadence instead of one lump at reconcile."""
        req = st.req
        take: List[int] = []
        done = False
        reason = None
        for tok in toks_row:
            take.append(tok)
            req.n_emitted += 1
            st.remaining -= 1
            self.tokens_generated += 1
            if req.eos_id is not None and tok == req.eos_id:
                done, reason = True, "eos"
                break
            if st.remaining == 0:
                done, reason = True, "length"
                break
        if defer is None:
            self._emit_now(req, take, now, times)
        else:
            defer.append(("toks", req, take))
        if done:
            self._complete(s, reason, defer=defer)
        return len(take), done

    def _emit_now(self, req: Request, toks, now, times=None):
        for i, tok in enumerate(toks):
            t = now if times is None else times[i]
            if req.last_token_t is not None and t < req.last_token_t:
                # interpolated window timestamps never run time
                # backwards across a reconcile boundary (a pipelined
                # window can be dispatched before the previous one's
                # tokens were stamped)
                t = req.last_token_t
            if req.first_token_t is None:
                req.first_token_t = t
                ttft_ms = (t - req.submit_t) * 1e3
                self._m_ttft_ms.observe(ttft_ms, exemplar=req.trace_id)
                self._m_qos_ttft.labels(tier=req.tier).observe(ttft_ms)
            else:
                itl_ms = (t - req.last_token_t) * 1e3
                # the exemplar joins the latency tail back to its
                # trace: p99 now names a request you can `report
                # --trace`
                self._m_itl_ms.observe(itl_ms, exemplar=req.trace_id)
                self._m_qos_itl.labels(tier=req.tier).observe(itl_ms)
            req.last_token_t = t
            req.stream._put(tok)

    def _flush_emissions(self, defer: list):
        """Deliver deferred token puts and finish sentinels (pipelined
        spec mode), in the exact order bookkeeping produced them — a
        request's finish always lands after its final tokens."""
        if not defer:
            return
        now = time.monotonic()
        for item in defer:
            if item[0] == "toks":
                self._emit_now(item[1], item[2], now)
            else:
                self._notify_finish(item[1], item[2], item[3])

    # -- speculative decoding (draft-assisted verify ticks) ------------------

    def _run_draft(self, cfgs, spec_rows):
        """Draft-model pass for one speculative tick: ONE catch-up feed
        (each row's queue of true tokens the draft hasn't consumed —
        prompt chunks after admission, the 1-2 tokens emitted since
        the last window in steady state — with any rejected-proposal
        cursor overshoot rewound in the same dispatch), then ``spec_k``
        proposal steps, each sampling one draft token per speculating
        row and feeding it (the k-th is sample-only). Returns
        ``(q_probs [S, k, V], draft_toks [S, k])`` on device — the
        proposals never round-trip the host."""
        S, k = self.slots, self.spec_k
        feed_rows = [
            (s, st) for s, st in enumerate(self._slots)
            if st is not None and st.draft_queue is not None
            and (st.draft_queue.size > 0 or st.draft_rewind > 0)
        ]
        # full-shape dummies: a no-proposal tick still traces the
        # verify fn's q lookups for sampled rows (masked to no effect
        # by their zero draft counts)
        none_q = jnp.zeros((S, k, self.model.vocab_size), jnp.float32)
        none_d = jnp.zeros((S, k), jnp.int32)
        if not feed_rows and not spec_rows:
            return none_q, none_d
        # steady state feeds at most 2 lag tokens per row; only prompt
        # catch-up widens the feed to chunk size (two compiled shapes)
        need = max((int(st.draft_queue.size) for _, st in feed_rows),
                   default=0)
        Wd = 2 if need <= 2 else max(self.prefill_chunk, 2)
        dfed = np.zeros((S, Wd), np.int32)
        dvalid = np.zeros((S,), np.int32)
        rewind = np.zeros((S,), np.int32)
        for s, st in feed_rows:
            take = min(Wd, int(st.draft_queue.size))
            dfed[s, :take] = st.draft_queue[:take]
            dvalid[s] = take
            rewind[s] = st.draft_rewind
            st.draft_queue = st.draft_queue[take:]
            st.draft_rewind = 0
        feed = _draft_feed_fn(self._dm_draft, self._draft_ctx)
        self._draft_cache, logits = feed(
            self._draft_params_only, self._draft_cache,
            jnp.asarray(dfed), jnp.asarray(dvalid), jnp.asarray(rewind))
        if not spec_rows:
            return none_q, none_d
        spec_mask = np.zeros((S,), bool)
        for s, _ in spec_rows:
            spec_mask[s] = True
        step = _draft_step_fn(self._dm_draft, cfgs, self._draft_ctx)
        sm = jnp.asarray(spec_mask)
        feed_on = jnp.asarray(spec_mask.astype(np.int32))
        feed_off = jnp.zeros((S,), jnp.int32)
        toks_l, qs_l = [], []
        for i in range(k):
            (self._draft_cache, logits, tok, q,
             self._draft_rngs) = step(
                self._draft_params_only, self._draft_cache, logits,
                self._draft_rngs,
                feed_on if i < k - 1 else feed_off, sm)
            toks_l.append(tok)
            qs_l.append(q)
        return jnp.stack(qs_l, axis=1), jnp.stack(toks_l, axis=1)

    def _spec_tick(self):
        """One speculative mixed tick, sync mode: plan+dispatch, then
        reconcile immediately with inline emission."""
        self._reconcile_spec(self._plan_dispatch_spec(), None)

    def _plan_dispatch_spec(self) -> _InflightTick:
        """Plan one speculative verify tick: per-row verify windows
        (pending token + granted draft width) and prompt chunks under
        the shared token budget, run the drafter (model steps or
        host-side n-gram lookup), and dispatch the fused ``[S, W]``
        verify with per-row rejection sampling and in-dispatch
        rollback. Acceptance-length variation changes only traced
        values — steady state compiles exactly two shapes (``[S,
        k+1]`` all-decode, ``[S, max(C, k+1)]`` with chunks), like the
        non-speculative mixed tick. Host-tier restore uploads are
        issued first, same as the plain mixed plan; RESTORING rows are
        planned idle."""
        tick_no = self.ticks + len(self._pending) + 1
        with self._phase("plan", tick=tick_no) as plan:
            if self.host is not None:
                self._issue_restores()
            S, k = self.slots, self.spec_k
            cfgs = tuple(
                (st.req.temperature, st.req.top_k, st.req.top_p)
                if st else _IDLE_CFG
                for st in self._slots
            )
            pre = sorted(
                ((s, st) for s, st in enumerate(self._slots)
                 if st and not st.decoding and st.restoring is None),
                key=lambda p: p[1].admit_seq,
            )
            mtp = self.draft_kind == "mtp"
            # (a tick ahead: a row whose budget the unread tick may use
            # up, every draft of it accepted, is held)
            dec = [(s, st) for s, st in enumerate(self._slots)
                   if st and st.decoding and st.unplanned > 0]
            # rows eligible to speculate: a pending token (host-known, or
            # with the module's draft on the device), room for at least
            # one draft, and a drafter able to propose (the n-gram index
            # found a match / the draft model is caught up / the module
            # drafted when the pending token was sampled)
            spec_rows, want = [], []
            ngram_toks = {}
            for s, st in dec:
                if st.pending_tok is None:
                    continue  # transition row: samples its first token
                w = min(k, st.unplanned - 1)
                if self.draft_kind == "ngram":
                    toks, found = _ngram_propose(st.history, k,
                                                 self.ngram_max)
                    ngram_toks[s] = toks
                    w = min(w, found)
                elif (not mtp and st.draft_queue is not None
                      and st.draft_queue.size > 2):
                    w = 0  # draft still consuming the prompt
                if w > 0:
                    spec_rows.append((s, st))
                    want.append(w)
            spec_set = {s for s, _ in spec_rows}
            takes, widths = self.scheduler.plan_spec(
                len(dec), [len(st.pending) for _, st in pre],
                self.prefill_chunk, want,
                tiers=[st.req.tier for _, st in pre],
            )
            fed_tokens = sum(takes)
            W = max(self.prefill_chunk, k + 1) if fed_tokens else k + 1
            fed = np.zeros((S, W), np.int32)
            valid = np.zeros((S,), np.int32)
            n_forced = np.zeros((S,), np.int32)
            sample_mask = np.zeros((S,), np.int32)
            draft_np = np.zeros((S, k), np.int32)
            granted = np.zeros((S,), np.int32)
            # draft="mtp": the prompt's next token behind a chunk (the
            # module's input at the chunk's last position), and the rows
            # whose pending token is the device's
            next_tok = np.zeros((S,), np.int32)
            use_pending = np.zeros((S,), np.int32)
            rows: List[Optional[tuple]] = [None] * S
            starts = np.fromiter((st.cursor if st else 0
                                  for st in self._slots), np.int64, S)
            for s, st in dec:
                sample_mask[s] = 1
                rows[s] = ("dec", st)
                if st.pending_tok is not None:
                    fed[s, 0] = st.pending_tok
                    n_forced[s] = 1
                    valid[s] = 1
                    use_pending[s] = mtp
            for (s, st), w in zip(spec_rows, widths):
                valid[s] = 1 + w
                granted[s] = w
                if self.draft_kind == "ngram":
                    draft_np[s] = ngram_toks[s]
            for (s, st), take in zip(pre, takes):
                flipped = False
                if take > 0:
                    fed[s, :take] = st.pending[:take]
                    valid[s] = take
                    n_forced[s] = take
                    st.pending = st.pending[take:]
                    if st.pending.size == 0:
                        # last chunk dealt: the next tick is this row's
                        # transition tick (samples its first token, which
                        # becomes the pending token); with the module
                        # drafting, this tick samples it, since the
                        # module's row at the prompt's last position
                        # needs it
                        st.decoding = True
                        flipped = True
                        if mtp:
                            sample_mask[s] = 1
                            st.pending_tok = -1  # the device holds it
                    elif mtp:
                        next_tok[s] = st.pending[0]
                rows[s] = ("pre", st, take, flipped)
            for s, row in enumerate(rows):
                if row is None:
                    continue
                if sample_mask[s]:
                    # the tokens the unread ticks may still sample for
                    # the row, every draft accepted: what the next plan
                    # holds its budget against
                    row[1].inflight += 1 + int(granted[s])
                if mtp:
                    # what is certain of the row's advance (the work
                    # counters read it); an accepted draft is added when
                    # the tick is read
                    row[1].cursor += int(n_forced[s])
            # host-owned cursors hold here: how far a row advances is
            # known only when its accepted length is read back
            # (_reconcile_spec)
            fields = (fed, valid, n_forced, sample_mask)
            packed = self._layout.pack(
                self, fields + (next_tok, use_pending) if mtp else fields)
        work = live = None
        if mtp:
            live = self._live_count(W, int(valid.sum()))
            work = self._window_work(starts, valid, W, live)
            work["draft_tokens"] = int(granted.sum())
        return self._dispatch(tick_no, plan, cfgs, packed, rows,
                              n_dec=len(dec), fed_tokens=fed_tokens,
                              chunk=W, live=live, work=work,
                              drafts=draft_np,
                              spec_rows=spec_rows, n_forced=n_forced,
                              granted=granted, spec_set=spec_set)

    def _window_work(self, starts, valid, W: int,
                     live: Optional[int]) -> dict:
        """What a verify tick of a model that drafts for itself computes
        against what it was dealt, as :meth:`_plan_dispatch_mixed`
        counts a mixed tick: a window's draft position is a query like
        any other (``window_positions``: every position the tick ran,
        forced or drafted; the module runs the same,
        ``mtp_positions_fed``), and whether it was worth running shows
        in ``decode_tokens``, which a verify tick's record counts as the
        tokens emitted. ``starts`` are the host's cursors: behind the
        device's by the drafts an unread tick accepts."""
        S = self.slots
        live_rows = valid > 0
        ends = (starts + valid)[live_rows]
        first = starts[live_rows]
        return {
            # query i of a row's run attends the cached span and the run
            # up to itself
            "attended_tokens": int(
                (ends * (ends + 1) - first * (first + 1)).sum() // 2),
            "key_positions": int(ends.sum()),
            "key_positions_fetched": self._kv_fetched(starts, valid, W),
            "cache_positions": S * self.model.max_len,
            "query_positions": live or S * W,
            "attend_query_positions": S * W,
            "window_positions": int(valid.sum()),
            "mtp_positions_fed": int(valid.sum())}

    def _reconcile_spec(self, rec: _InflightTick,
                        defer: Optional[list]):
        """Materialize one verify tick and settle the host side: read
        back tokens AND accepted-prefix lengths (the next plan depends
        on both — pending tokens, n-gram history, paged cursor
        arithmetic), emit each row's accepted prefix plus its extra
        token, and do the draft-cache lag bookkeeping. With ``defer``
        (pipelined mode) the consumer-visible emission is queued and
        flushed after the NEXT dispatch; all scheduling state still
        settles here."""
        k = self.spec_k
        with self._phase("wait", tick=rec.tick) as wait:
            self._clock.read_begins(rec)
            # forces completion of the tick
            toks_host = np.asarray(rec.toks)
            clock = self._clock.read_ends(rec)
            acc_host = np.asarray(rec.acc)
            counters = getattr(self.model, "tick_counters", ())
            if counters and rec.work is not None:
                # what the model counted on the device rides behind the
                # S accepted lengths (see _mtp_verify_fn)
                rec.work.update(_counter_work(
                    self.model, acc_host[self.slots:].tolist()))
                acc_host = acc_host[:self.slots]
            rec.toks = rec.acc = None  # freed here, as in _reconcile
        wait_ms = wait.ms
        with self._phase("stream", tick=rec.tick) as stream:
            # each row keeps only its forced tokens plus the accepted
            # prefix — where the cursors are the host's, the
            # rejected-suffix rollback IS this arithmetic
            self._layout.advance(self, rec.n_forced + acc_host)
            self.ticks += 1
            occupancy = sum(st is not None for st in self._slots)
            self._occ_sum += occupancy
            now = time.monotonic()
            emitted = 0
            overrun = 0
            proposed = int(rec.granted.sum())
            accepted = 0
            mtp = self.draft_kind == "mtp"
            for s, row in enumerate(rec.rows):
                if row is None:
                    continue
                st = row[1]
                # a prompt's last chunk samples the row's first token
                # where the model's own module drafts
                samples = row[0] == "dec" or (mtp and row[3])
                a = int(acc_host[s]) if samples else 0
                if self._slots[s] is not st:
                    # late finish: the row's request ended (an eos) in
                    # the tick before this one, read after this one was
                    # dispatched. Only the loop a tick ahead gets here
                    if samples:
                        overrun += a + 1
                    continue
                if samples:
                    st.inflight -= 1 + int(rec.granted[s])
                    st.cursor += a
                if row[0] == "pre":
                    if row[3]:
                        req = st.req
                        req.prefill_done_t = now
                        prefill_ms = (now - st.admit_t) * 1e3
                        self.tracer.record(
                            req.trace_id, "prefill", st.admit_t,
                            prefill_ms, slot=s,
                            prompt_tokens=int(req.prompt.size),
                            cached_tokens=st.cached_tokens,
                            chunk=self.prefill_chunk,
                            wv=self.weight_version,
                        )
                        self._m_prefill_ms.observe(prefill_ms)
                    if not samples:
                        continue
                if rec.granted[s] > 0:
                    accepted += a
                    self._m_accept_len.observe(a)
                toks_row = [int(t) for t in toks_host[s, :a + 1]]
                e, done = self._stream_row(s, st, toks_row, now, defer)
                emitted += e
                if done:
                    continue
                st.pending_tok = toks_row[-1]
                if st.history is not None:
                    st.history = np.concatenate(
                        [st.history, np.asarray(toks_row, np.int32)])
                if self.draft_kind == "model":
                    lag = []
                    if s in rec.spec_set and a == k:
                        # every proposal survived: the k-th was accepted
                        # but never fed to the draft (only d_1..d_{k-1}
                        # were) — it precedes the extra token in the queue
                        lag.append(int(toks_host[s, k - 1]))
                    lag.append(st.pending_tok)
                    lag_np = np.asarray(lag, np.int32)
                    st.draft_queue = (
                        np.concatenate([st.draft_queue, lag_np])
                        if st.draft_queue.size else lag_np)
                    if s in rec.spec_set:
                        st.draft_rewind = max(k - 1 - a, 0)
            self.draft_tokens_proposed += proposed
            self.draft_tokens_accepted += accepted
            self._m_draft_tokens.inc(proposed)
            self._m_accepted_tokens.inc(accepted)
            if overrun:
                self.overrun_tokens += overrun
                self._m_overrun.inc(overrun)
            queue_depth = self.scheduler.depth()
            device_ms = rec.dispatch_ms + wait_ms
            self._m_ticks.inc()
            self._m_tokens.inc(emitted)
            self._m_occupancy.set(sum(st is not None for st in self._slots))
            self._m_device_wait.observe(wait_ms)
            self.dispatches += 1
            self._m_dispatches.inc()
            self._m_tokens_per_dispatch.observe(emitted)
            if rec.fed_tokens + rec.n_dec > 0:
                self._m_prefill_frac.observe(
                    rec.fed_tokens / (rec.fed_tokens + rec.n_dec))
            self._observe_token_ms(
                clock[0], 1, emitted, occupancy, queue_depth,
                prefill_tokens=rec.fed_tokens,
                draft_tokens=proposed, accepted_tokens=accepted)
        self._record_tick(
            rec, device_ms=device_ms, clock=clock, stream_ms=stream.ms,
            emitted=emitted, occupancy=occupancy,
            queue_depth=queue_depth, device_wait_ms=wait_ms,
            draft_tokens=proposed, accepted_tokens=accepted,
            overrun=overrun,
        )

    def _decode_tick(self):
        """One plain decode tick (monolithic-prefill mode), sync:
        plan+dispatch then reconcile immediately."""
        self._reconcile(self._plan_dispatch_decode())

    def _plan_dispatch_decode(self) -> _InflightTick:
        tick_no = self.ticks + len(self._pending) + 1
        with self._phase("plan", tick=tick_no) as plan:
            cfgs = tuple(
                (st.req.temperature, st.req.top_k, st.req.top_p)
                if st else _IDLE_CFG
                for st in self._slots
            )
            rows: List[Optional[tuple]] = [
                ("dec", st, 1) if st is not None else None
                for st in self._slots
            ]
            n_dec = sum(1 for r in rows if r is not None)
            # this tick has no control buffer to hold a row back with:
            # a row whose budget the unread tick uses up overruns here
            for st in self._slots:
                if st is not None:
                    st.inflight += 1
            # the tick writes each live row's K/V at its cursor
            packed = self._layout.pack(self, advance=np.fromiter(
                (st is not None for st in self._slots), np.int32,
                self.slots))
        return self._dispatch(tick_no, plan, cfgs, packed, rows,
                              n_dec=n_dec)

    # -- device-resident multi-step decode -----------------------------------

    def _multi_gate(self) -> int:
        """Decide this step's window width: the granted k (> 1) when
        the engine is in all-decode steady state, else 1 with the
        blocking condition counted as a fallback reason. Steady state
        means every occupied slot is DECODING and nothing host-side
        needs a tick boundary within the window: no speculative
        verify (its plan needs each window's accepted tokens), no
        staged control call (weight push / KV export must land between
        dispatches), no host-tier restore queued, in flight, or
        holding a row, and no prompt chunk to deal. A future
        constrained/filtered row gates here too — any row whose
        sampling needs per-token host work is not steady state. The
        scheduler has the final word: a window charges every decoding
        row one budget token per step, and a grant the budget cannot
        cover falls back rather than starving prefill admissions."""
        if self.multi_step_k <= 1:
            return 1
        if self.spec:
            reason = "spec"
        elif self._ctrl:
            reason = "control"
        elif (self._restore_queue or self._inflight_restores
              or any(st is not None and st.restoring is not None
                     for st in self._slots)):
            reason = "restore"
        elif any(st is not None and not st.decoding
                 for st in self._slots):
            reason = "prefill"
        else:
            n_dec = sum(1 for st in self._slots if st is not None)
            granted = self.scheduler.plan_multi_step(
                n_dec, self.multi_step_k)
            if granted > 1:
                return granted
            reason = "budget"
        self.multi_step_fallbacks[reason] = (
            self.multi_step_fallbacks.get(reason, 0) + 1)
        self._m_multi_fallbacks.labels(reason=reason).inc()
        return 1

    def _plan_dispatch_multi(self, k: int) -> _InflightTick:
        """Plan and dispatch ONE k-step decode window (all-decode
        steady state: every occupied slot is decoding, the gate said
        so). The packed buffer carries each row's EOS id and its
        emission limit ``min(k, remaining)`` — in steady state both are
        constant, so the upload dedup re-dispatches the previous device
        buffer and the slot path stays zero-upload. Paged cursors
        advance by the worst case ``lim`` NOW (the next pipelined plan
        must see the window's writes); a row that stops early always
        COMPLETES at this window's reconcile — EOS or emptied budget
        are the only stop reasons — where :meth:`_complete` returns its
        whole block chain to the pool and zeroes its cursor in the same
        reconcile, the PR-7 worst-case-rollback discipline."""
        tick_no = self.ticks + len(self._pending) + 1
        with self._phase("plan", tick=tick_no) as plan:
            S = self.slots
            cfgs = tuple(
                (st.req.temperature, st.req.top_k, st.req.top_p)
                if st else _IDLE_CFG
                for st in self._slots
            )
            rows: List[Optional[tuple]] = [None] * S
            n_dec = sum(1 for st in self._slots if st is not None)
            eos = np.full((S,), -1, np.int32)
            lim = np.zeros((S,), np.int32)
            for s, st in enumerate(self._slots):
                if st is None:
                    continue
                if st.req.eos_id is not None:
                    eos[s] = st.req.eos_id
                # what the unread window leaves of the row's budget (0:
                # the row goes quiet on the device, as after its EOS)
                lim[s] = min(k, st.unplanned)
                rows[s] = ("dec", st, int(lim[s]))
                # a row that stops short of lim completes at this
                # window's reconcile: nothing reads its cursor again
                st.cursor += int(lim[s])
                st.inflight += int(lim[s])
            packed = self._layout.pack(self, (eos, lim), advance=lim)
        return self._dispatch(tick_no, plan, cfgs, packed, rows,
                              n_dec=n_dec, multi_k=k)

    def _complete(self, slot: int, reason: str,
                  defer: Optional[list] = None):
        """Free a finished slot NOW (blocks released, row parked, the
        scheduler's head-of-line short-circuit invalidated — the next
        plan/admit must see the capacity), and notify the consumer —
        immediately, or queued behind the row's deferred tokens when
        the pipelined spec loop is emitting after the next dispatch."""
        st = self._slots[slot]
        req = st.req
        req.done_t = time.monotonic()
        if self.paged:
            self._release_blocks(st)
            # copy-and-rebind: park the freed row on the trash block
            tables = self._block_tables.copy()
            tables[slot, :] = 0
            self._block_tables = tables
            lens = self._seq_lens.copy()
            lens[slot] = 0
            self._seq_lens = lens
        self._slots[slot] = None
        self.requests_completed += 1
        # freed capacity (slot, blocks, prefix registrations) may make
        # the queue head admissible again — drop the scheduler's
        # head-blocked short-circuit
        self.scheduler.note_capacity_change()
        if defer is None:
            self._notify_finish(req, reason, slot)
        else:
            defer.append(("finish", req, reason, slot))

    def _notify_finish(self, req: Request, reason: str, slot: int):
        # spans first, then the stream-end sentinel: a client that saw
        # "done" can immediately trace_dump and find the full chain
        decode_t0 = req.prefill_done_t or req.submit_t
        decode_ms = (req.done_t - decode_t0) * 1e3
        device_ms = min(req.device_ms_accum, decode_ms)
        self.tracer.record(
            req.trace_id, "decode", decode_t0, decode_ms,
            slot=slot, tokens=req.n_emitted,
            device_ms=round(device_ms, 3),
            wv=self.weight_version,
        )
        self.tracer.record(
            req.trace_id, "finish", req.done_t, 0.0,
            reason=reason, slot=slot, tokens=req.n_emitted,
            ttft_ms=round((req.first_token_t - req.submit_t) * 1e3, 3),
            wv=self.weight_version,
        )
        # critical-path attribution: the engine-visible phases of this
        # request's wall time (the stream tail and router overhead are
        # observed by the TCP pump / router into the same family)
        admit_t = req.admit_t or req.submit_t
        prefill_done = req.prefill_done_t or admit_t
        phase_ms = (
            ("queue", (admit_t - req.submit_t) * 1e3),
            ("prefill", (prefill_done - admit_t) * 1e3),
            ("device", device_ms),
            ("decode", max(decode_ms - device_ms, 0.0)),
        )
        for ph, ms in phase_ms:
            self._m_cp[ph].observe(ms)
            self._m_qos_critical.labels(tier=req.tier, phase=ph).observe(ms)
        self._m_requests.labels(reason=reason).inc()
        req.stream._finish(reason)
        ttft_ms = round((req.first_token_t - req.submit_t) * 1e3, 3)
        self._ttft_recent.append(ttft_ms)
        if self.metrics is not None:
            self.metrics.summary(
                "request", rid=req.rid, reason=reason,
                tokens=req.n_emitted, ttft_ms=ttft_ms,
                total_ms=round((req.done_t - req.submit_t) * 1e3, 3),
            )

    def _release_blocks(self, st: _SlotState):
        """Finish-time block bookkeeping: register the prompt's full
        blocks in the radix index (future requests hit them), then drop
        this request's references. Blocks at refcount zero stay
        allocated if the index registers them (prefix cache, LRU
        evictable); private blocks — generated tokens, partial prompt
        tails, COW copies past the prompt — go straight back to the
        free list."""
        req = st.req
        if self.prefix is not None:
            n_full = int(req.prompt.size) // self.block_size
            self.prefix.insert(
                req.prompt[:n_full * self.block_size],
                st.blocks[:n_full],
            )
        released = self.pool.decref(st.blocks)
        to_free = [
            b for b in released
            if self.prefix is None or not self.prefix.contains_block(b)
        ]
        if to_free:
            self.pool.free(to_free)

    # -- observability ------------------------------------------------------

    MEM_SAMPLE_EVERY = 32  # ticks between /proc + device-allocator reads
    STATS_RECENT = 4096    # observations stats()' percentiles are over

    def _slot_snaps(self) -> list:
        """Per-slot state for the flight snapshot: None (idle) or a
        small dict — rid, state, tokens left to emit (decode) or prompt
        tokens still pending (prefill)."""
        out = []
        for st in self._slots:
            if st is None:
                out.append(None)
            elif st.decoding:
                out.append({"rid": st.req.rid, "state": "decode",
                            "remaining": st.remaining})
            elif st.restoring is not None:
                out.append({"rid": st.req.rid, "state": "restore",
                            "pending": len(st.restoring),
                            "remaining": st.remaining})
            else:
                out.append({"rid": st.req.rid, "state": "prefill",
                            "pending": int(st.pending.size),
                            "remaining": st.remaining})
        return out

    def _sample_memory(self) -> dict:
        """Host RSS + device allocator watermarks into gauges; returns
        the plain-dict summary for the flight snapshot. Backends
        without ``memory_stats()`` (CPU returns None) are probed once
        and then skipped."""
        rss = self._mem.sample_host()
        if rss is not None:
            self._m_rss.set(rss)
        if self._mem.device_supported is not False:
            try:
                dstats = self._device.memory_stats()
            except Exception:
                dstats = None
            self._mem.sample_device(dstats)
            if self._mem.device_supported:
                if self._mem.device_bytes is not None:
                    self._m_device_mem.set(self._mem.device_bytes)
                self._m_device_peak.set(self._mem.device_peak_bytes)
        return self._mem.summary()

    def _record_tick(self, rec: _InflightTick, *, device_ms: float,
                     clock: tuple, stream_ms: float, emitted: int,
                     occupancy: int,
                     queue_depth: int, device_wait_ms: float,
                     draft_tokens: Optional[int] = None,
                     accepted_tokens: Optional[int] = None,
                     overrun: int = 0):
        """Post-tick runtime introspection + the flight snapshot — the
        ``record`` phase, which closes the tick's period: the snapshot
        gets what every phase took in it. The snapshot build is
        self-timed against tick wall time —
        ``stats()["flight"]["overhead_frac"]`` is that ratio, and
        ``serve_bench --smoke`` asserts it stays under 5%."""
        plan_ms = rec.plan_ms
        device_tick_ms, starved_ms, unasked_ms, clock_err_ms = clock
        # the clock's values ride the span: each estimate lies on the
        # device trace's clock beside the operations it describes; so do
        # the counters the model keeps in a unit of its own (the bytes
        # of expert weights the tick had to read: over the seconds under
        # moe_experts in the same window, the layer's share of the
        # chip's memory rate)
        work = rec.work or {}
        read = {kept: work[kept] for kept, _ in getattr(
            self.model, "tick_counter_units", {}).values() if kept in work}
        with self._phase("record", tick=rec.tick, program=rec.program,
                         device_tick_ms=device_tick_ms,
                         device_starved_ms=starved_ms,
                         device_unasked_ms=unasked_ms, **read):
            self._tick_ns += int((plan_ms + device_ms + stream_ms) * 1e6)
            # runtime introspection runs with or without a recorder (the
            # gauges are its output); only the snapshot build + ring append
            # below counts as flight-recorder overhead
            rec_total = recompiles.total()
            oldest = self.scheduler.oldest_age_s()
            sample_tick = self.ticks % self.MEM_SAMPLE_EVERY == 1
            if sample_tick:
                # gauge refreshes ride the slow cadence: SLO polls are
                # ~1 s apart and ticks are ~ms, so a 32-tick-stale gauge
                # is fresh to every scraper — and the hot path stays lean
                mem = self._sample_memory()
                self._m_recompiles.set(rec_total)
                self._m_oldest_wait.set(round(oldest, 3))
            else:
                mem = None
            # device-compute attribution: split this tick's device time
            # evenly over the rows that were active — summed per request
            # into the critical-path "device" phase (a finished row freed
            # earlier in this step misses its final share; attribution,
            # not accounting)
            if device_tick_ms > 0.0:
                live = [st for st in self._slots if st is not None]
                if live:
                    share = device_tick_ms / len(live)
                    for st in live:
                        st.req.device_ms_accum += share
            snap = None
            t0 = time.perf_counter_ns()
            if self.flight is not None:
                # one flat dict, no rounding: this runs every tick and the
                # smoke bound is 5% of a ~1 ms CPU tick — formatting is the
                # renderer's job, not the hot path's
                snap = {
                    "kind": "tick", "tick": self.ticks,
                    "t": time.monotonic(),
                    "tick_ms": plan_ms + device_ms + stream_ms,
                    "plan_ms": plan_ms, "device_ms": device_ms,
                    "stream_ms": stream_ms,
                    # the device clock: which program ran, the device's
                    # own time for it, and how long the device had
                    # nothing queued before it (late host / nobody asked)
                    "program": rec.program,
                    "device_tick_ms": device_tick_ms,
                    "device_starved_ms": starved_ms,
                    "device_unasked_ms": unasked_ms,
                    "device_clock_err_ms": clock_err_ms,
                    "occupancy": occupancy, "queue_depth": queue_depth,
                    "queue_oldest_wait_s": oldest,
                    # per-tier backlog: a postmortem can show the batch
                    # queue absorbing an overload while interactive stays
                    # shallow (the QoS degradation order, as it happened)
                    "qos_depth": self.scheduler.depth_by_tier(),
                    "budget_limit": self.scheduler.tick_token_budget,
                    # a verify tick's are the tokens it emitted, not the
                    # positions it verified: a rejected draft is waste
                    "decode_tokens": (rec.n_dec if draft_tokens is None
                                      else emitted),
                    "prefill_tokens": rec.fed_tokens, "chunk": rec.chunk,
                    "emitted": emitted,
                    "slots": self._slot_snaps(),
                    "recompiles": rec_total,
                    # the weight set this tick served: a swap between two
                    # snapshots is visible as the version stepping (the
                    # report renderer's w=vN column)
                    "weight_version": self.weight_version,
                    "weight_bytes_held": self.weight_bytes_held,
                    "weight_bytes_handed": self.weight_bytes_handed,
                    **self._cache_bytes,
                }
                if rec.multi_k is not None:
                    # multi-step window: this one dispatch carried up to
                    # multi_k decode steps per row (report's k= column)
                    snap["multi_k"] = rec.multi_k
                if rec.work is not None:
                    # mixed ticks: (query, key) pairs and K/V positions the
                    # dealt tokens required, of the query positions the
                    # dispatch computed (its per-token layers' and its
                    # attend's) and the K/V positions its attend copied in
                    snap.update(rec.work)
                if self.pipeline:
                    snap["pipeline_depth"] = len(self._pending)
                    snap["overrun_tokens"] = overrun
                if draft_tokens is not None:
                    # speculative ticks: proposals entering this tick's
                    # verify windows and how many survived rejection
                    snap["draft_tokens"] = draft_tokens
                    snap["accepted_tokens"] = accepted_tokens
                if mem is not None:
                    snap["mem"] = mem
                if self.paged:
                    # cheap counts every tick; the live/cached refcount
                    # decomposition only on sample ticks (numpy scan)
                    snap["blocks"] = (self.pool.stats() if sample_tick
                                      else {"in_use": self.pool.in_use_count(),
                                            "free": self.pool.free_count()})
                    snap["prefix_hit_tokens"] = self.prefix_hit_tokens
                    if self.host is not None:
                        # tiered KV cache: per-tick swap activity + the
                        # host pool's current footprint
                        snap["demoted"] = self._tick_demoted
                        snap["restored"] = self._tick_restored
                        snap["host_blocks"] = self.host.count()
                    if self._tick_exported or self._tick_imported:
                        # KV-block migration: blocks exported/imported by
                        # control calls serviced since the previous tick
                        snap["kv_exported"] = self._tick_exported
                        snap["kv_imported"] = self._tick_imported
            self._flight_ns += time.perf_counter_ns() - t0
            self._tick_demoted = 0
            self._tick_restored = 0
            self._tick_exported = 0
            self._tick_imported = 0
        # the period ends here: what the engine thread did since the
        # previous tick's record, by phase, and the period's own length
        # (the phases sum to it but for the statements between brackets)
        period = self._phase.take()
        if rec.work is not None:
            self.attended_tokens_total += rec.work["attended_tokens"]
            self.query_positions_total += rec.work["query_positions"]
            self.attend_query_positions_total += rec.work[
                "attend_query_positions"]
            self.packed_ticks_total += (
                rec.work["query_positions"]
                < rec.work["attend_query_positions"])
            self.key_positions_fetched_total += rec.work[
                "key_positions_fetched"]
            self.cache_positions_total += rec.work["cache_positions"]
            self.useful_query_tokens_total += rec.fed_tokens + (
                rec.n_dec if draft_tokens is None else emitted)
            for name in _MODEL_WORK:
                if name in rec.work:
                    self.model_work_totals[name] = (
                        self.model_work_totals.get(name, 0)
                        + rec.work[name])
        if snap is not None:
            # overlap decomposition: device_ms = dispatch_ms (upload_ms
            # + the jitted call returning) + device_wait_ms (time
            # BLOCKED on readback — what pipelining exists to shrink).
            # plan, upload and dispatch are this TICK's (in the
            # pipelined loop they ran one period earlier); ctrl, admit,
            # record, idle and loop are this PERIOD's
            snap.update(
                device_wait_ms=device_wait_ms,
                dispatch_ms=rec.dispatch_ms, upload_ms=rec.upload_ms,
                ctrl_ms=period.get("ctrl", 0.0),
                admit_ms=period.get("admit", 0.0),
                record_ms=period.get("record", 0.0),
                idle_ms=period.get("idle", 0.0),
                loop_ms=period["loop"],
            )
            if self.pipeline and self.spec and self.draft_kind != "mtp":
                # the previous tick's tokens reached their consumers
                # inside this period, behind this tick's dispatch
                snap["deferred_stream_ms"] = (
                    period.get("stream", 0.0) - stream_ms)
            self.flight.record(snap)

    def stats(self) -> dict:
        """Counters + latency percentiles (TTFT and per-token, ms) for
        THIS engine. The process-cumulative view (histograms, labeled
        series) is ``self.registry.collect()`` — served by the TCP
        ``metrics`` op and the HTTP endpoint."""
        qos_depth = self.scheduler.depth_by_tier()
        out = {
            # replica specialization (disaggregated serving): the
            # router classifies replicas into prefill/decode pools from
            # this advertised role; "mixed" serves everything
            "role": self.role,
            "prefill_kernel": self.prefill_kernel,
            "ticks": self.ticks,
            "requests_completed": self.requests_completed,
            "tokens_generated": self.tokens_generated,
            "queue_depth": self.scheduler.depth(),
            "active_slots": sum(1 for st in self._slots if st is not None),
            # graceful-drain state (begin_drain closes admissions; the
            # router routes around draining replicas, deploy tooling
            # polls for drained before stopping the process)
            "draining": self.draining,
            "drained": self.drained,
            # live weight updates: the version currently serving and
            # how many atomic hot swaps this engine has applied — the
            # router's rolling updates poll this for convergence
            "weight_version": self.weight_version,
            "weight_swaps": self.weight_swaps,
            # bytes of the flagship's tree the tick programs read every
            # tick, beside the bytes of the tree as handed: less where
            # the engine holds the model's compute-dtype casts
            "weight_bytes_held": self.weight_bytes_held,
            "weight_bytes_handed": self.weight_bytes_handed,
            "mean_occupancy": (
                round(self._occ_sum / self.ticks, 3) if self.ticks else 0.0
            ),
            # over the latest STATS_RECENT finished requests / ticks
            "ttft_ms": percentiles(list(self._ttft_recent)),
            "token_ms": percentiles(list(self._token_ms_recent)),
            # the device clock since mark_steady(): the device's time by
            # program and the time it had nothing queued (see
            # _DeviceClock)
            **self._clock.stats(),
            # bucket-interpolated stream-gap percentiles; None until two
            # tokens of one stream have been emitted (the registry
            # histogram keeps the full distribution)
            "itl_ms": {
                "p50": self._m_itl_ms.percentile(50),
                "p99": self._m_itl_ms.percentile(99),
                # the most recent tail observation's trace id
                # ({"value", "trace_id", "le"}, or None before any
                # exemplar lands) — feed it to `report --trace`
                "p99_exemplar": self._m_itl_ms.tail_exemplar(),
            },
            "decode_stalls": self._m_decode_stalls.value,
            # device-resident multi-step decode: the configured window
            # width, the per-reason count of planned ticks that fell
            # back to k=1, and the tokens-per-dispatch amortization
            # actually achieved (p50 pinned at the configured k in a
            # true steady state)
            "multi_step_k": self.multi_step_k,
            "multi_step_fallbacks": dict(self.multi_step_fallbacks),
            "dispatches": self.dispatches,
            "tokens_per_dispatch": {
                "p50": self._m_tokens_per_dispatch.percentile(50),
                "p99": self._m_tokens_per_dispatch.percentile(99),
            },
            "queue_oldest_wait_s": round(
                self.scheduler.oldest_age_s(), 3),
            # runtime introspection: process-global jit traces of the
            # serving functions (per fn), and the delta since
            # mark_steady() — nonempty in steady state is a bug
            "recompiles": recompiles.counts(),
            "recompiles_since_mark": self.recompiles_since_mark(),
            "memory": self._mem.summary(),
            # tensor-parallel degree of the tick bodies (1 = single-chip)
            "tp": self.tp,
            # pipelined loop: whether dispatch runs ahead of readback,
            # how long the host actually blocked on the device per tick
            # (the overlap residue), and how many optimistic tokens
            # were dropped at reconciliation (late finishes)
            "pipeline": self.pipeline,
            "device_wait_ms": {
                "p50": self._m_device_wait.percentile(50),
                "p99": self._m_device_wait.percentile(99),
            },
            "overrun_tokens": self.overrun_tokens,
            # ... as a share of every token the ticks sampled for a
            # request: what running a tick ahead costs the device
            "overrun_pct": 100.0 * self.overrun_tokens / max(
                self.tokens_generated + self.overrun_tokens, 1),
            # mixed ticks: (query, key) pairs the dealt tokens required,
            # query positions the dispatches' per-token layers computed
            # (N on a packed tick, else the attend's [S, C] whatever
            # was dealt), and the decode + fed tokens among them
            "attended_tokens_total": self.attended_tokens_total,
            "query_positions_total": self.query_positions_total,
            "attend_query_positions_total":
                self.attend_query_positions_total,
            "packed_ticks_total": self.packed_ticks_total,
            # K/V positions the mixed ticks' attends copied in (every
            # row's walk to its cursor, in whole tiles), of the S x L
            # a dense attend reads every tick
            "key_positions_fetched_total": self.key_positions_fetched_total,
            "cache_positions_total": self.cache_positions_total,
            "useful_query_tokens_total": self.useful_query_tokens_total,
            # only for a model that selects over its cache or routes to
            # experts: index_positions_scored_total, keys_selected_total
            # (beside attended_tokens_total: the pairs a dense attend
            # would have been allowed), routed_here_total over
            # routed_total_total, expert_rows_computed_total,
            # expert_weight_bytes_total (the banks of the held experts
            # that were sent a row, every apply); for a model
            # whose layers differ in kind: full_key_positions_total,
            # window_key_positions_total (K/V positions the attends of
            # each kind copied in, summed over the kind's layers) and
            # cache_bytes_full, cache_bytes_window; for a model with
            # recurrent-state layers: state_rows_stepped_total,
            # chunk_positions_live_total over
            # chunk_positions_computed_total, cache_bytes_state
            **{f"{name}_total": total
               for name, total in self.model_work_totals.items()},
            **self._cache_bytes,
            # engine-side critical-path phases (the stream tail and
            # router overhead land in the same histogram family from
            # the TCP pump / router; one merged chain's exact breakdown
            # is `report --trace <id>` / telemetry.critical_path)
            "critical_path_ms": {
                ph: {"p50": self._m_critical.percentile(50, phase=ph),
                     "p99": self._m_critical.percentile(99, phase=ph)}
                for ph in ("queue", "prefill", "decode", "device")
            },
            # QoS classes: per-tier queue depth and latency
            # percentiles, plus how often a tier's prefill chunk was
            # starved/truncated by tick-budget pressure — the evidence
            # that overload degraded the batch tier first
            "qos": {
                t: {
                    "queue_depth": qos_depth.get(t, 0),
                    "ttft_p99_ms": self._m_qos_ttft.percentile(
                        99, tier=t),
                    "itl_p50_ms": self._m_qos_itl.percentile(50, tier=t),
                    "itl_p99_ms": self._m_qos_itl.percentile(99, tier=t),
                    "preempted_chunks": (
                        self.scheduler._m_qos_preempted
                        .labels(tier=t).value),
                }
                for t in QOS_TIERS
            },
        }
        if self.spec:
            out.update({
                "draft": self.draft_kind,
                "spec_k": self.spec_k,
                "draft_tokens": self.draft_tokens_proposed,
                "accepted_tokens": self.draft_tokens_accepted,
                "acceptance_rate": (
                    round(self.draft_tokens_accepted
                          / self.draft_tokens_proposed, 4)
                    if self.draft_tokens_proposed else 0.0
                ),
                # the same two, named as the other per-tick sums are,
                # and their ratio unrounded
                "draft_tokens_total": self.draft_tokens_proposed,
                "accepted_tokens_total": self.draft_tokens_accepted,
                "spec_accept_pct": 100.0 * self.draft_tokens_accepted
                / max(self.draft_tokens_proposed, 1),
            })
        if self.flight is not None:
            out["flight"] = {
                "recorded": len(self.flight),
                "dropped": self.flight.dropped,
                "overhead_frac": round(
                    self._flight_ns
                    / max(self._tick_ns + self._flight_ns, 1), 5),
            }
        if self.paged:
            pool = self.pool.stats()
            out.update({
                "blocks_in_use": self.pool.in_use_count(),
                "blocks_free": self.pool.free_count(),
                # free + cached-unreferenced: what an admission could
                # actually obtain. The router's block-pool saturation
                # signal — a transiently empty free list with a warm
                # prefix cache is NOT saturation
                "blocks_reclaimable": pool["free"] + pool["cached"],
                "prompt_tokens": self.prompt_tokens,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_hit_fraction": (
                    round(self.prefix_hit_tokens / self.prompt_tokens, 4)
                    if self.prompt_tokens else 0.0
                ),
                # KV-block migration (disaggregated serving): blocks
                # this engine shipped out / installed via the
                # export_kv / import_kv ops
                "kv_blocks_exported": self.kv_blocks_exported,
                "kv_blocks_imported": self.kv_blocks_imported,
            })
            if self.host is not None:
                # tiered KV cache: the router's spill gate reads
                # host_blocks_cached next to blocks_reclaimable — a
                # replica whose device pool looks tight but whose host
                # tier holds the prefixes is one swap-in away from a
                # hit, not saturated
                hs = self.host.stats()
                out.update({
                    "host_blocks_cached": hs["blocks"],
                    "host_bytes": hs["bytes"],
                    "block_demotions": self.demotions,
                    "block_restores": self.restores,
                    "restore_wait_ms": {
                        "p50": self._m_restore_wait.percentile(50),
                        "p99": self._m_restore_wait.percentile(99),
                    },
                })
        return out
