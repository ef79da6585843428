"""Attention over a latent (MLA) slot cache with a learned sparse
selection: the lightning indexer's score over a row's held positions,
the top-k threshold that bounds what the attend may see, and the
absorbed attend itself (one shared 576-wide "KV head" under all query
heads), each walking a row's cache in tiles up to the row's cursor and
no further.

A row of the slot cache holds, per position, one latent entry ``[c_kv |
rope(k_r)]`` and one index key. The entry lies in two leaves, each with
a minor axis of whole 128-lane groups (PR 40): ``latent [L, kv_lora_rank]``
(512) and ``rope_keys [rope, L]`` (positions minor: a tile of it is the
score's ``[d, t]`` operand as it lies). One leaf 576 wide the chip
stores positions-minor rather than pad it, and every layer's walk then
copied the 453 MB pool into the layout it reads and back. For a chunk
of ``C`` queries of row ``r`` at absolute positions ``start .. start +
C - 1``:

1. ``index_score``: ``I[t, s] = sum_j w[t, j] * relu(q_i[t, j] .
   k_i[s])`` for the positions ``s`` the row holds (scope
   ``index_score``), kept as order-preserving unsigned keys;
2. ``index_select``: the ``topk``-th largest key of every query among
   its causal positions, by a 32-step search on the keys' bits (exact:
   no sort, no approximation; scope ``index_select``). A query with no
   more than ``topk`` positions gets threshold 0 and sees them all;
3. ``mla_attend``: an online-softmax walk of the latent tiles with the
   mask ``key >= threshold and s <= t`` (scope ``mla_attend``). The
   score is ``q_lat . c_kv + q_rope . rope(k_r)``, two products summed
   in float32; values are the latent tile itself, so it is read once.

A model with NO selection (``glm4_moe_lite_lm``: every position up to
the query's is attended, keys 192 + 64 against values 256 folded into
the same 512 + 64 latent) takes :func:`dense_latent_attention`: step 3
alone, with the rows that feed a token or a verify window walking
together and the prompt chunks one by one.

Everything is plain ``jax.numpy`` under ``lax`` loops with trip counts
read from the cursors: XLA compiles one program for every context
length, and a row that holds a third of ``max_len`` costs a third. No
Pallas kernel here: the trace reports these scopes' seconds, not a
roofline. Rows that feed at most one token (decoding and idle rows of a
mixed tick) take the same walk with one query instead of ``C``.

Two things in the walk are there for the TPU compiler and were measured
on a v5e (PR 28, a ``[32, 64]`` tick of the five-layer cut): loops that
close over the pooled ``[S, L, D]`` cache and a score matmul that
contracts a key tile's minor axis make it give the whole pool a
transposed layout and copy it, every layer and tick (a third of the
device's time; 352 ms a tick where every row holds a chunk). The row's
slice handed out by the scan and the tile transposed behind an
``optimization_barrier`` leave the pool where it is: 123-165 ms for the
same ticks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
LANES = 128  # a TPU register's minor axis: what a leaf's minor axis fills


def yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``[dim // 2]`` inverse frequencies of YaRN-scaled rope: channels
    that turn more than ``beta_fast`` times over the original context
    keep their frequency, those that turn less than ``beta_slow`` times
    are slowed by ``factor``, with a linear ramp between."""
    half = dim // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / dim)

    def correction_dim(turns):
        return dim * math.log(original_len / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (freqs / factor * (1 - smooth) + freqs * smooth).astype(
        np.float32)


def yarn_softmax_scale(qk_head_dim: int, factor: float) -> float:
    """``qk_head_dim ** -0.5 * m ** 2`` with ``m = 0.1 ln(factor) + 1``."""
    m = 0.1 * math.log(factor) + 1.0
    return qk_head_dim ** -0.5 * m * m


def rope_half(x, pos, inv_freq):
    """Rotate the last axis of ``x [..., T, H, 2 * half]`` (or ``[..., T,
    2 * half]``) by each token's own position ``pos [..., T]``, channel
    ``i`` paired with ``i + half``; float32 inside."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    if x.ndim == pos.ndim + 2:  # a head axis between T and the channels
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def sortable_keys(x):
    """float32 -> uint32 with the same order (``a < b`` iff ``key(a) <
    key(b)``), so that a threshold can be searched bit by bit."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def kth_largest_key(keys, k: int):
    """``[C]``: for each row of ``keys [C, L]`` (uint32) the largest
    ``T`` with ``count(keys >= T) >= k``: the ``k``-th largest key, or 0
    where the row holds fewer than ``k`` non-zero keys."""

    def bit(b, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - b.astype(jnp.uint32)))
        count = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, t)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(keys.shape[:1], jnp.uint32))


def _row_walk(q, qi, w, latent, rope_keys, index_keys, start, n, *,
              topk: int, tile: int, scale: float):
    """One row's chunk: ``q [C, H, rank + rope]`` absorbed queries, ``qi
    [C, J, Di]`` index queries, ``w [C, J]`` float32 head weights,
    against the row's own ``latent [L, rank]``, ``rope_keys [rope, L]``
    (positions minor) and ``index_keys [L, Di]`` up to position ``n``
    (exclusive); query ``c`` sits at ``start + c``. Returns ``[C, H,
    rank]`` float32 (zeros where the row holds nothing)."""
    C, H, _ = q.shape
    L, Di = index_keys.shape
    rank, rope = latent.shape[1], rope_keys.shape[0]
    q_lat, q_rope = q[..., :rank], q[..., rank:]
    qpos = start + jnp.arange(C)
    tiles = (n + tile - 1) // tile

    def causal(i):
        return (i * tile + jnp.arange(tile))[None, :] <= qpos[:, None]

    with jax.named_scope("index_score"):
        def score(i, keys):
            kt = jax.lax.dynamic_slice(index_keys, (i * tile, 0), (tile, Di))
            s = jnp.einsum("cjd,td->cjt", qi, kt,
                           preferred_element_type=jnp.float32)
            score = jnp.einsum("cjt,cj->ct", jax.nn.relu(s), w)
            key = jnp.where(causal(i), sortable_keys(score), jnp.uint32(0))
            return jax.lax.dynamic_update_slice(keys, key, (0, i * tile))

        keys = jax.lax.fori_loop(0, tiles, score,
                                 jnp.zeros((C, L), jnp.uint32))
    with jax.named_scope("index_select"):
        threshold = kth_largest_key(keys, topk)
    with jax.named_scope("mla_attend"):
        def attend(i, carry):
            m, l, acc = carry
            kt = jax.lax.dynamic_slice(latent, (i * tile, 0), (tile, rank))
            rt = jax.lax.dynamic_slice(rope_keys, (0, i * tile),
                                       (rope, tile))
            ok = (jax.lax.dynamic_slice(keys, (0, i * tile), (C, tile))
                  >= threshold[:, None]) & causal(i)
            # the latent tile is transposed here, behind a barrier:
            # folded into the matmul, the transpose becomes a layout
            # that the compiler gives the whole cache and copies every
            # tick (the rope tile lies transposed in its leaf). The
            # score is the 576-wide product in its two halves, both
            # accumulated in float32
            s = (jnp.einsum("chd,dt->cht", q_lat,
                            jax.lax.optimization_barrier(kt.T),
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("chd,dt->cht", q_rope, rt,
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where(ok[:, None, :], s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(ok[:, None, :], jnp.exp(s - m_new[..., None]), 0.0)
            fade = jnp.exp(m - m_new)
            acc = acc * fade[..., None] + jnp.einsum(
                "cht,tv->chv", p.astype(kt.dtype), kt,
                preferred_element_type=jnp.float32)
            return m_new, l * fade + p.sum(axis=-1), acc

        _, l, acc = jax.lax.fori_loop(
            0, tiles, attend,
            (jnp.full((C, H), NEG, jnp.float32),
             jnp.zeros((C, H), jnp.float32),
             jnp.zeros((C, H, rank), jnp.float32)))
        return acc / jnp.maximum(l, 1e-30)[..., None]


def write_positions_minor(leaf, new, starts, fed):
    """``leaf [S, D, L]`` (positions minor) with the first ``fed [S]``
    of each row's ``new [S, T, D]`` entries written at positions
    ``starts [S]`` on; what would land past ``L`` is dropped.

    A row's write is one window of whole 128-lane groups about its
    cursor, read, overlaid and written back in place, a row at a time:
    a scatter of single positions wants them major, and a gather of the
    rows' windows wants a layout of its own, so that either makes the
    compiler copy the whole leaf in and out, every layer and tick."""
    S, D, L = leaf.shape
    T = new.shape[1]
    W = min(L, -(-(T + LANES - 1) // LANES) * LANES)
    first = jnp.clip(starts // LANES * LANES, 0, L - W)
    # the window's lane j takes entry j - (cursor - first) where fed
    c = jnp.arange(W)[None, :] - (starts - first)[:, None]  # [S, W]
    live = (c >= 0) & (c < fed[:, None])
    placed = jnp.take_along_axis(
        new, jnp.clip(c, 0, T - 1)[:, :, None], axis=1).swapaxes(1, 2)

    def row(s, leaf):
        at = (s, 0, first[s])
        old = jax.lax.dynamic_slice(leaf, at, (1, D, W))
        return jax.lax.dynamic_update_slice(
            leaf, jnp.where(live[s], placed[s], old[0])[None], at)

    return jax.lax.fori_loop(0, S, row, leaf)


def sparse_latent_attention(q, qi, w, latent, rope_keys, index_keys,
                            starts, valid_lens, *, topk: int, tile: int,
                            scale: float):
    """``[S, C, H, rank]``: every row's chunk of queries attended over
    the positions the indexer selects among those the row holds.

    ``q [S, C, H, D]`` (absorbed: ``D = rank + rope``), ``qi [S, C, J,
    Di]``, ``w [S, C, J]``; ``latent [S, L, rank]``, ``rope_keys [S,
    rope, L]`` and ``index_keys [S, L, Di]`` already hold this chunk's
    own entries; ``starts [S]`` are the
    cursors before the chunk and ``valid_lens [S]`` how many of its
    ``C`` tokens each row feeds (``None``: all). ``L`` is a multiple of
    ``tile``. A row that feeds at most one token walks with its first
    query alone; the other outputs of such a row are zeros, which
    nothing reads."""
    S, C, H, _ = q.shape
    rank = latent.shape[-1]
    if latent.shape[1] % tile:
        raise ValueError(f"cache length {latent.shape[1]} is no multiple of "
                         f"the walk's tile {tile}")
    valid = (jnp.full((S,), C, jnp.int32) if valid_lens is None
             else valid_lens)
    walk = functools.partial(_row_walk, topk=topk, tile=tile, scale=scale)

    def one(args):
        # the row's own slices of the cache (the scan hands them out): the
        # walk's loops then hold a row, not the pool
        qr, qir, wr, start, fed, lat, rot, keys = args
        # a row that feeds nothing (idle, or starved of budget) walks no
        # tile, wherever its cursor was left
        n = jnp.where(fed > 0, start + fed, 0)

        def chunk(_):
            return walk(qr, qir, wr, lat, rot, keys, start, n)

        def single(_):
            first = walk(qr[:1], qir[:1], wr[:1], lat, rot, keys, start, n)
            return jnp.zeros((C, H, rank), jnp.float32).at[:1].set(first)

        if C == 1:
            return chunk(None)
        return jax.lax.cond(fed <= 1, single, chunk, None)

    return jax.lax.map(one, (q, qi, w, starts, valid, latent, rope_keys,
                             index_keys))


def sparse_latent_attention_packed(q, qi, w, latent, rope_keys, index_keys,
                                   starts, valid_lens, offsets, chunk: int,
                                   out=None, *, topk: int, tile: int,
                                   scale: float):
    """:func:`sparse_latent_attention` over PACKED queries, for a mixed
    tick whose per-token layers run over the live tokens packed to the
    front: ``q [N, H * D]`` (a token's heads side by side), ``qi [N, J,
    Di]``, ``w [N, J]`` hold row ``s``'s ``valid_lens[s]`` tokens at
    ``offsets[s]`` on (``offsets`` = the exclusive cumulative sum of
    ``valid_lens``), and ``N`` leaves ``chunk`` rows beyond the last
    token, so that a row's slice of ``chunk`` rows always fits. Returns
    ``[N, H * rank]`` in ``q``'s dtype with each row's results where its
    queries were. Rows are walked in order and each writes its whole
    slice: what lies beyond a row's valid tokens is overwritten by the
    rows after it, and beyond the last token nothing reads; ``out`` is
    an array of the result's shape to write over (another layer's
    result: every row a reader looks at is written here first), zeros
    if none. Neither the
    queries (302 MB a layer at ``[32, 64]``) nor the results are ever
    laid out ``[S, chunk]``; both are rows of features, two axes, so
    that the layouts the walk's and the projections' matmuls want are
    given to a row's slice or a block, not to all ``N`` rows in a copy."""
    N = q.shape[0]
    rank = latent.shape[-1]
    D = rank + rope_keys.shape[1]
    H = q.shape[1] // D
    if latent.shape[1] % tile:
        raise ValueError(f"cache length {latent.shape[1]} is no multiple of "
                         f"the walk's tile {tile}")
    walk = functools.partial(_row_walk, topk=topk, tile=tile, scale=scale)

    def one(out, args):
        offset, start, fed, lat, rot, keys = args
        n = jnp.where(fed > 0, start + fed, 0)

        def rows(c):
            # the row's slice, behind a barrier: folded into the walk,
            # the layout its matmuls want becomes a copy of all N rows,
            # made again for every row
            qr, qir, wr = jax.lax.optimization_barrier(tuple(
                jax.lax.dynamic_slice_in_dim(t, offset, c)
                for t in (q, qi, w)))
            res = walk(qr.reshape(c, H, D), qir, wr, lat, rot, keys, start,
                       n)
            return jax.lax.dynamic_update_slice_in_dim(
                out, res.astype(out.dtype).reshape(c, H * rank), offset, 0)

        # a row that feeds at most one token takes, walks and writes one
        return jax.lax.cond(fed <= 1, lambda _: rows(1),
                            lambda _: rows(chunk), None), None

    if out is None:
        out = jnp.zeros((N, H * rank), q.dtype)
    return jax.lax.scan(
        one, out, (offsets, starts, valid_lens, latent, rope_keys,
                   index_keys))[0]


def _dense_tiles(q, latent, rope_keys, qpos, n, *, tile: int, scale: float):
    """The absorbed attend with no selection, for ``B`` rows walking
    together: ``q [B, Q, H, rank + rope]`` at positions ``qpos [B, Q]``
    against ``latent [B, L, rank]`` and ``rope_keys [B, rope, L]``
    (positions minor), each row over its positions below ``n [B]`` and
    no later than the query's own. One loop over the tiles up to the
    longest row's ``n``; a row that holds less is masked in the tiles
    beyond it. Returns ``[B, Q, H, rank]`` float32 (zeros for a row
    with ``n`` 0)."""
    B, Q, H, _ = q.shape
    rank, rope = latent.shape[-1], rope_keys.shape[1]
    q_lat, q_rope = q[..., :rank], q[..., rank:]

    def step(i, carry):
        m, l, acc = carry
        kt = jax.lax.dynamic_slice(latent, (0, i * tile, 0),
                                   (B, tile, rank))
        rt = jax.lax.dynamic_slice(rope_keys, (0, 0, i * tile),
                                   (B, rope, tile))
        at = (i * tile + jnp.arange(tile))[None, None, :]
        ok = ((at <= qpos[:, :, None]) & (at < n[:, None, None]))[:, :, None]
        # the latent tile transposed behind a barrier, the rope tile as
        # it lies: see ``_row_walk``
        s = (jnp.einsum("bqhd,bdt->bqht", q_lat,
                        jax.lax.optimization_barrier(kt.swapaxes(1, 2)),
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bdt->bqht", q_rope, rt,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(ok, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "bqht,btv->bqhv", p.astype(kt.dtype), kt,
            preferred_element_type=jnp.float32)
        return m_new, l * fade + p.sum(axis=-1), acc

    with jax.named_scope("mla_attend"):
        _, l, acc = jax.lax.fori_loop(
            0, (jnp.max(n) + tile - 1) // tile, step,
            (jnp.full((B, Q, H), NEG, jnp.float32),
             jnp.zeros((B, Q, H), jnp.float32),
             jnp.zeros((B, Q, H, rank), jnp.float32)))
        return acc / jnp.maximum(l, 1e-30)[..., None]


def dense_latent_attention(q, latent, rope_keys, starts, fed, offsets,
                           chunk: int, *, small: int, tile: int,
                           scale: float):
    """The absorbed attend over a latent cache with NO selection: every
    query sees every position its row holds up to its own. For a chunk,
    for one query and for a verify window of ``small`` queries.

    ``q [N + chunk, H * D]`` holds the tick's queries as rows of
    features (``D = rank + rope``), row ``s``'s ``fed[s]`` of them from
    ``offsets[s]`` on: ``offsets = s * chunk`` for a tick laid out ``[S,
    chunk]``, the exclusive cumulative sum of ``fed`` where the live
    tokens are packed; the ``chunk`` rows of tail let any row's slice of
    ``chunk`` fit. ``latent [S, L, rank]`` and ``rope_keys [S, rope, L]``
    already hold the fed tokens' own entries; ``starts [S]`` are the
    cursors before them. Returns ``[N, H * rank]`` in ``q``'s dtype,
    each token's result where its query was; rows no token lies on are
    zeros or stale and nothing reads them.

    The rows that feed at most ``small`` tokens (decoding rows, verify
    windows) walk TOGETHER, ``small`` queries each, one loop over the
    tiles up to the longest of them: a step reads the tile of every row
    in one slice and multiplies it in one batched product, where a walk
    a row is a dozen small kernels a step, a thousand steps a tick. The
    rows that feed more (prompt chunks, a few a tick) walk one by one
    with all ``chunk`` queries. A tick no wider than ``small`` is the
    first kind alone."""
    S, L, rank = latent.shape
    D = rank + rope_keys.shape[1]
    H = q.shape[1] // D
    N = q.shape[0] - chunk
    if L % tile:
        raise ValueError(f"cache length {L} is no multiple of the walk's "
                         f"tile {tile}")
    walk = functools.partial(_dense_tiles, tile=tile, scale=scale)
    ends = jnp.where(fed > 0, starts + fed, 0)
    if chunk <= small:
        if N != S * chunk:
            raise ValueError("a tick no wider than the verify window is "
                             "laid out [S, chunk], not packed")
        # laid out [S, chunk]: the queries are the rows as they lie
        res = walk(q[:N].reshape(S, chunk, H, D), latent, rope_keys,
                   starts[:, None] + jnp.arange(chunk)[None], ends)
        return res.astype(q.dtype).reshape(N, H * rank)
    few = (fed > 0) & (fed <= small)
    cols = jnp.arange(small)[None]
    idx = offsets[:, None] + cols  # [S, small] rows of q
    res = walk(q[idx].reshape(S, small, H, D), latent, rope_keys,
               starts[:, None] + cols, jnp.where(few, ends, 0))
    out = jnp.zeros((N + chunk, H * rank), q.dtype).at[
        jnp.where(few[:, None] & (cols < fed[:, None]), idx,
                  N + chunk).reshape(-1)].set(
        res.astype(q.dtype).reshape(S * small, H * rank), mode="drop")
    many = fed > small
    order = jnp.argsort(~many, stable=True)
    live = jnp.arange(chunk)[:, None]

    def one(i, out):
        r = order[i]
        at = offsets[r]
        # the row's queries and its cache behind a barrier: folded into
        # the walk, the layouts its products want are given to all N
        # rows and to the pool, in copies made again for every row
        qr, lat, rot = jax.lax.optimization_barrier((
            jax.lax.dynamic_slice_in_dim(q, at, chunk),
            jax.lax.dynamic_slice_in_dim(latent, r, 1),
            jax.lax.dynamic_slice_in_dim(rope_keys, r, 1)))
        res = walk(qr.reshape(1, chunk, H, D), lat, rot,
                   (starts[r] + jnp.arange(chunk))[None], ends[r][None])
        old = jax.lax.dynamic_slice_in_dim(out, at, chunk)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(live < fed[r],
                           res.astype(q.dtype).reshape(chunk, H * rank),
                           old), at, 0)

    return jax.lax.fori_loop(0, many.sum(), one, out)[:N]


def dense_fetched_positions(starts, valid, tile: int, small: int) -> int:
    """Cache positions one layer's :func:`dense_latent_attention` reads
    in a tick, all rows of it: the rows that feed at most ``small``
    tokens walk together to the longest of them (every row's tile is
    read in every step), the others their own tiles (host arithmetic,
    for the engine's ``key_positions_fetched``)."""
    valid = np.asarray(valid, np.int64)
    n = np.where(valid > 0, np.asarray(starts, np.int64) + valid, 0)
    few = (valid > 0) & (valid <= small)
    together = -(-int(n[few].max(initial=0)) // tile) * tile * len(n)
    return together + int((-(-n[valid > small] // tile) * tile).sum())


def fetched_positions(starts, valid, tile: int) -> int:
    """Cache positions the walks of one tick read, all rows of it: each
    row's tiles up to its last valid token, none for a row that feeds
    nothing (host arithmetic, for the engine's
    ``key_positions_fetched``)."""
    valid = np.asarray(valid, np.int64)
    n = np.where(valid > 0, np.asarray(starts, np.int64) + valid, 0)
    return int((-(-n // tile) * tile).sum())
