"""The slot cache's cursor-bounded attention kernel (chunk attend and
decode attend): parity vs the dense reference, what its index map
fetches, auto gating, and engine-level stream parity with the kernel
forced.

The kernel (ops/splash_prefill.py) runs in interpret mode off-TPU, so
CPU CI executes the identical program the TPU would; the dense masked
attend stays the bit-parity reference (``prefill_kernel='gather'``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.ops import splash_prefill as sp
from distkeras_tpu.serving import ServingEngine


def _dense_ref(q, keys, vals, starts):
    """The _cached_attend math: grouped masked attend at absolute
    per-row positions."""
    B, T, H, hd = q.shape
    L, Hk = keys.shape[1], keys.shape[2]
    G = H // Hk
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, T, Hk, G, hd)
    s = jnp.einsum("bqkgd,blkd->bkgql", qg, keys).astype(jnp.float32) * scale
    qpos = starts[:, None] + jnp.arange(T)[None]
    mask = jnp.arange(L)[None, None, :] <= qpos[..., None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgql,blkd->bqkgd", p.astype(q.dtype), vals)
    return out.reshape(B, T, H, hd)


@pytest.mark.parametrize("B,T,H,Hk,hd,L", [
    (2, 8, 4, 2, 16, 64),    # GQA, chunk mid-cache
    (3, 5, 4, 4, 8, 48),     # MHA, odd chunk, odd-tile L
    (1, 16, 8, 2, 32, 128),  # wide group
])
def test_kernel_matches_dense_reference(B, T, H, Hk, hd, L):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, L - T, size=B), jnp.int32)
    out = sp.splash_prefill_attention(q, k, v, starts)
    ref = _dense_ref(q, k, v, starts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_rows_at_distinct_depths():
    """Each batch row at its own cursor — the mixed tick's shape: one
    row deep into its context, one at the start (most KV tiles
    skipped), one mid-way."""
    rng = np.random.default_rng(1)
    B, T, H, Hk, hd, L = 3, 4, 4, 2, 16, 96
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    starts = jnp.asarray([0, 40, 90], jnp.int32)
    out = sp.splash_prefill_attention(q, k, v, starts)
    ref = _dense_ref(q, k, v, starts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _cursor_cases(kb, L, T):
    """Cursors at the tile edges (first tile only, last slot of a tile,
    first slot of the next, the cache's end) and one row far deeper
    than the others."""
    return {
        "zero": [0, 0, 0],
        "tile_end": [kb - 1, 0, kb - 1],
        "tile_start": [kb, kb, 0],
        "cache_end": [L - T, L - T, L - T],
        "one_deep": [1, L - T, 2],
    }


@pytest.mark.parametrize("cursors", ["zero", "tile_end", "tile_start",
                                     "cache_end", "one_deep"])
@pytest.mark.parametrize("T,H,Hk", [
    (1, 4, 4),    # MHA decode step: T*G = 1, seven padding rows
    (1, 4, 2),    # GQA decode step: T*G = 2
    (3, 4, 4),    # ragged MHA window: T*G = 3
    (1, 12, 1),   # one KV head, wide group: T*G = 12, padded to 16
    (3, 8, 2),    # T*G = 12 from a window of three
])
def test_kernel_matches_dense_at_small_query_tiles(T, H, Hk, cursors):
    """Query tiles that are not a multiple of a sublane are padded with
    zero rows whose outputs are dropped: a decode step (T == 1) and a
    ragged window read the same as the dense attend, at every cursor
    the tile walk treats specially."""
    hd, L = 16, 160
    kb = sp.choose_kv_block(L)
    assert L // kb == 5  # a walk of several tiles
    rng = np.random.default_rng(7)
    starts = jnp.asarray(_cursor_cases(kb, L, T)[cursors], jnp.int32)
    B = starts.shape[0]
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    out = sp.splash_prefill_attention(q, k, v, starts)
    ref = _dense_ref(q, k, v, starts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_never_reads_past_the_cursor():
    """Poison (NaN) behind every row's last tile: the walk neither
    fetches nor scores it, so the output stays finite and equal to the
    dense attend over the clean cache."""
    rng = np.random.default_rng(2)
    B, T, H, Hk, hd, L = 3, 1, 4, 2, 16, 1024
    kb, nkv = sp.choose_kv_block(L), L // sp.choose_kv_block(L)
    starts = np.asarray([0, kb + 3, 2 * kb - 1], np.int32)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = rng.standard_normal((B, L, Hk, hd)).astype(np.float32)
    v = rng.standard_normal((B, L, Hk, hd)).astype(np.float32)
    # read back before the poison goes in: jnp.asarray may alias the
    # numpy buffers, and the dispatch is asynchronous
    ref = np.asarray(_dense_ref(q, jnp.asarray(k), jnp.asarray(v),
                                jnp.asarray(starts)))
    for b, tiles in enumerate(sp.walk_tiles(starts, T, kb, nkv)):
        k[b, tiles * kb:] = np.nan
        v[b, tiles * kb:] = np.nan
    out = sp.splash_prefill_attention(q, jnp.asarray(k), jnp.asarray(v),
                                      jnp.asarray(starts))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("T", [1, 5, 64])
def test_kv_walk_stops_at_the_rows_last_tile(T):
    """The flattened walk the K/V index map reads names, for every row
    in turn, tile 0 up to the tile that holds the row's last query
    position and no tile past it — the grid has no other step, so
    nothing else is ever copied in."""
    kb, nkv = 256, 8
    starts = np.asarray([0, kb - T, kb - T + 1, 700, nkv * kb - T],
                        np.int32)
    B = len(starts)
    row, tile, steps = sp.kv_schedule(jnp.asarray(starts),
                                      jnp.full((B,), T, jnp.int32), kb, nkv)
    assert row.shape == tile.shape == (B * nkv,)
    steps = int(steps)
    refs = (row, tile, None, None)
    named = [tuple(int(i) for i in sp.kv_index(t, *refs))
             for t in range(B * nkv)]
    want = [(b, j, 0, 0) for b, st in enumerate(starts)
            for j in range((int(st) + T - 1) // kb + 1)]
    assert named[:steps] == want and steps == len(want)
    # past the grid's end the arrays repeat the last step: a stray read
    # names a block that is already resident
    assert set(named[steps:]) <= {want[-1]}
    for t in (0, steps - 1):
        assert tuple(int(i) for i in sp.q_index(t, *refs)) == (
            named[t][0], 0, 0, 0)
    # the host-side count is the same arithmetic, in positions
    assert sp.fetched_positions(starts, T, nkv * kb) == kb * len(want)
    # a cursor parked past the cache (an idle row that ticked on) walks
    # the whole cache and no further
    assert sp.fetched_positions([nkv * kb + 5], T, nkv * kb) == nkv * kb


def test_kv_walk_follows_the_valid_tokens():
    """A mixed tick's rows say how many of their ``T`` queries are real:
    a decoding row's walk ends at its one token's tile, not at the
    chunk's end, and a row with no valid token gets one tile."""
    kb, nkv, T = 32, 5, 16
    assert sp.choose_kv_block(nkv * kb) == kb
    starts = jnp.asarray([20, 20, 60, 100], jnp.int32)
    lens = jnp.asarray([16, 1, 0, 3], jnp.int32)
    row, tile, steps = sp.kv_schedule(starts, lens, kb, nkv)
    walk = list(zip(np.asarray(row)[:int(steps)].tolist(),
                    np.asarray(tile)[:int(steps)].tolist()))
    assert walk == [(0, 0), (0, 1), (1, 0), (2, 0),
                    (3, 0), (3, 1), (3, 2), (3, 3)]
    assert sp.fetched_positions(np.asarray(starts), np.asarray(lens),
                                nkv * kb) == 8 * kb
    assert sp.fetched_positions(np.asarray(starts), T, nkv * kb) == (
        2 + 2 + 3 + 4) * kb


@pytest.mark.parametrize("H,Hk", [(4, 4), (4, 2), (8, 1)])
def test_kernel_matches_dense_on_the_valid_queries(H, Hk):
    """The mixed tick's shape: a chunk-wide query tile of which a
    decoding row has one real token (scored as a sublane of rows), a
    prefilling row a few or all, a starved row none. The valid queries
    read as the dense attend; the rest stay finite."""
    rng = np.random.default_rng(5)
    B, T, hd, L = 5, 16, 16, 160
    kb = sp.choose_kv_block(L)
    starts = jnp.asarray([kb - 1, 3, 2 * kb + 5, 40, L - T], jnp.int32)
    lens = np.asarray([1, T, 5, 0, T], np.int32)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
    out = np.asarray(sp.splash_prefill_attention(
        q, k, v, starts, jnp.asarray(lens)))
    ref = np.asarray(_dense_ref(q, k, v, starts))
    assert np.isfinite(out).all()
    for b, n in enumerate(lens):
        np.testing.assert_allclose(out[b, :n], ref[b, :n],
                                   atol=2e-5, rtol=2e-5)


def test_supports_and_preferred_gating():
    # lane-aligned shapes pass the static gate...
    assert sp.supports(64, 2, 128, 1024)
    # ...and so do a single decode token and a ragged query tile: the
    # wrapper pads the tile to a sublane
    assert sp.supports(1, 8, 128, 1024)
    assert sp.supports(1, 1, 128, 2048, 16)
    assert sp.supports(3, 1, 128, 1024)
    # an unaligned head dim or an unaligned cache length never take the
    # kernel, nor does a tile set over the VMEM budget
    assert not sp.supports(64, 2, 96, 1024)
    assert not sp.supports(64, 2, 128, 100)
    assert not sp.supports(512, 1, 128, 2048, 16)
    # preferred() is supports() AND-gated on the TPU backend — on the
    # CPU CI it must always keep 'auto' on the dense reference
    if jax.default_backend() != "tpu":
        assert not sp.preferred(64, 2, 128, 1024)
        assert not sp.preferred(1, 1, 128, 2048, 16)


def test_choose_kv_block_divides():
    for L in (64, 96, 100, 128, 1024, 7):
        assert L % sp.choose_kv_block(L) == 0


def test_module_resolves_prefill_kernel():
    from distkeras_tpu.models.transformer import CausalSelfAttention

    m = CausalSelfAttention(num_heads=4, decode=True, cache_len=64,
                            slot_cursor=True, prefill_kernel="gather")
    assert not m._use_prefill_kernel(64, 2, 128, 1024)
    m = m.clone(prefill_kernel="splash")
    assert m._use_prefill_kernel(8, 2, 16, 64)
    # a decode step takes the same walk: 'splash' forces it at every T,
    # 'gather' keeps the dense attend at every T
    assert m._use_prefill_kernel(1, 2, 16, 64)
    assert not m.clone(prefill_kernel="gather")._use_prefill_kernel(
        1, 2, 16, 64)
    m = m.clone(prefill_kernel="auto")
    for T in (64, 1):
        assert (m._use_prefill_kernel(T, 2, 128, 1024)
                == sp.preferred(T, 2, 128, 1024))


def test_unknown_prefill_kernel_rejected():
    from distkeras_tpu.models import get_model

    model = get_model(
        "transformer_lm", vocab_size=32, d_model=32, num_heads=4,
        num_layers=1, max_len=32, dtype=jnp.float32, attention="dense",
    )
    bad = model.clone(decode=True, slot_cursor=True,
                      prefill_kernel="flash", parent=None)
    with pytest.raises(ValueError, match="prefill_kernel"):
        bad.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))


def _mk_engine(model, params, *, paged, prefill_kernel):
    kw = dict(paged=True, block_size=8, num_blocks=64) if paged else {}
    return ServingEngine(
        model, params, slots=2, prefill_chunk=8,
        prefill_kernel=prefill_kernel,
        registry=telemetry.MetricRegistry(), tracer=telemetry.Tracer(),
        **kw,
    )


@pytest.mark.parametrize("paged", [False, True])
def test_engine_streams_match_with_kernel_forced(paged):
    """The acceptance bar: chunked-prefill streams with the splash
    kernel forced (interpret mode on CPU) are token-identical to the
    dense-reference engine across both cache layouts — the chunk
    ticks and the decode ticks alike (a cache of three KV tiles; the
    rows cross a tile edge while they decode)."""
    from distkeras_tpu.models import get_model

    model = get_model(
        "transformer_lm", vocab_size=64, d_model=64, num_heads=4,
        num_layers=2, max_len=96, dtype=jnp.float32, attention="dense",
    )
    assert sp.choose_kv_block(96) == 32
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (19, 30)]

    def run(prefill_kernel):
        eng = _mk_engine(model, params, paged=paged,
                         prefill_kernel=prefill_kernel)
        reqs = [eng.submit(p, max_new_tokens=6, temperature=0.7, seed=i)
                for i, p in enumerate(prompts)]
        eng.drain()
        return [r.stream.tokens(timeout=60) for r in reqs]

    assert run("splash") == run("gather")
