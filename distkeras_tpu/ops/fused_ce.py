"""Fused (chunked) linear + softmax cross-entropy.

The flagship LM's loss head was the largest single HBM consumer in the
training step: ``VocabHead`` materializes ``[B, T, V]`` f32 logits
(512 MB at the flagship shape), softmax-CE reads them back, and the
backward materializes a same-sized ``dlogits`` and feeds it through two
matmuls — ~2.5 GB of HBM traffic and >1 GB of live memory that exist
only to be reduced to one scalar (VERDICT r4 next #1).

:func:`fused_linear_softmax_ce` computes the same quantity chunk-by-chunk
over rows with a custom VJP: the forward runs ``chunk x V`` logits
through logsumexp and discards them (saving only the inputs as
residuals), and the backward *recomputes* each chunk's logits, forms the
softmax cotangent in-register, and immediately consumes it in the
``dx``/``dkernel`` matmuls. Peak live logits memory drops from
``N x V`` to ``chunk x V`` and the full-size logits/dlogits arrays never
touch HBM.

Numerics: the forward is bit-comparable to ``VocabHead`` +
``optax.softmax_cross_entropy_with_integer_labels`` (same bf16-operand /
f32-accumulation matmul, same f32 logsumexp). The backward casts the
softmax cotangent to the activation dtype (bf16) before its two matmuls
so they run at the MXU's bf16 rate — XLA's unfused backward promotes
them to f32 — which perturbs gradients at the bf16 rounding level
(~2^-8 relative), well under the noise the bf16 forward already
introduces; ``tests/test_fused_ce.py`` pins both tolerances.

Reference: the reference expresses losses as Keras objectives compiled
into the worker graph (distkeras/workers.py · the per-batch train op);
this op is the TPU-first realization of its categorical cross-entropy
for the LM head, restructured for HBM rather than translated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Rows per chunk. chunk x V f32 logits is the transient the backward
# recomputes: 2048 x 8192 x 4B = 64 MB at the flagship vocab — big
# enough that the matmuls stay MXU-shaped (the profile bills the bwd
# chunk dots at 171 TF/s), small enough that the transient is ~1/8 of
# the logits it replaces. Swept on-chip (BASELINE.md r5): flagship
# tok/s is flat within noise across chunk 1024/2048/4096.
DEFAULT_CHUNK = 2048


def _pad_rows(a, n):
    if n == 0:
        return a
    pad = jnp.zeros((n,) + a.shape[1:], a.dtype)
    return jnp.concatenate([a, pad], axis=0)


def _chunked(x, labels, weights, chunk):
    """Reshape [N, ...] row arrays into [nc, chunk, ...], padding the tail
    with weight-0 rows so every chunk is full (static shapes for scan)."""
    N = x.shape[0]
    C = min(chunk, N)
    r = (-N) % C
    x = _pad_rows(x, r)
    labels = _pad_rows(labels, r)
    weights = _pad_rows(weights, r)
    nc = x.shape[0] // C
    return (x.reshape(nc, C, x.shape[-1]), labels.reshape(nc, C),
            weights.reshape(nc, C), C)


def _vma_zero(*arrays):
    """A scalar f32 zero carrying the union of the arrays' vma (varying-
    over-mesh-axes) type: inside ``shard_map``, a plain ``jnp.zeros``
    scan carry is *unvarying* while the body's output varies over the
    mesh axes its inputs do, and scan rejects the carry-type mismatch.
    Adding ``0 * (one element of each input)`` ties the types without
    naming any axis, so the op stays mesh-agnostic."""
    z = jnp.zeros((), jnp.float32)
    for a in arrays:
        z = z + jnp.sum(jnp.ravel(a)[:1]).astype(jnp.float32) * 0.0
    return z


def _typed_like(ct, primal):
    """``ct`` carrying exactly ``primal``'s vma type, which a custom-VJP
    bwd rule must return under ``shard_map``. The scan above types every
    cotangent with the UNION of the inputs' varying axes; an input that
    is replicated over some of them (the loss mask varies over sp only,
    the activations over dp and sp) gets the psum over the extra axes —
    the correct cotangent of a replicated value, and dead code when
    nobody differentiates that input. Outside ``shard_map`` both sets are
    empty and this is the identity."""
    have, want = jax.typeof(ct).vma, jax.typeof(primal).vma
    extra = tuple(sorted(have - want))
    if extra:
        ct = jax.lax.psum(ct, extra)
    missing = tuple(sorted(want - have))
    if missing:
        ct = jax.lax.pcast(ct, missing, to="varying")
    return ct


def _logits(xc, kernel, bias, dtype):
    """One chunk's logits exactly as VocabHead computes them: bf16 (model
    dtype) operands on the MXU, f32 accumulation, f32 bias add."""
    return jax.lax.dot_general(
        xc.astype(dtype), kernel.astype(dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + bias


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_linear_softmax_ce(x, kernel, bias, labels, weights,
                            chunk: int = DEFAULT_CHUNK):
    """``sum_i weights[i] * CE(softmax(x[i] @ kernel + bias), labels[i])``
    without materializing the ``[N, V]`` logits.

    Args:
      x: ``[N, D]`` activations (any float dtype; bf16 in the flagship).
      kernel: ``[D, V]`` f32 head weights (cast to ``x.dtype`` on the MXU,
        f32 accumulation — identical to ``VocabHead``).
      bias: ``[V]`` f32.
      labels: ``[N]`` int32 target ids.
      weights: ``[N]`` f32 per-row weights (0 masks a row out; the caller
        divides by its own count — this returns the weighted SUM so SPMD
        callers can psum numerator and denominator separately).
      chunk: rows per chunk; the backward's transient is ``chunk x V``.

    Returns: scalar f32 weighted sum of per-row cross-entropies.
    """
    return _fwd(x, kernel, bias, labels, weights, chunk)[0]


@jax.named_scope("fused_ce")
def _fwd(x, kernel, bias, labels, weights, chunk):
    xs, ls, ws, C = _chunked(x, labels, weights, chunk)

    def body(acc, args):
        xc, lc, wc = args
        logits = _logits(xc, kernel, bias, x.dtype)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=1)[:, 0]
        return acc + jnp.sum(wc * (lse - ll)), None

    total, _ = jax.lax.scan(
        body, _vma_zero(x, kernel, bias, labels, weights), (xs, ls, ws)
    )
    return total, (x, kernel, bias, labels, weights)


@jax.named_scope("fused_ce")
def _bwd(chunk, res, g):
    x, kernel, bias, labels, weights = res
    xs, ls, ws, C = _chunked(x, labels, weights, chunk)
    nc = xs.shape[0]
    D, V = kernel.shape

    def body(carry, args):
        dk, db = carry
        xc, lc, wc = args
        # recompute this chunk's logits (cheaper than having stored them:
        # one matmul vs N x V of HBM), then the softmax cotangent
        logits = _logits(xc, kernel, bias, x.dtype)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=1)[:, 0]
        p = jax.nn.softmax(logits, axis=-1)
        scale = (wc * g)[:, None]
        dl = p * scale
        dl = dl - scale * jax.nn.one_hot(lc, V, dtype=jnp.float32)
        # both consuming matmuls run bf16-operand/f32-accum like the
        # forward (XLA's unfused backward promotes these to f32 — slower
        # and no more accurate than the bf16 forward deserves)
        dlc = dl.astype(x.dtype)
        dxc = jax.lax.dot_general(
            dlc, kernel.astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        dk = dk + jax.lax.dot_general(
            xc.astype(x.dtype), dlc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db = db + jnp.sum(dl, axis=0)
        # d loss / d weights[i] is the row's own CE (the loss is linear
        # in weights) — free here since lse/ll are already in hand;
        # returning None instead would silently zero a caller that
        # differentiates through learned row weights (r5 review)
        dwc = (lse - ll) * g
        return (dk, db), (dxc, dwc)

    z = _vma_zero(x, kernel, bias, labels, weights, g)
    (dk, db), (dxs, dws) = jax.lax.scan(
        body,
        (jnp.zeros((D, V), jnp.float32) + z, jnp.zeros((V,), jnp.float32) + z),
        (xs, ls, ws),
    )
    dx = dxs.reshape(nc * C, D)[: x.shape[0]]
    dw = dws.reshape(nc * C)[: x.shape[0]].astype(weights.dtype)
    # padded rows have weight 0 -> their dl is exactly 0; no correction
    return (_typed_like(dx, x), _typed_like(dk.astype(kernel.dtype), kernel),
            _typed_like(db.astype(bias.dtype), bias), None,
            _typed_like(dw, weights))


fused_linear_softmax_ce.defvjp(_fwd, _bwd)


def lm_head_loss(features, head_params, targets, mask,
                 chunk: int = DEFAULT_CHUNK):
    """Flagship-LM convenience wrapper: ``features`` ``[B, T, D]`` (the
    backbone's ln_f output), ``head_params`` the VocabHead subtree
    (``{'kernel': [D, V], 'bias': [V]}``), ``targets`` ``[B, T]`` int32,
    ``mask`` ``[B, T]`` f32 row weights.

    Returns ``(local_sum, local_count)`` so SPMD callers can psum each
    side; single-device callers divide directly.
    """
    B, T, D = features.shape
    kernel = head_params["kernel"]
    bias = head_params.get("bias")
    if bias is None:
        # typed as the kernel is, so that the scan's carries agree
        bias = jnp.zeros((kernel.shape[1],), jnp.float32) + _vma_zero(kernel)
    s = fused_linear_softmax_ce(
        features.reshape(B * T, D), kernel, bias,
        targets.reshape(B * T).astype(jnp.int32),
        mask.reshape(B * T).astype(jnp.float32),
        chunk,
    )
    return s, jnp.sum(mask)
