"""The gated delta rule with a decay per key channel (Kimi Delta
Attention, arXiv:2510.26692), for a serving tick: a recurrent state
``S [dk, dv]`` a head in place of keys stored by position,

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(dk)

with ``g_t <= 0`` the log of the decay. Three entry points, one
mathematics (the token-by-token recurrence's, which the tests hold them
to):

- :func:`delta_step`: one token a row (a decode tick, the decoding rows
  of a mixed tick). One read and one write of every row's state; both
  of the step's reductions over the key channel (``k^T S``, ``q^T S``)
  come from one pass.
- :func:`delta_chunk`: up to ``C`` tokens a row from the row's state,
  in the chunk form (the WY representation: ``U = (I + A)^-1 Diag(beta)
  (V - (K e^Gamma) S_0)``). A decay per channel makes the factorised
  products ``(K e^Gamma)(K e^-Gamma)^T`` overflow, so every exponent
  here is a difference ``gamma_t - gamma_j <= 0``: explicit within
  blocks of :data:`SUB` tokens, and split at the block's edge between
  blocks (``gamma_t - gamma_edge`` and ``gamma_edge - gamma_j``, both
  ``<= 0``; a factor that underflows is the product's own limit).
  Stable where a channel's decay sums to -40 and beyond in a chunk.
- :func:`delta_ragged`: a tick's tokens, laid flat, row ``s`` holding
  ``valid_lens[s]`` of them. **The work follows the tokens dealt**: the
  rows that fed one token take the step; the rows that fed more take
  the chunk form one after the other, in a loop of as many trips as
  there are such rows (1-3 of 64 in a serving tick, where the chunk
  form over all ``S x C`` positions would compute 3 % useful); a row
  that fed none is left as it was, bit for bit.

A padding token (beyond a row's ``valid_lens``) has ``beta = 0`` and
``g = 0``: it changes nothing. Plain XLA under the scopes ``delta_step``
and ``delta_chunk``; float32 throughout, the small matmuls at precision
"highest" (the state is what the layer remembers: rounding it to
bfloat16 on every read is another model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SUB = 16  # tokens of a block inside which the decays' differences are explicit

_mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def delta_step(state, q, k, v, g, beta, live=None, fresh=None):
    """One token a row. ``state [B, H, dk, dv]`` float32; ``q``, ``k``,
    ``g [B, H, dk]``, ``v [B, H, dv]``, ``beta [B, H]``; ``live [B]``
    bool (None: all): the rows that fed a token, the others' state comes
    back untouched; ``fresh [B]`` bool (None: none): rows whose state
    reads as zero whatever it holds (a reused slot). Returns ``(out [B,
    H, dv], new_state)``; ``out`` of a row that is not live is not
    meant to be read."""
    with jax.named_scope("delta_step"):
        q, k, v, g = (t.astype(jnp.float32) for t in (q, k, v, g))
        beta = beta.astype(jnp.float32)
        a = jnp.exp(g)
        # k^T (a S) and q^T (a S): one pass over the state for both.
        # A fresh row's zero is selected where each pass ends, not
        # written into a copy of the state that both would then read
        both = jnp.stack([k * a, q * a], axis=2)  # [B, H, 2, dk]
        red = jnp.sum(both[..., None] * state[:, :, None], axis=3)
        decayed = a[..., None] * state
        if fresh is not None:
            red = jnp.where(fresh[:, None, None, None], 0.0, red)
            decayed = jnp.where(fresh[:, None, None, None], 0.0, decayed)
        k_s, q_s = red[:, :, 0], red[:, :, 1]     # [B, H, dv]
        w = beta[..., None] * (v - k_s)
        out = (q_s + jnp.sum(q * k, -1, keepdims=True) * w) / np.sqrt(
            q.shape[-1])
        new = decayed + k[..., None] * w[:, :, None, :]
        if live is not None:
            new = jnp.where(live[:, None, None, None], new, state)
        return out, new


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a [..., C, C]``,
    ``C`` a power of two: the inverses of the diagonal blocks, doubled
    ``log2 C`` times (``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R
    P^-1, Q^-1]]``)."""
    C = a.shape[-1]
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (C, 1, 1), a.dtype)
    b = 1
    while b < C:
        n = C // (2 * b)
        blocks = jnp.moveaxis(jnp.diagonal(
            a.reshape(lead + (n, 2 * b, n, 2 * b)), axis1=-4, axis2=-2),
            -1, -3)  # [..., n, 2b, 2b]: the diagonal blocks of 2b
        pair = inv.reshape(lead + (n, 2, b, b))
        p_inv, q_inv = pair[..., 0, :, :], pair[..., 1, :, :]
        r = -_mm("...ij,...jk,...kl->...il", q_inv,
                 blocks[..., b:, :b], p_inv)
        inv = jnp.concatenate([
            jnp.concatenate([p_inv, jnp.zeros_like(p_inv)], -1),
            jnp.concatenate([r, q_inv], -1)], -2)
        b *= 2
    return inv[..., 0, :, :]


def _decayed_products(q, k, gam):
    """``(KK, QK)``, each ``[..., C, C]``: ``sum_c x[t, c] k[j, c]
    exp(gam[t, c] - gam[j, c])`` for ``j <= t`` with ``x`` = ``k`` and
    ``x`` = ``q``, 0 elsewhere; ``q``, ``k``, ``gam [..., C, dk]``. Every
    exponent is ``<= 0``: the pairs inside a block of :data:`SUB` tokens
    take their difference, the others split it at the query's block's
    edge. Both products share the keys' side and every exponential."""
    C, dk = k.shape[-2:]
    lead = k.shape[:-2]
    sub = min(SUB, C)
    n = C // sub
    gb = gam.reshape(lead + (n, sub, dk))
    kb = k.reshape(gb.shape)
    rows = jnp.stack([kb, q.reshape(gb.shape)], axis=-4)  # [.., 2, n, sub, dk]
    # gamma at the last token before each block (zero before the first)
    edge = jnp.concatenate(
        [jnp.zeros(lead + (1, dk), gam.dtype), gb[..., :-1, -1, :]], -2)
    left = rows * jnp.exp(gb - edge[..., None, :])[..., None, :, :, :]
    # keys before block I, decayed from their own position to I's edge
    before = (jnp.arange(C)[None, :] < sub * jnp.arange(n)[:, None])
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], edge[..., None, :] - gam[..., None, :, :],
        -jnp.inf))  # [..., n, C, dk]
    off = _mm("...xisc,...ijc->...xisj", left, right)  # [.., 2, n, sub, C]
    # inside a block: the difference itself
    s, r = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    diff = jnp.where((r <= s)[..., None],
                     gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf)
    diag = jnp.sum(rows[..., :, None, :] * (
        kb[..., None, :, :] * jnp.exp(diff))[..., None, :, :, :, :], -1)
    place = jnp.eye(n, dtype=diag.dtype)  # block I's pairs into column I
    full = off.reshape(lead + (2, n, sub, n, sub)) + (
        diag[..., :, :, None, :] * place[:, None, :, None])
    full = full.reshape(lead + (2, C, C))
    return full[..., 0, :, :], full[..., 1, :, :]


def delta_chunk(state, q, k, v, g, beta, valid_lens=None):
    """Up to ``C`` tokens a row, in the chunk form. ``state [B, H, dk,
    dv]`` float32, each row's state before its first token (zero for a
    fresh row: the caller's to read so); ``q``, ``k``, ``g [B, C, H,
    dk]``, ``v [B, C, H, dv]``, ``beta [B, C, H]``; ``valid_lens [B]``
    or None (all ``C``): a row's tokens beyond it are padding and
    change nothing. ``C`` is padded here to a power of two. Returns
    ``(out [B, C, H, dv], new_state)``: the recurrence's outputs and
    each row's state after its last valid token."""
    with jax.named_scope("delta_chunk"):
        B, C0, H, dk = q.shape
        scale = 1.0 / np.sqrt(dk)
        C = 1 << (C0 - 1).bit_length()
        fed = (jnp.full((B,), C0, jnp.int32) if valid_lens is None
               else valid_lens)
        live = (jnp.arange(C)[None, :] < fed[:, None])[:, None, :, None]

        def heads_first(t):  # [B, C0, H, x] -> [B, H, C, x], padding zero
            t = jnp.moveaxis(t.astype(jnp.float32), 1, 2)
            t = jnp.pad(t, ((0, 0), (0, 0), (0, C - C0), (0, 0)))
            return jnp.where(live, t, 0.0)

        q, k, v, g = (heads_first(t) for t in (q, k, v, g))
        beta = heads_first(beta[..., None])  # [B, H, C, 1]
        gam = jnp.cumsum(g, axis=2)
        decay = jnp.exp(gam)
        # u_t = beta_t (v_t - k_t^T Diag(e^gam_t) S_0) - sum_{j<t} A_tj u_j
        kk, qk = _decayed_products(q, k, gam)
        a = beta * jnp.tril(kk, -1)  # A_tj for j < t
        rhs = beta * (v - _mm("bhtc,bhcv->bhtv", k * decay, state))
        u = _mm("bhtj,bhjv->bhtv", _unit_lower_inverse(a), rhs)
        out = scale * (_mm("bhtc,bhcv->bhtv", q * decay, state)
                       + _mm("bhtj,bhjv->bhtv", qk, u))
        last = gam[:, :, -1:, :]  # padding adds 0: the last valid token's
        new = jnp.swapaxes(decay[:, :, -1:, :], 2, 3) * state + _mm(
            "bhjc,bhjv->bhcv", k * jnp.exp(last - gam), u)
        return jnp.moveaxis(out[:, :, :C0], 1, 2), new


def delta_ragged(state, q, k, v, g, beta, first, valid_lens, fresh,
                 chunk: int):
    """A tick's tokens laid flat: ``q``, ``k``, ``g [M, H, dk]``, ``v
    [M, H, dv]``, ``beta [M, H]``, row ``s`` of ``state [S, H, dk, dv]``
    holding the ``valid_lens[s] <= chunk`` tokens from ``first[s]`` on;
    ``fresh [S]`` marks the rows whose state reads as zero. Rows that
    fed one token take :func:`delta_step`, rows that fed more take
    :func:`delta_chunk` one at a time (none can where ``chunk`` is 1),
    rows that fed none keep their state. Returns ``(out [M, H, dv]``
    float32, zero where no live token lies, ``new_state)``."""
    M = q.shape[0]
    one = valid_lens == 1
    at = jnp.where(one, first, M)

    def rows_of(t):
        return jnp.take(t, at, axis=0, mode="fill", fill_value=0)

    o, state = delta_step(state, rows_of(q), rows_of(k), rows_of(v),
                          rows_of(g), rows_of(beta), live=one, fresh=fresh)
    out = jnp.zeros((M,) + v.shape[1:], jnp.float32).at[at].set(
        o, mode="drop")
    if chunk == 1:
        return out, state
    more = valid_lens > 1
    order = jnp.argsort(~more, stable=True)  # the chunk rows first

    def row(i, carry):
        state, out = carry
        s = order[i]
        n = valid_lens[s]
        tok = jnp.where(jnp.arange(chunk) < n, first[s] + jnp.arange(chunk),
                        M)

        def tokens_of(t):  # [1, chunk, ...]
            return jnp.take(t, tok, axis=0, mode="fill", fill_value=0)[None]

        s0 = jax.lax.dynamic_slice_in_dim(state, s, 1)
        s0 = jnp.where(fresh[s], 0.0, s0)
        o, s1 = delta_chunk(s0, tokens_of(q), tokens_of(k), tokens_of(v),
                            tokens_of(g), tokens_of(beta), n[None])
        return (jax.lax.dynamic_update_slice_in_dim(state, s1, s, 0),
                out.at[tok].set(o[0], mode="drop"))

    state, out = jax.lax.fori_loop(0, more.sum(dtype=jnp.int32), row,
                                   (state, out))
    return out, state


def delta_sequence(q, k, v, g, beta, chunk: int = 64):
    """Whole sequences from a zero state (no cache: a full forward):
    ``q``, ``k``, ``g [B, T, H, dk]``, ``v [B, T, H, dv]``, ``beta [B,
    T, H]``, :func:`delta_chunk` over ``chunk`` tokens at a time, the
    state carried from one chunk to the next. Returns ``out [B, T, H,
    dv]`` float32."""
    B, T, H, dk = q.shape
    chunk = min(chunk, 1 << (T - 1).bit_length())
    n = -(-T // chunk)

    def chunks(t):  # [B, T, ...] -> [n, B, chunk, ...], zeros past T
        t = jnp.pad(t, ((0, 0), (0, n * chunk - T)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((B, n, chunk) + t.shape[2:]), 1, 0)

    def step(state, xs):
        i, (qc, kc, vc, gc, bc) = xs
        fed = jnp.full((B,), jnp.clip(T - i * chunk, 0, chunk), jnp.int32)
        out, state = delta_chunk(state, qc, kc, vc, gc, bc, fed)
        return state, out

    state = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, state, (jnp.arange(n), tuple(
        chunks(t) for t in (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1).reshape((B, n * chunk) + out.shape[3:])[
        :, :T]
