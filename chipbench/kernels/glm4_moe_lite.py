"""Operations ``glm4_moe_lite_lm`` needs of the chip for the tokens that
entered a stream in a serving tick, from the configuration's widths and
the engine's own counters: the USEFUL count, the same whatever
implements the tick and whatever drafts. A position a tick multiplies
without a token on it (a chunk's padding, a packed tick's tail, a
tile's padding in the grouped matmul) is not work and is not counted;
nor is a refused draft's position, nor anything the
multi-token-prediction module computes: a draft is a means, and what it
buys shows as more tokens a second, not as more operations a token.

Four prices:

- :func:`fed_token`: two operations a weight of every matmul a token
  passes through in the main model: each layer's attention projections,
  the two absorbed halves of ``wkv_b`` among them; the leading dense
  layer's SwiGLU; an expert layer's router and shared expert;
- :func:`routed_pair`: one routed expert's three matmuls. Every expert
  is held here, so a token makes ``num_experts_per_tok`` pairs an
  expert layer (the program's ``routed_here`` counts refused drafts and
  the module's layer too, and is not used);
- :func:`emitted_token`: the head at one position;
- :func:`attended_key`: a query's score over one position's latent
  entry and that entry's part of the value sum, all heads, one layer.
"""

from __future__ import annotations


def fed_token(model: dict) -> int:
    d, H = model["d_model"], model["num_heads"]
    q, R = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    attention = (d * q + q * H * (nope + rope) + d * (R + rope)
                 + H * nope * R + H * R * v + H * v * d)
    dense = 3 * d * model["intermediate_size"]
    expert_layer = (3 * d * model["moe_intermediate_size"]
                    * model["n_shared_experts"]
                    + d * model["n_routed_experts"])
    layers, first = model["num_layers"], model["first_k_dense"]
    return 2 * (layers * attention + first * dense
                + (layers - first) * expert_layer)


def routed_pair(model: dict) -> int:
    return 2 * 3 * model["d_model"] * model["moe_intermediate_size"]


def emitted_token(model: dict) -> int:
    return 2 * model["d_model"] * model["vocab_size"]


def attended_key(model: dict) -> int:
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    return 2 * model["num_heads"] * (latent + model["kv_lora_rank"])


def kept_positions(work: dict) -> int:
    """The positions of verify ticks that entered a stream, from the
    engine's sums (a flight record's fields or ``stats()``'s totals
    under the same names less ``_total``): every position the ticks ran
    (``window_positions``: prompt tokens, each decoding row's pending
    token, its draft) less the drafts refused. A row whose request had
    ended when its tick was read (``overrun_tokens``, one a position it
    kept) entered none. Never more than ``window_positions``."""
    return (work["window_positions"] - work["draft_tokens"]
            + work["accepted_tokens"] - work["overrun_tokens"])


def useful_flops(model: dict, work: dict) -> float:
    """The useful operations of the verify ticks ``work`` sums:
    :func:`kept_positions` through the main model, the head at each of
    ``emitted_tokens``, and of ``attended_tokens`` (counted at plan time
    over every window position, refused drafts among them) the share
    that the kept positions are of the window's."""
    kept = kept_positions(work)
    layers = model["num_layers"]
    expert_layers = layers - model["first_k_dense"]
    return (
        kept * (fed_token(model) + expert_layers
                * model["num_experts_per_tok"] * routed_pair(model))
        + work["emitted_tokens"] * emitted_token(model)
        + work["attended_tokens"] * kept / max(work["window_positions"], 1)
        * layers * attended_key(model))
