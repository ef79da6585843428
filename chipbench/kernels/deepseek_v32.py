"""Operations ``deepseek_v32_lm`` needs of the chip for the tokens a
serving tick was dealt, from the configuration's widths and the
program's own counters: the USEFUL count, the same whatever implements
the tick. A position a tick multiplies without a token on it (a chunk's
padding, a block's tail, a tile's padding in the grouped matmul) is not
work and is not counted; nor is what the compiler recomputes or copies.

Per token fed (a decode token or a prompt token, ``decode_tokens`` +
``prefill_tokens`` of a flight record): two operations a weight of every
matmul the token passes through: each layer's attention and indexer
projections, the two absorbed halves of ``wkv_b`` among them (the key
half folded into the query, the value half into the output); the dense
SwiGLU of the leading dense layers; the router and the shared expert of
the expert layers. Per (token, expert) pair routed to an expert held
here (``routed_here``, counted on the device, all layers): one routed
expert's three matmuls. Per token sampled (``emitted``): the head, which
the engine reads at one position a row. Per position a query attended
(``keys_selected``: ``min(t + 1, index_topk)`` a live query, counted at
plan time ONCE a query, so times the layers): the score over the latent
and the value product, all heads. Per position the indexer scored
(``index_positions_scored``: ``t + 1`` a live query, once a query, so
times the layers): the index heads' dot products."""

from __future__ import annotations


def flops_per_token(model: dict) -> int:
    """Two operations a matmul weight a fed token multiplies, over all
    layers (no head, no routed expert, no attend)."""
    d, H = model["d_model"], model["num_heads"]
    q, R = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    J, Di = model["index_n_heads"], model["index_head_dim"]
    attention = (d * q + q * H * (nope + rope) + d * (R + rope)
                 + H * nope * R + H * R * v + H * v * d
                 + q * J * Di + d * Di + d * J)
    dense = 3 * d * model["intermediate_size"]
    expert_layer = (3 * d * model["moe_intermediate_size"]
                    * model["n_shared_experts"]
                    + d * model["n_routed_experts"])
    layers, first = model["num_layers"], model["first_k_dense"]
    return 2 * (layers * attention + first * dense
                + (layers - first) * expert_layer)


def tick_flops(model: dict, tick: dict) -> int:
    """The useful operations of one flight record's tick."""
    d, H = model["d_model"], model["num_heads"]
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    layers = model["num_layers"]
    return (
        flops_per_token(model) * (tick["decode_tokens"]
                                  + tick["prefill_tokens"])
        + tick.get("routed_here", 0) * 2 * 3 * d
        * model["moe_intermediate_size"]
        + tick.get("emitted", 0) * 2 * d * model["vocab_size"]
        + tick.get("keys_selected", 0) * layers * 2 * H
        * (latent + model["kv_lora_rank"])
        + tick.get("index_positions_scored", 0) * layers * 2
        * model["index_n_heads"] * model["index_head_dim"])
