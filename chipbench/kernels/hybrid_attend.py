"""Operations and bytes of the full layers' attention calls of a model
whose layers differ in kind (``ops/hybrid_attend.py``: the launches
``full_attend`` of a tick that fed a chunk and ``full_decode_attend`` of
a tick of plain decoding), from what each tick was dealt.

The engine counts on the host as it plans a tick and writes the counts
on the tick's ``engine.dispatch`` span: ``attended_tokens``, the (query,
key) pairs the dealt tokens require of ONE full layer, and
``key_positions``, the K/V positions those rows hold, live rows only
(``full_key_positions`` beside them is what the kernels copied in, in
whole tiles and over all full layers: never less). Idle rows, a chunk's
padding and a tile's tail are computed or copied by the kernel and
required by nobody: they are not counted, so the share cannot pass
100 %. The window layers' attend is plain XLA over a ring of 256: no
kernel, no roofline (``window_attend_device_pct``)."""

from __future__ import annotations

from chipbench.harness import span_reduce


def tick(attended: int, keys: int, queries: int, model: dict,
         itemsize: int = 2):
    """(flops, bytes) of one full layer's call in one tick: QK^T over
    ``head_dim`` and PV over ``v_head_dim``, 2 flops a pair, channel and
    query head; every K and V position of its ``num_kv_heads`` read
    once, the queries read and the output written."""
    H, dk, dv = model["num_heads"], model["head_dim"], model["v_head_dim"]
    flops = 2 * H * (dk + dv) * attended
    return flops, (keys * model["num_kv_heads"] + queries * H) * (
        dk + dv) * itemsize


def full_least_seconds(cell: dict, run: dict, trace: dict, peaks: dict):
    """The least seconds the chip could take for the full layers'
    kernel calls in the traced window: over the ``engine.dispatch``
    spans the profile holds that carry the counts (a chunk tick and a
    decode tick alike: both launch the kernel), one call a full layer.
    ``None`` where no span does (a program without these layers)."""
    profile = span_reduce.profile_of(run)
    dealt = [a for n, _, _, a in (profile["spans"] if profile else [])
             if n == "engine.dispatch" and "full_key_positions" in a]
    if not dealt:
        return None
    model = cell["config_spec"]["model"]
    itemsize = {"bfloat16": 2, "float32": 4}[
        cell["config_spec"]["precision"]["kv_cache"]]
    total = 0.0
    for a in dealt:
        flops, nbytes = tick(a["attended_tokens"], a["key_positions"],
                             a["n_dec"] + a["fed_tokens"], model, itemsize)
        total += max(flops / peaks["flops_bf16"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return model["hybrid_layer_pattern"].count(0) * total
