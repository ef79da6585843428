"""Operations and bytes of what ``solar_open2_lm``'s two kinds of mixing
layer need of the chip, from what each tick was dealt (the engine counts
on the host as it plans a tick and writes the counts on the tick's
``engine.dispatch`` span).

**The GQA layers' attention calls** (``ops/hybrid_attend.py``: the
launches ``full_attend`` of a tick that fed a chunk and
``full_decode_attend`` of a tick of plain decoding): ``attended_tokens``
(query, key) pairs and ``key_positions`` K/V positions of ONE layer,
live rows only, priced by :func:`chipbench.kernels.hybrid_attend.tick`
at this model's widths (keys and values both 128 wide).

**The KDA layers' state** (``ops/delta_rule.py``, the scopes
``delta_step`` and ``delta_chunk``): a row that fed a token has its
state of ``H x dk x dv`` float32 a layer read once and written once,
whatever implements the rule and however many tokens the row fed; the
tokens' own q, k, v, g are a thousandth of that and are left out. A row
that fed nothing needs nothing. Bytes-bound: the rule's flops (a few
per state element a token) are under the chip's ridge by two orders."""

from __future__ import annotations

from chipbench.harness import span_reduce
from chipbench.kernels import hybrid_attend


def _dispatches(run: dict, field: str):
    profile = span_reduce.profile_of(run)
    return [a for n, _, _, a in (profile["spans"] if profile else [])
            if n == "engine.dispatch" and field in a]


def _kinds(model: dict):
    """(GQA layers, KDA layers) of the configuration's ``model``."""
    gqa = len([i for i in model["gqa_layers"] if i < model["num_layers"]])
    return gqa, model["num_layers"] - gqa


def full_least_seconds(cell: dict, run: dict, trace: dict, peaks: dict):
    """The least seconds the chip could take for the GQA layers' kernel
    calls in the traced window, one call a GQA layer a dispatch.
    ``None`` where no span carries the counts."""
    dealt = _dispatches(run, "full_key_positions")
    if not dealt:
        return None
    model = dict(cell["config_spec"]["model"])
    model["v_head_dim"] = model["head_dim"]
    itemsize = {"bfloat16": 2, "float32": 4}[
        cell["config_spec"]["precision"]["kv_cache"]]
    total = 0.0
    for a in dealt:
        flops, nbytes = hybrid_attend.tick(
            a["attended_tokens"], a["key_positions"],
            a["n_dec"] + a["fed_tokens"], model, itemsize)
        total += max(flops / peaks["flops_bf16"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return _kinds(model)[0] * total


def state_bytes(model: dict, itemsize: int = 4) -> int:
    """One row's state in one KDA layer."""
    return model["kda_num_heads"] * model["kda_head_dim"] ** 2 * itemsize


def state_rows(a: dict) -> int:
    """Rows that fed at least one token in a dispatch, summed over the
    KDA layers: those that took the step and those the chunk form ran
    (a whole chunk, padded to a power of two, a row)."""
    width = 1 << (int(a.get("chunk", 1)) - 1).bit_length()
    return a["state_rows_stepped"] + a["chunk_positions_computed"] // width


def state_least_seconds(cell: dict, run: dict, peaks: dict):
    """The least seconds for the traced dispatches' state traffic: one
    read and one write of each fed row's state a KDA layer. ``None``
    where no span carries the counts."""
    dealt = _dispatches(run, "state_rows_stepped")
    if not dealt:
        return None
    itemsize = {"bfloat16": 2, "float32": 4}[
        cell["config_spec"]["precision"]["recurrent_state"]]
    one = 2 * state_bytes(cell["config_spec"]["model"], itemsize)
    return sum(state_rows(a) for a in dealt) * one / peaks[
        "hbm_bytes_per_s"]
