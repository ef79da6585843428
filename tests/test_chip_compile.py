"""The main path's Pallas kernels, compiled for a described (not
attached) TPU v5e at the shapes ``chip_smoke.py`` runs them at.

Interpret mode (what every other kernel test runs) says nothing about
Mosaic's block rules or VMEM: both serving kernels once passed all their
interpret-mode tests while the chip's compiler refused them at every
shape. The TPU compiler is installed without a chip, so these compiles
guard each later change at no chip time. Nothing here runs.

The topology is described inside a fixture of this file and nowhere
else: only one process may load the TPU library, every xdist worker
imports every test file, and only the worker that is handed this file
may make the call.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distkeras_tpu.ops import (grouped_experts, hybrid_attend, moe,
                               paged_attention, pallas_attention,
                               pallas_pair, splash_prefill)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(monkeypatch, one_chip):
    """``compiled_text(fn, *shapes)`` for the described chip: kernels out
    of interpret mode, the persistent cache off (an entry written here
    cannot be read back without a chip, and the next run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache

    for mod in (pallas_attention, pallas_pair, paged_attention,
                splash_prefill, hybrid_attend):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compiled_text(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compiled_text
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32


def paged_shapes(B, T, H, Hk, hd, bs, quant, pages=512, max_blocks=64):
    pool = ((pages, bs, Hk, hd), I8 if quant else BF16)
    shapes = [((B, T, H, hd), BF16), pool, pool,
              ((B, max_blocks), I32), ((B,), I32)]
    if quant:
        shapes += [((pages, bs, Hk), F32)] * 2
    return shapes


def splash_shapes(B, T, H, Hk, hd, L):
    kv = ((B, L, Hk, hd), BF16)
    return [((B, T, H, hd), BF16), kv, kv, ((B,), I32)]


def _abstract(tree, sharding):
    """``tree``'s shapes and dtypes as arguments placed by ``sharding``."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def grad_of(attend):
    """fwd + bwd of one attention op as a single program."""
    def loss(q, k, v):
        out = attend(q, k, v)
        return sum(jnp.sum(o.astype(F32)) for o in jax.tree.leaves(out))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


def test_train_attention_fwd_and_grad(for_chip):
    """The flagship train step's attention: B8 / T2048 / H8 / hd256."""
    qkv = [((8, 2048, 8, 256), BF16)] * 3
    block = pallas_attention.choose_block(2048, 256, itemsize=2)
    text = for_chip(grad_of(functools.partial(
        pallas_attention.pallas_causal_attention, block=block)), *qkv)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dk/dv


@pytest.mark.parametrize("causal", [True, False])
def test_ring_pair_fwd_and_grad(for_chip, causal):
    """One chunk pair of the ring (sp=2 of T2048): T1024 / hd256; the
    diagonal pair is causal, the off-diagonal one is not."""
    qkv = [((4, 1024, 8, 256), BF16)] * 3
    text = for_chip(grad_of(functools.partial(
        pallas_pair.pallas_pair_attention, causal=causal)), *qkv)
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("T,Hk,quant", [
    (64, 2, False),  # the mixed tick's 64-token chunk, bf16 pool
    (64, 2, True),   # ... and the int8 pool the smoke serves
    (1, 1, True),    # a decode tick the gate admits: G = 8
])
def test_paged_attention(for_chip, T, Hk, quant):
    assert paged_attention.supports(T, 8 // Hk, 256, 32,
                                    1 if quant else 2, Hk)
    text = for_chip(paged_attention.paged_attention,
                    *paged_shapes(8, T, 8, Hk, 256, 32, quant))
    assert "tpu_custom_call" in text and "%paged_attention" in text


def test_splash_prefill(for_chip):
    """The slot engine's 64-token chunk over its 2048-token cache."""
    assert splash_prefill.supports(64, 4, 256, 2048, 2)
    text = for_chip(splash_prefill.splash_prefill_attention,
                    *splash_shapes(8, 64, 8, 2, 256, 2048))
    assert "tpu_custom_call" in text and "%splash_prefill" in text


@pytest.mark.parametrize("T,name", [
    (64, "%splash_prefill"),     # a mixed tick's chunk attend
    (1, "%slot_decode_attend"),  # a decode tick's attend: the same walk
])
def test_slot_attend_at_the_benchmarks_shapes(for_chip, T, name):
    """chipbench's serving configuration (Cerebras-GPT-1.3B: 16 slots,
    16 heads of 128, MHA, a 2048-token slot cache): both of its ticks'
    attends are the cursor-bounded kernel, each under its own name."""
    assert splash_prefill.supports(T, 1, 128, 2048, 16)
    text = for_chip(splash_prefill.splash_prefill_attention,
                    *splash_shapes(16, T, 16, 16, 128, 2048))
    assert "tpu_custom_call" in text and name in text
    other = {"%splash_prefill", "%slot_decode_attend"} - {name}
    assert not any(o in text for o in other)


@pytest.mark.parametrize("T,name", [
    (64, "%full_attend"),        # a mixed tick's chunk attend
    (1, "%full_decode_attend"),  # a decode tick's attend
])
def test_full_attend_at_the_published_widths(for_chip, T, name):
    """chipbench's ``mimo-v2.5-serve`` (32 slots of 24576 positions, 64
    query heads of 192 over 4 KV heads, values of 128, the heads of a
    position side by side in the leaf's 768 and 512 lanes): the chip's
    compiler takes the 192-lane slices, the 1024-row chunk tile and the
    scoped VMEM the call asks for."""
    assert hybrid_attend.supports(T, 16, 192, 128, 24576, 4)
    text = for_chip(
        hybrid_attend.full_attention,
        ((32, T, 64, 192), BF16), ((32, 24576, 4 * 192), BF16),
        ((32, 24576, 4 * 128), BF16), ((32,), I32), ((32,), I32))
    assert "tpu_custom_call" in text and name in text
    other = {"%full_attend", "%full_decode_attend"} - {name}
    assert not any(o in text for o in other)
    # the pool stays where it lies: no copy of a cache leaf about the call
    assert not any(" copy(" in line and "[32,24576," in line.split(" copy(")[0]
                   for line in text.splitlines())


@pytest.mark.parametrize("T,name", [
    (64, "%full_attend"),        # a mixed tick's chunk attend
    (1, "%full_decode_attend"),  # a decode tick's attend
])
def test_full_attend_at_the_gqa_layers_widths_of_solar_open2(for_chip, T,
                                                             name):
    """chipbench's ``solar-open2-250b-serve`` (64 slots of 6144
    positions, 64 query heads of 128 over 8 KV heads, keys and values
    alike, the heads of a position side by side in the leaf's 1024
    lanes, a KV tile of 256): the chip's compiler takes the 128-lane
    slices of eight heads and the 512-row chunk tile a KV head."""
    assert hybrid_attend.supports(T, 8, 128, 128, 6144, 8)
    assert splash_prefill.choose_kv_block(6144) == 256
    text = for_chip(
        hybrid_attend.full_attention,
        ((64, T, 64, 128), BF16), ((64, 6144, 8 * 128), BF16),
        ((64, 6144, 8 * 128), BF16), ((64,), I32), ((64,), I32))
    assert "tpu_custom_call" in text and name in text
    other = {"%full_attend", "%full_decode_attend"} - {name}
    assert not any(o in text for o in other)
    # the pool stays where it lies: no copy of a cache leaf about the call
    assert not any(" copy(" in line and "[64,6144," in line.split(" copy(")[0]
                   for line in text.splitlines())


# (kernel, T, H, Hk, hd): what supports() says must be what the compiler
# says. The refusals are VMEM: the tiles hold all Hk heads of a chunk.
GATE_CASES = [
    ("paged", 150, 8, 2, 256),    # a whole prompt in one prefill
    ("paged", 24, 8, 8, 128),     # MHA, hd128
    ("paged", 1024, 8, 2, 256),   # refused: 16 MiB of tiles
    ("splash", 2, 8, 2, 256),     # the smallest chunk
    ("splash", 320, 8, 2, 256),   # near the budget
    ("splash", 56, 32, 32, 128),  # wide MHA near the budget
    ("splash", 96, 32, 32, 128),  # refused: 32 heads of stacked scores
    ("splash", 1024, 8, 8, 128),  # refused
    ("splash", 512, 16, 16, 128),  # refused
    ("splash", 1, 16, 16, 128),   # a decode step, MHA: one query row
    ("splash", 1, 8, 2, 256),     # a decode step, GQA: T*G = 4
    ("splash", 3, 8, 8, 128),     # a ragged window, padded to 8 rows
    ("splash", 1, 64, 64, 128),   # refused even at T == 1: 64-head tiles
]


@pytest.mark.parametrize("kernel,T,H,Hk,hd", GATE_CASES)
def test_supports_agrees_with_the_compiler(for_chip, kernel, T, H, Hk, hd):
    if kernel == "paged":
        said = paged_attention.supports(T, H // Hk, hd, 32, 2, Hk)
        fn = paged_attention.paged_attention
        shapes = paged_shapes(2, T, H, Hk, hd, 32, False)
    else:
        said = splash_prefill.supports(T, H // Hk, hd, 2048, Hk)
        fn = splash_prefill.splash_prefill_attention
        shapes = splash_shapes(2, T, H, Hk, hd, 2048)
    try:
        for_chip(fn, *shapes)
        compiles = True
    except Exception as e:
        assert "vmem" in str(e).lower(), e
        compiles = False
    assert said == compiles


@pytest.mark.parametrize("T,G,hd,bs,itemsize", [
    (64, 4, 64, 32, 2),   # head dim not lane-aligned
    (1, 4, 256, 32, 1),   # T*G = 4: the smoke's own decode tick
    (64, 4, 256, 16, 1),  # int8 pages in 16-row blocks
])
def test_shapes_the_paged_gate_keeps_off_the_kernel(T, G, hd, bs, itemsize):
    """Alignment terms of the gate: conservative (the compiler accepts
    some of these since the tiles took the whole head axis), never
    measured, so they stay on the gathered attend."""
    assert not paged_attention.supports(T, G, hd, bs, itemsize, 2)


def test_the_packed_tick_at_the_benchmarks_shapes(for_chip, monkeypatch,
                                                  one_chip):
    """chipbench's GPT serving configuration (16 slots, chunk 64, the
    scheduler's budget of 256), lowered from abstract arguments: of the
    mixed tick's 1 024 positions the per-token matmuls take the 256 the
    budget can deal, the head takes each row's last token, and the
    attend is still the ``[16, 64]`` kernel. Lowered, not compiled: the
    text names every matmul's rows (the compile is 16 s and says no
    more about them)."""
    import re

    from distkeras_tpu.models import get_model
    from distkeras_tpu.models.transformer import compute_params
    from distkeras_tpu.serving import engine

    S, C, L, V = 16, 64, 2048, 50257
    N = engine._packed_count(256, S, C)
    assert N == 256
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = get_model("transformer_lm", vocab_size=V, d_model=2048,
                      num_heads=16, num_layers=24, max_len=L, dtype=BF16)
    dm = model.clone(decode=True, slot_cursor=True, parent=None)
    shapes = jax.eval_shape(dm.init, jax.random.PRNGKey(0),
                            jnp.zeros((S, 1), I32))
    held = jax.eval_shape(lambda p: compute_params(model, p),
                          {"params": shapes["params"]})

    args = _abstract((held, shapes["cache"],
                      jax.ShapeDtypeStruct((S, V), F32),
                      jax.ShapeDtypeStruct((S, 2), jnp.uint32),
                      jax.ShapeDtypeStruct((S * C + 2 * S,), I32)), one_chip)
    cfgs = ((0.0, None, None),) * S

    def matmul_rows(live):
        text = engine._mixed_tick_fn(engine._CacheLayout(dm), cfgs, C, None,
                                     live).lower(*args).as_text()
        # one kernel, jitted once, called by each of the 24 layers
        assert "splash_prefill" in text
        assert text.count("call @_attend") == 24
        return set(re.findall(
            r"dot_general.*?-> tensor<([0-9x]+)x[a-z0-9]+>", text))

    assert matmul_rows(N) == {"1x256x6144", "1x256x2048", "1x256x8192",
                              "16x1x50257"}
    assert matmul_rows(None) == {"16x64x6144", "16x64x2048", "16x64x8192",
                                 "16x64x50257"}


# -- the held experts' grouped matmul ------------------------------------------

@pytest.mark.parametrize("name,N,k,D,F,held", [
    ("glm-4.7-flash-serve [64, 2]", 128, 4, 2048, 1536, 64),
    ("glm-4.7-flash-serve packed", 256, 4, 2048, 1536, 64),
    ("mimo-v2.5-serve packed", 512, 8, 4096, 2048, 16),
    ("solar-open2-250b-serve packed", 256, 8, 4096, 1280, 20),
    ("deepseek-v3.2-exp-serve by blocks", 2048, 8, 7168, 2048, 16),
])
def test_grouped_experts_at_the_four_configurations_widths(for_chip, name, N,
                                                           k, D, F, held):
    """Both launches at the rows and widths of each expert
    configuration's widest tick (and glm's verify tick): Mosaic's block
    rules, the row copies' alignment, the scalar memory the pair lists
    take and the VMEM of the weight blocks, all met before any chip
    time."""
    text = for_chip(
        functools.partial(grouped_experts.grouped_experts, tile=128,
                          interpret=False),
        ((N, D), BF16), ((N * k,), I32), ((N * k,), F32), ((held,), I32),
        ((held, D, F), BF16), ((held, D, F), BF16), ((held, F, D), BF16))
    assert "moe_gate_up" in text and "moe_down" in text
    assert text.count("tpu_custom_call") >= 2
    assert grouped_experts.supports(D, F, 128)


def test_grouped_experts_backward_at_the_training_cells_shapes(for_chip):
    """``train-moe-seq8k``'s expert layer, a batch row at a time: 8192
    tokens, 8 of 128 experts held, 8 a token. The forward's two
    launches, the hidden rows' and the banks' backward launches and the
    down launch again over the transposed banks, for the real compiler
    (a row's 65 536 pairs fill the launches' scalar memory: two rows
    at once do not fit its 1 MB)."""
    N, k, D, F, held = 8192, 8, 2048, 1024, 8

    def loss(x, tok, gate, sizes, wg, wu, wd):
        return jnp.sum(grouped_experts.grouped_experts(
            x, tok, gate, sizes, wg, wu, wd, tile=128, interpret=False))

    text = for_chip(
        jax.value_and_grad(loss, argnums=(0, 2, 4, 5, 6)),
        ((N, D), BF16), ((N * k,), I32), ((N * k,), F32), ((held,), I32),
        ((held, D, F), BF16), ((held, D, F), BF16), ((held, F, D), BF16))
    for launch in ("moe_gate_up", "moe_down", "moe_bwd_hidden",
                   "moe_bwd_weights"):
        assert launch in text
    assert text.count("tpu_custom_call") >= 5


@pytest.mark.parametrize("B,T,H,Hk,window", [
    (2, 8192, 32, 4, 2048), (2, 8192, 32, 4, None), (4, 2048, 16, 16, None),
], ids=["moe-seq8k-window", "moe-seq8k-full", "seq2k"])
def test_banded_attention_at_the_training_cells_shapes(for_chip, B, T, H, Hk,
                                                       window):
    """``train-moe-seq8k``'s attends, forward, dq and dk/dv: 32 query
    heads over 4 KV heads of 128, two rows of 8192 positions, under the
    window of 2048 and under the causal wedge alone; and
    ``train-seq2k``'s: four rows of 2048, 16 equal heads of 128. Exactly
    three launches a value-and-grad (one forward, dq, dk with dv): what
    the benchmark's readers count (backward least = calls / 2)."""
    q, kv = ((B, T, H, 128), BF16), ((B, T, Hk, 128), BF16)
    block = pallas_attention.choose_block(T, 128, itemsize=2)
    text = for_chip(grad_of(functools.partial(
        pallas_attention.pallas_causal_attention, block=block,
        window=window)), q, kv, kv)
    assert text.count("tpu_custom_call") == 3


def test_the_chip_has_one_form_of_the_grouped_matmul(monkeypatch):
    """A width the launches cannot take is refused where they would be
    built, by name: on a chip the layer does not fall back to the XLA
    form the other backends run (the tests' tiny widths run the kernels
    in interpret mode, or plain XLA off the chip)."""
    assert not grouped_experts.supports(64, 32, 8)
    x = jnp.zeros((8, 64), BF16)
    bank = jnp.zeros((2, 64, 32), BF16)
    args = (x, jnp.zeros((8, 2), I32), jnp.ones((8, 2), F32),
            jnp.ones((8,), bool), bank, bank, bank.transpose(0, 2, 1), 0, 8)
    y, _ = moe.dropless_held_experts(*args)
    assert y.shape == (8, 64)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="d_model in whole 1024s.*64, 32, 8"):
        moe.dropless_held_experts(*args)


def test_the_expert_banks_are_read_where_they_lie(for_chip, one_chip):
    """chipbench's ``glm-4.7-flash-serve`` at its real size, the ``[64,
    2]`` verify tick as the engine builds it, from shapes alone: the
    held experts' layers are the two launches a layer (five layers and
    the module), nothing under ``moe_experts`` is a loop, and no bank
    (1.2 GB a layer) is copied on its way to them."""
    from chipbench.harness import spec
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import engine

    cfg = spec.load("configs", "glm-4.7-flash-serve")
    S, V, m = cfg["engine"]["slots"], cfg["model"]["vocab_size"], cfg["model"]
    model = get_model(spec.model_name(cfg), **m,
                      dtype=jnp.dtype(cfg["compute_dtype"]))
    dm = model.clone(decode=True, slot_cursor=True, parent=None,
                     verify_window=2)
    cache = jax.eval_shape(dm.init, jax.random.PRNGKey(0),
                           jnp.zeros((S, 1), I32))["cache"]
    params = jax.eval_shape(
        lambda: spec.reference(cfg).make_params(cfg, 0))["params"]
    sds = jax.ShapeDtypeStruct
    state = (sds((S,), I32), sds((S, 1), I32), sds((S, 1, V), F32),
             sds((S, 2), jnp.uint32))
    args = _abstract(({"params": params}, cache, sds((S, V), F32),
                      sds((S, 2), jnp.uint32), sds((S * 2 + 5 * S,), I32),
                      state), one_chip)
    text = engine._mtp_verify_fn(
        engine._CacheLayout(dm), (engine._IDLE_CFG,) * S, 2, None,
        None).lower(*args).compile().as_text()
    applies = m["num_layers"] - m["first_k_dense"] + 1
    for launch in ("moe_gate_up", "moe_down"):
        calls = re.findall(rf"^\s*%?{launch}[.\d]* = .*custom-call\(.*"
                           rf'custom_call_target="tpu_custom_call"', text,
                           re.M)
        assert len(calls) == applies, (launch, len(calls))
    assert [line for line in text.splitlines()
            if " while(" in line and "moe_experts" in line] == []
    bank = (m["n_routed_experts"] * m["d_model"]
            * m["moe_intermediate_size"] * 2)
    _, moves = _moves(text)
    assert [what for what, _, size, _ in moves if size >= bank] == []


# -- deepseek-v3.2-exp-serve: what the compiled ticks do to the cache ---------

_CALLS = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_MOVE = re.compile(r"(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]"
                   r"\{([\d,]*)[^ ]* (copy|transpose)\(")
_LEAF = re.compile(r"%?([\w.\-]+) = bf16\[([\d,]*)\]\{([\d,]*)[^ ]* "
                   r"parameter\(\d+\).*op_name=\"cache\[\\'layers_\d+\\'\]"
                   r"\[\\'attn\\'\]\[\\'(\w+)\\'\]\"")


def _moves(text):
    """``(entry parameters of the cache, copies and transposes)`` of a
    compiled module's text: each cache leaf as ``(leaf name, dims, minor
    to major)``, each ``copy`` or ``transpose`` as ``(instruction, dims,
    bytes, in a loop's body)``, a fusion's or a call's instructions
    counted under the loop that reaches them."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        if line[:1] not in ("", " ", "}") and line.rstrip().endswith("{"):
            head = re.match(r"(ENTRY\s+)?%?([\w.\-]+)", line)
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif name is not None and line.startswith(" "):
            comps[name].append(line.strip())
    looped = set()
    todo = [body for lines in comps.values() for line in lines
            for body in re.findall(r" while\(.*body=%?([\w.\-]+)", line)]
    while todo:
        c = todo.pop()
        if c in comps and c not in looped:
            looped.add(c)
            for line in comps[c]:
                todo += _CALLS.findall(line)
                for group in _BRANCHES.findall(line):
                    todo += [b.strip().lstrip("%") for b in group.split(",")]
    leaves = [(m.group(4), _dims(m.group(2)), m.group(3))
              for m in map(_LEAF.match, comps[entry]) if m]
    size = {"bf16": 2, "pred": 1, "s8": 1, "u8": 1}  # else 4 bytes
    moves = []
    for c, lines in comps.items():
        for m in filter(None, map(_MOVE.match, lines)):
            dims = _dims(m.group(3))
            moves.append((f"{m.group(5)} %{m.group(1)} {m.group(2)}"
                          f"[{m.group(3)}]{{{m.group(4)}}} in %{c}", dims,
                          math.prod(dims) * size.get(m.group(2), 4),
                          c in looped))
    return leaves, moves


def _dims(text):
    return tuple(int(d) for d in text.split(",") if d)


@pytest.mark.parametrize("C,temp_gb", [
    (64, 1.7),   # the mixed tick, packed by blocks (live = 32 * 64)
    (1, None),   # the decode tick
])
def test_the_latent_cache_is_read_where_it_lies(for_chip, one_chip, C,
                                                temp_gb):
    """chipbench's ``deepseek-v3.2-exp-serve`` at its real size (32
    slots of 12 288 positions, five layers), both tick programs as the
    engine builds them, from shapes alone: the chip stores every cache
    leaf as the model declares it (a minor axis of whole 128-lane
    groups), so no layer copies the pool into another layout and back.
    One leaf ``[32, 12288, 576]`` was stored positions-minor, and ten
    copies of 453 MB a tick were a fifth of the device's time (PR 40)."""
    from chipbench.harness import spec
    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import engine

    cfg = spec.load("configs", "deepseek-v3.2-exp-serve")
    S, V = cfg["engine"]["slots"], cfg["model"]["vocab_size"]
    L, m = cfg["engine"]["max_len"], cfg["model"]
    model = get_model(spec.model_name(cfg), **m,
                      dtype=jnp.dtype(cfg["compute_dtype"]))
    dm = model.clone(decode=True, slot_cursor=True, parent=None)
    cache = jax.eval_shape(dm.init, jax.random.PRNGKey(0),
                           jnp.zeros((S, 1), I32))["cache"]
    params = jax.eval_shape(
        lambda: spec.reference(cfg).make_params(cfg, 0))["params"]
    # the cache is what the configuration's `slots` line states
    per_position = sum(math.prod(x.shape) * x.dtype.itemsize
                       for x in jax.tree.leaves(cache) if x.ndim == 3)
    assert per_position == S * L * 7040
    args = _abstract(({"params": params}, cache,
                      jax.ShapeDtypeStruct((S, V), F32),
                      jax.ShapeDtypeStruct((S, 2), jnp.uint32),
                      jax.ShapeDtypeStruct((S * C + 2 * S,), I32)), one_chip)
    compiled = engine._mixed_tick_fn(
        engine._CacheLayout(dm), (engine._IDLE_CFG,) * S, C, None,
        S * C if C > 1 else None).lower(*args).compile()
    leaves, moves = _moves(compiled.as_text())

    # nothing the size of the 512-wide leaf (403 MB) or larger is moved
    pool = S * L * m["kv_lora_rank"] * 2
    assert [what for what, _, size, _ in moves if size >= pool] == []
    # every cache leaf enters in the layout its shape declares
    assert {name for name, _, _ in leaves} >= {"latent", "index_key"}
    assert [leaf for leaf in leaves if leaf[2] != "2,1,0"] == []
    # of the cache, at most the small leaf is moved, once in and out a layer
    shapes = {tuple(sorted(dims)): name for name, dims, _ in leaves}
    moved = [(shapes[tuple(sorted(dims))], what)
             for what, dims, _, _ in moves if tuple(sorted(dims)) in shapes]
    assert [x for x in moved if x[0] != "rope_key"] == []
    assert len(moved) <= 10, moved
    # the weights' relayouts are made once a tick, not once a block
    weights = {x.shape for x in jax.tree.leaves(params) if x.size >= 1 << 20}
    assert [what for what, dims, _, looped in moves
            if looped and dims in weights] == []
    if temp_gb is not None:
        assert compiled.memory_analysis().temp_size_in_bytes <= temp_gb * 1e9
