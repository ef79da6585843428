"""The engine holds its weights in the dtype the model multiplies in.

``models/transformer.py · compute_params`` applies, once, the cast a
module makes as the first thing it does with a leaf (``TPDenseGeneral``
kernel and bias, ``VocabHead`` kernel, ``nn.Embed`` embedding, the
Switch MoE expert banks); ``ServingEngine`` holds that tree, so its tick
programs neither read the float32 leaves nor convert them every tick.
Held here:

- the cast tree gives the handed tree's logits bit for bit, through
  prefill and through a decode step on the slot cache, and the leaves a
  module uses in float32 (LayerNorm, the head's bias, the router) stay;
- a float32 model, a tree already in the compute dtype and a model that
  brings no rule come back as handed, leaf objects included;
- an engine built on float32 weights streams ``generate()``'s tokens on
  the float32 tree, and counts what it holds beside what it was handed;
- ``update_weights`` still takes the tree as handed at construction,
  refuses any other with the leaf's name, and compiles nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import deepseek_v32 as deepseek_reference
from distkeras_tpu import telemetry
from distkeras_tpu.models import get_model
from distkeras_tpu.models.transformer import compute_params, generate
from distkeras_tpu.serving import ServingEngine, WeightPushError

V, D, H, L, MAXLEN = 96, 32, 4, 2, 64
VARIANTS = {
    "dense": {},
    "gqa": {"num_kv_heads": 2},
    "switch_moe": {"moe_experts": 4},
}
# leaves a module uses in float32: casting one changes the numbers
STAY_F32 = ("LayerNorm_0", "LayerNorm_1", "ln_f", "router")


def _model(variant="dense", dtype=jnp.bfloat16):
    return get_model(
        "transformer_lm", vocab_size=V, d_model=D, num_heads=H,
        num_layers=L, max_len=MAXLEN, attention="dense", dtype=dtype,
        **VARIANTS[variant])


def _init(model, seed=0):
    variables = model.init(jax.random.PRNGKey(seed),
                           jnp.zeros((1, 4), jnp.int32))
    # biases are initialised at zero: give every leaf a value a cast moves
    leaves, treedef = jax.tree.flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return {"params": treedef.unflatten([
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])}


def _paths(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def _bytes(tree):
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("registry", telemetry.MetricRegistry())
    kw.setdefault("tracer", telemetry.Tracer())
    return ServingEngine(model, params, **kw)


def _ref(model, params, prompt, n, **kw):
    return np.asarray(
        generate(model, params, jnp.asarray(prompt)[None], n, **kw)
    )[0, len(prompt):].tolist()


def _stream(engine, prompt, n, **kw):
    req = engine.submit(prompt, max_new_tokens=n, **kw)
    engine.drain()
    return req.stream.tokens(timeout=5)


PROMPT = (np.arange(1, 22, dtype=np.int32) * 7) % V


# -- (a) the cast tree is the handed tree, to the model ----------------------


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_prefill_logits_are_the_handed_trees(variant):
    model = _model(variant)
    handed = _init(model)
    held = compute_params(model, handed)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, V)
    want = model.apply(handed, tokens)
    got = model.apply(held, tokens)
    assert want.dtype == jnp.float32
    assert np.array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_decode_step_on_the_slot_cache_is_the_handed_trees(variant):
    """A chunk into the slot cache, then one token at each row's own
    cursor: the programs the engine's ticks run."""
    model = _model(variant)
    handed = _init(model)
    held = compute_params(model, handed)
    dm = model.clone(decode=True, slot_cursor=True, parent=None)
    chunk = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, V)
    step = jax.random.randint(jax.random.PRNGKey(5), (2, 1), 0, V)
    valid = jnp.asarray([8, 5], jnp.int32)
    out = []
    for tree in (handed, held):
        cache = dm.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 1), jnp.int32))["cache"]
        first, mut = dm.apply({**tree, "cache": cache}, chunk,
                              valid_lens=valid, mutable=["cache"])
        second, _ = dm.apply({**tree, "cache": mut["cache"]}, step,
                             mutable=["cache"])
        out.append((np.asarray(first), np.asarray(second)))
    assert np.array_equal(out[0][0], out[1][0])
    assert np.array_equal(out[0][1], out[1][1])


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_only_what_a_module_casts_first_is_cast(variant):
    model = _model(variant)
    handed = _init(model)
    held = _paths(compute_params(model, handed))
    for path, leaf in _paths(handed).items():
        assert leaf.dtype == jnp.float32
        stays = (any(f"['{name}']" in path for name in STAY_F32)
                 or path.endswith("['head']['bias']"))
        assert held[path].dtype == (jnp.float32 if stays else jnp.bfloat16), \
            path
        if stays:
            assert held[path] is leaf
        else:
            assert np.array_equal(
                np.asarray(held[path]),
                np.asarray(leaf.astype(jnp.bfloat16)))
    cast = [p for p, x in held.items() if x.dtype == jnp.bfloat16]
    assert any("embed" in p for p in cast)
    assert any("['head']['kernel']" in p for p in cast)
    if variant == "switch_moe":
        assert sum("['moe']" in p for p in cast) == 4 * L


# -- (b) identity where there is nothing to cast ------------------------------


def _same_objects(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(x is y for x, y in zip(la, lb))


@pytest.mark.parametrize("case", ["float32_model", "already_cast",
                                  "no_rule"])
def test_nothing_to_cast_returns_the_handed_leaves(case):
    if case == "float32_model":
        model = _model(dtype=jnp.float32)
        tree = _init(model)
    elif case == "already_cast":
        model = _model()
        tree = compute_params(model, _init(model))
    else:
        class Plain:
            dtype = jnp.bfloat16

        model, tree = Plain(), _init(_model())
    assert _same_objects(compute_params(model, tree), tree)


# -- (b2) one program makes the casts ------------------------------------------


def _leaf_by_leaf(model, tree):
    """The cast as it was made before there was one program: every leaf
    the rule names, eagerly and alone."""
    def cast(path, leaf):
        names = [k.key for k in path
                 if isinstance(k, jax.tree_util.DictKey)]
        return (leaf.astype(model.dtype)
                if leaf.dtype != model.dtype and model.casts_first(names)
                else leaf)

    return jax.tree_util.tree_map_with_path(cast, tree)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_one_program_cast_is_the_leaf_by_leaf_cast(variant):
    """Leaf for leaf the bits and dtypes of the eager casts, from ONE
    compiled program (nine shapes of leaf were nine small compiles a
    process), with the handed tree left alive: it is the caller's."""
    from distkeras_tpu.models import transformer

    model = _model(variant)
    handed = _init(model)
    before = {path: np.asarray(x) for path, x in _paths(handed).items()}
    transformer._cast_program.cache_clear()
    held = _paths(compute_params(model, handed))
    want = _paths(_leaf_by_leaf(model, handed))
    assert held.keys() == want.keys()
    for path, leaf in want.items():
        assert held[path].dtype == leaf.dtype, path
        assert np.array_equal(np.asarray(held[path]).view(np.uint8),
                              np.asarray(leaf).view(np.uint8)), path
        if leaf.dtype == jnp.float32:
            assert held[path] is _paths(handed)[path]
    assert transformer._cast_program.cache_info().currsize == 1
    program = transformer._cast_program(
        jnp.dtype(model.dtype),
        (None,) * sum(x.dtype == jnp.bfloat16 for x in held.values()))
    assert program._cache_size() == 1
    # not donated: every handed leaf still reads what it read
    for path, x in _paths(handed).items():
        assert not x.is_deleted()
        assert np.array_equal(np.asarray(x), before[path])


def test_update_weights_applies_the_same_program(served):
    from distkeras_tpu.models import transformer

    model, pa, pb = served
    transformer._cast_program.cache_clear()
    eng = _engine(model, pa)
    assert transformer._cast_program.cache_info().currsize == 1
    eng.update_weights(pb)
    # the same jitted function, found again by dtype and placements
    info = transformer._cast_program.cache_info()
    assert (info.currsize, info.hits) == (1, 1)
    want = _paths(_leaf_by_leaf(model, pb))
    for path, leaf in _paths(eng._params_only).items():
        assert np.array_equal(np.asarray(leaf).view(np.uint8),
                              np.asarray(want[path]).view(np.uint8))
    assert all(not x.is_deleted() for x in jax.tree.leaves(pb))


# -- (c) the engine serves the handed tree's tokens ---------------------------


@pytest.fixture(scope="module")
def served():
    model = _model()
    return model, _init(model, 0), _init(model, 1)


@pytest.mark.parametrize("sampling", [
    {},
    {"temperature": 0.8, "top_k": 20, "seed": 11},
], ids=["greedy", "sampled"])
def test_engine_on_f32_weights_streams_generates_tokens(served, sampling):
    model, pa, _ = served
    eng = _engine(model, pa)
    assert _stream(eng, PROMPT, 12, **sampling) == _ref(
        model, pa, PROMPT, 12, **sampling)


def test_stats_count_the_held_tree_beside_the_handed(served):
    model, pa, _ = served
    eng = _engine(model, pa)
    held = compute_params(model, pa)
    st = eng.stats()
    assert st["weight_bytes_handed"] == _bytes(pa)
    assert st["weight_bytes_held"] == _bytes(held) == _bytes(
        eng._params_only)
    held_at = _paths(held)
    cast_f32_bytes = sum(x.nbytes for path, x in _paths(pa).items()
                         if held_at[path].dtype == jnp.bfloat16)
    assert cast_f32_bytes > 0
    assert (st["weight_bytes_handed"] - st["weight_bytes_held"]
            == cast_f32_bytes // 2)
    # the engine holds none of the handed leaves it replaced
    handed = {id(x) for x in jax.tree.leaves(pa)}
    kept = [x for x in jax.tree.leaves(eng._params_only)
            if id(x) in handed]
    assert all(x.dtype == jnp.float32 for x in kept)
    assert len(kept) == sum(y.dtype == jnp.float32
                            for y in jax.tree.leaves(held))


def test_a_float32_engine_holds_what_it_was_handed():
    model = _model(dtype=jnp.float32)
    params = _init(model)
    eng = _engine(model, params)
    assert _same_objects(eng._params_only, {"params": params["params"]})
    st = eng.stats()
    assert st["weight_bytes_held"] == st["weight_bytes_handed"] == _bytes(
        params)


def test_under_a_mesh_the_cast_leaves_carry_the_serving_specs(served):
    """The cast is made where the placed leaves lie: a sharded kernel
    stays sharded as ``lm_param_specs`` says, at half the bytes."""
    from jax.sharding import Mesh, PartitionSpec as P

    model, pa, pb = served
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    eng = _engine(model, pa, mesh=mesh)
    held = eng._params_only["params"]
    up = held["Block_0"]["mlp_up"]["kernel"]
    assert up.dtype == jnp.bfloat16
    assert up.sharding.spec == P(None, "model")
    assert up.addressable_shards[0].data.shape == (D, 4 * D // 2)
    assert held["ln_f"]["scale"].dtype == jnp.float32
    assert eng.stats()["weight_bytes_held"] == _bytes(
        compute_params(model, pa))
    assert len(_stream(eng, PROMPT, 8)) == 8
    eng.mark_steady()
    eng.update_weights(pb)
    again = eng._params_only["params"]["Block_0"]["mlp_up"]["kernel"]
    assert again.dtype == jnp.bfloat16
    assert again.sharding.spec == P(None, "model")
    assert len(_stream(eng, PROMPT, 8)) == 8
    assert eng.recompiles_since_mark() == {}


# -- (d) update_weights keeps its contract ------------------------------------


def test_update_weights_takes_the_tree_as_handed_and_compiles_nothing(
        served):
    model, pa, pb = served
    eng = _engine(model, pa)
    assert _stream(eng, PROMPT, 8) == _ref(model, pa, PROMPT, 8)
    eng.mark_steady()
    out = eng.update_weights(pb)
    assert out["version"] == 2 == eng.weight_version
    assert eng.weight_swaps == 1
    assert _stream(eng, PROMPT, 8) == _ref(model, pb, PROMPT, 8)
    assert eng.recompiles_since_mark() == {}
    # what it holds after the swap is the cast of what was pushed
    want = _paths(compute_params(model, pb))
    for path, leaf in _paths(eng._params_only).items():
        assert leaf.dtype == want[path].dtype
        assert np.array_equal(np.asarray(leaf), np.asarray(want[path]))
    assert eng.stats()["weight_bytes_held"] == _bytes(eng._params_only)


def test_update_weights_takes_a_host_tree(served):
    model, pa, pb = served
    eng = _engine(model, pa)
    eng.update_weights(jax.tree.map(np.asarray, pb), version=7)
    assert eng.weight_version == 7
    assert _stream(eng, PROMPT, 8) == _ref(model, pb, PROMPT, 8)


@pytest.mark.parametrize("fault", ["held_dtype", "one_bf16_leaf",
                                   "wrong_shape"])
def test_update_weights_refuses_what_was_not_handed(served, fault):
    model, pa, pb = served
    eng = _engine(model, pa)
    if fault == "held_dtype":
        # the tree the engine holds is not the tree it was handed
        bad = compute_params(model, pb)
        leaf = "['Block_0']['CausalSelfAttention_0']['out']['bias']"
    elif fault == "one_bf16_leaf":
        bad = jax.tree.map(lambda x: x, pb)
        bad["params"]["head"]["kernel"] = (
            bad["params"]["head"]["kernel"].astype(jnp.bfloat16))
        leaf = "['head']['kernel']"
    else:
        bad = jax.tree.map(lambda x: x, pb)
        bad["params"]["embed"]["embedding"] = jnp.zeros(
            (V + 1, D), jnp.float32)
        leaf = "['embed']['embedding']"
    before = eng._params_only
    with pytest.raises(WeightPushError) as err:
        eng.update_weights(bad)
    assert err.value.leaf == leaf and leaf in str(err.value)
    assert eng._params_only is before
    assert eng.weight_version == 1 and eng.weight_swaps == 0
    assert _stream(eng, PROMPT, 8) == _ref(model, pa, PROMPT, 8)


# -- (e) a model that brings no rule is held as handed ------------------------


def test_deepseek_handed_bf16_sees_the_handed_leaves():
    cfg = {"model": dict(
        vocab_size=96, d_model=64, num_layers=2, first_k_dense=1,
        num_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, index_n_heads=4,
        index_head_dim=16, index_topk=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=16,
        num_experts_per_tok=4, n_group=4, topk_group=2, experts_held=4,
        expert_rank=0, rope_original_len=32, max_len=64, kv_tile=16,
        expert_tile=8), "precision": {"parameters": "bfloat16"}}
    params = deepseek_reference.make_params(cfg, 3)
    model = get_model("deepseek_v32_lm", **cfg["model"],
                      dtype=jnp.bfloat16)
    eng = _engine(model, params, prefill_chunk=8)
    assert _same_objects(eng._params_only, {"params": params["params"]})
    st = eng.stats()
    assert st["weight_bytes_held"] == st["weight_bytes_handed"] == _bytes(
        params)
    # a push stages its own copy, as it always did, and casts nothing
    eng.update_weights(params)
    assert all(x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(eng._params_only), jax.tree.leaves(params)))
