"""``afmoe_lm`` (Trinity-Mini's architecture, PR 44) trained through
``LMTrainer``, held to its plain reference
``chipbench/references/afmoe.py`` on seeded weights at a tiny size, and
the two kernels' new parts (the attention's band and grouped KV heads,
the grouped matmul's gradient) held to plain forms in interpret mode.

Tolerances: model and reference both compute in float32 here, so what
separates them is the order of sums (a fused loss over chunks, a
grouped matmul over sorted rows, an online softmax): 1e-5 relative a
loss is an order above what that gives at these sizes, and far below
what a wrong mask, a dropped norm or a bias that took a gradient gives
(1e-2 and more).
"""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.references import afmoe as reference
from distkeras_tpu.data.dataset import PartitionedDataset
from distkeras_tpu.models import get_model
from distkeras_tpu.models.blocks import RoutedExpertsByPart
from distkeras_tpu.ops import grouped_experts as ge
from distkeras_tpu.ops.pallas_attention import pallas_causal_attention
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.parallel.spmd import lm_step_model, make_lm_train_step
from distkeras_tpu.trainers import LMTrainer

MODEL = dict(
    vocab_size=64, d_model=128, num_layers=4, num_dense_layers=1,
    num_heads=4, num_kv_heads=2, head_dim=128, sliding_window=24,
    layer_types=["sliding_attention", "sliding_attention",
                 "full_attention", "sliding_attention"],
    intermediate_size=192, moe_intermediate_size=64, n_routed_experts=16,
    num_experts_per_tok=2, experts_held=4, expert_rank=1)
CONFIG = {
    "model": MODEL, "precision": {"parameters": "float32"},
    "trainer": {"batch_size": 2, "schedule": {
        "init": 1e-3, "peak": 1e-2, "warmup_steps": 10}}}
T = 64


def _model(**kw):
    return get_model("afmoe_lm", **MODEL, dtype=jnp.float32, expert_tile=8,
                     **kw)


def _corpus(rows: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (rows, T)).astype(np.int32)


def _row_losses(model, variables, batch):
    logits = model.apply(variables, jnp.asarray(batch))
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(
        logp, jnp.asarray(batch)[:, 1:, None], axis=-1)[..., 0].mean(-1)


def test_the_parameters_are_the_references_by_name_and_shape():
    variables = reference.make_params(CONFIG, 7)
    init = jax.eval_shape(_model().init, jax.random.PRNGKey(0),
                          jnp.zeros((1, T), jnp.int32))
    shapes = lambda tree: jax.tree.map(lambda a: a.shape, tree)  # noqa: E731
    assert shapes(variables["params"]) == shapes(init["params"])


@pytest.mark.parametrize("attention", ["dense", "pallas"])
def test_row_losses_against_the_reference(attention):
    variables = reference.make_params(CONFIG, 11)
    batch = _corpus(2, seed=1)
    got = _row_losses(_model(attention=attention), variables, batch)
    want = reference.row_losses(CONFIG, variables, batch)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the window is shorter than the sequence: attending the whole
    # wedge in the window layers is another model
    wrong = reference.row_losses(CONFIG, variables, batch, "no_window")
    assert abs(wrong[0] - want[0]) / want[0] > 1e-3


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Three optimizer steps through ``LMTrainer`` (one window of three)
    on the reference's weights, and the reference's own four losses and
    its state after three updates."""
    corpus = _corpus(8, seed=2)
    sched = CONFIG["trainer"]["schedule"]
    path = str(tmp_path_factory.mktemp("afmoe") / "metrics.jsonl")
    trainer = LMTrainer(
        _model(remat="block"), params=reference.make_params(CONFIG, 5),
        axes={"dp": 1}, batch_size=2, num_epoch=1, metrics_path=path,
        worker_optimizer=optax.adam(optax.linear_schedule(
            sched["init"], sched["peak"], sched["warmup_steps"])))
    trainer.train(PartitionedDataset.from_arrays(
        {"tokens": corpus[:6]}, num_partitions=1))
    losses, norms, after = reference.train_losses(
        CONFIG, reference.make_params(CONFIG, 5),
        corpus.reshape(4, 2, T), return_params=True)
    return trainer, losses, norms, after, path


def test_first_losses_through_lmtrainer_against_the_reference(trained):
    trainer, losses, _, _, _ = trained
    got = [h["loss"] for h in trainer.history]
    assert len(got) == 3
    # the second and third follow an adam update and a bias update
    np.testing.assert_allclose(got, losses[:3], rtol=1e-5)
    assert got[2] < got[0]


def test_the_bias_lands_where_the_references_does(trained):
    trainer, _, norms, after, _ = trained
    start = reference.make_params(CONFIG, 5)["params"]
    for i in (1, 2, 3):
        name = f"layers_{i}"
        got = trainer.params["params"][name]["moe"]["e_score_correction_bias"]
        want = after["params"][name]["moe"]["e_score_correction_bias"]
        np.testing.assert_allclose(got, want, atol=1e-7)
        # three steps of +-1e-3 (or none where the load was the mean)
        moved = np.asarray(got) - np.asarray(
            start[name]["moe"]["e_score_correction_bias"])
        assert np.abs(moved).max() <= 3e-3 + 1e-7
        assert np.abs(moved).max() >= 1e-3 - 1e-7
        # selection only: no gradient reaches it
        assert norms[name]["moe"]["e_score_correction_bias"] == 0.0
        # and a weight the optimizer moved agrees too (adam's first
        # steps are lr * sign-like: 1e-3 to 3e-3 each here, and a
        # gradient near zero takes its sign from the order of a sum)
        np.testing.assert_allclose(
            trainer.params["params"][name]["moe"]["w_down"],
            after["params"][name]["moe"]["w_down"], atol=2e-4)


def test_the_bias_takes_no_gradient_in_the_program():
    model, variables = _model(), reference.make_params(CONFIG, 3)
    batch = jnp.asarray(_corpus(1, seed=4))
    grads = jax.grad(lambda v: _row_losses(model, v, batch).sum())(variables)
    for i in (1, 2, 3):
        moe = grads["params"][f"layers_{i}"]["moe"]
        assert float(jnp.abs(moe["e_score_correction_bias"]).max()) == 0.0
        assert float(jnp.abs(moe["router"]).max()) > 0.0


def test_metrics_rows_gain_the_step_counters(trained):
    import json

    trainer, _, _, _, path = trained
    with open(path) as f:
        rows = [r for r in map(json.loads, f) if "step" in r]
    assert len(rows) == 3
    pairs = 2 * T * MODEL["num_experts_per_tok"] * 3  # three expert layers
    for row in rows:
        assert 0 < row["routed_here"] < pairs
        assert 1.0 <= row["expert_load_max_over_mean"] <= 16.0
        # what the chip pays of it: over its own experts alone
        held = MODEL["experts_held"]
        assert 1.0 <= row["held_load_max_over_mean"] <= held
        assert 0 < row["routed_here_over_even"] < \
            MODEL["n_routed_experts"] / held
        assert row["router_bias_abs_max"] > 0
    assert set(trainer.history[0]) == {
        "loss", "routed_here", "expert_load_max_over_mean",
        "held_load_max_over_mean", "routed_here_over_even",
        "router_bias_abs_max"}


def test_rows_of_a_model_without_experts_hold_the_loss_alone(tmp_path):
    import json

    model = get_model("transformer_lm", vocab_size=64, d_model=32,
                      num_heads=2, num_layers=1, max_len=16)
    trainer = LMTrainer(model, axes={"dp": 1}, batch_size=2, num_epoch=1,
                        metrics_path=str(tmp_path / "m.jsonl"))
    trainer.train(PartitionedDataset.from_arrays(
        {"tokens": _corpus(4)[:, :16]}, num_partitions=1))
    assert [set(h) for h in trainer.history] == [{"loss"}] * 2
    with open(tmp_path / "m.jsonl") as f:
        rows = [r for r in map(json.loads, f) if "step" in r]
    assert all(set(r) == {"t", "step", "samples", "loss"} for r in rows)


# -- the shares add up --------------------------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips of one expert each: their parts, with the shared
    expert counted once, are the reference's layer with every expert
    held."""
    whole = {"model": dict(MODEL, experts_held=None, expert_rank=0),
             "precision": {"parameters": "float32"}}
    uncut = reference.sizes(whole)
    p = reference.make_params(whole, 9)["params"]["layers_1"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 48, MODEL["d_model"]))
    live = jnp.ones(u.shape[:2], bool)
    want, load = reference._experts(uncut, p, u[0], "f32")
    assert float(load.sum()) == 48 * MODEL["num_experts_per_tok"]
    shared = reference._swiglu(p["shared"], u[0], "f32")
    total = shared
    for rank in range(16):
        module = RoutedExpertsByPart(
            n_routed_experts=16, experts_held=1, expert_rank=rank,
            num_experts_per_tok=2, n_group=1, topk_group=1,
            routed_scaling_factor=2.826, width=64, n_shared_experts=1,
            dtype=jnp.float32, expert_tile=8, d_model=MODEL["d_model"])
        share = {**p, **{k: p[k][rank:rank + 1]
                         for k in ("w_gate", "w_up", "w_down")}}
        total = total + module.apply({"params": share}, u, live)[0] - shared
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


# -- the attention's band and grouped heads (interpret mode) ------------------


def _plain_attention(q, k, v, window):
    B, T_, H, hd = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    qp, kp = jnp.arange(T_)[:, None], jnp.arange(T_)[None, :]
    ok = qp >= kp
    if window is not None:
        ok &= kp > qp - window
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(ok, s, -jnp.inf), -1), v)


BANDS = [
    (4, 4, 16, None),   # the parent's program
    (4, 2, 16, None),   # grouped heads under the whole wedge
    (4, 2, 16, 24),     # a band that cuts tiles, grouped heads
    (8, 2, 16, 16),     # a band of whole tiles, a group of four
    (4, 4, 16, 17),     # one key past a tile's edge
    (4, 1, 16, 40),     # all query heads on one KV head
    (2, 2, 32, 8),      # a band inside the diagonal tile
    (2, 2, 16, 100),    # a window longer than the sequence
    # PR 45, what an off-by-one in "interior" would break: a band whose
    # lower edge falls one key before, on and one key after a tile's
    # edge (k * block - 1, k * block, k * block + 1) ...
    (4, 4, 16, 15),
    (4, 2, 16, 31),
    (4, 2, 16, 32),
    (8, 2, 16, 33),
    (2, 2, 16, 47),
    (8, 1, 16, 48),
    (2, 2, 16, 49),
    (2, 2, 8, 16),      # ... and with eight blocks a sequence
    (2, 2, 8, 17),
    (2, 2, 8, 23),
    (4, 2, 16, 1),      # a band of the diagonal's own key alone
    (4, 4, 16, 64),     # a window of exactly the sequence
    (4, 4, 16, 63),     # and one key short of it
    (2, 2, 64, None),   # one block a sequence: its only tile an edge
    (2, 1, 64, 24),
    (8, 1, 8, 24),      # a group of eight; walks clipped at block 0
    (8, 1, 16, None),   # a group of eight under the whole wedge
]


def _through(attend, H, Hk):
    key = jax.random.PRNGKey(0)
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i),
                                    (2, 64, h, 128), jnp.float32)
                  for i, h in enumerate((H, Hk, Hk, H)))
    out, pull = jax.vjp(attend, q, k, v)
    return (out,) + pull(g)  # the values, then dq, dk, dv


@pytest.mark.parametrize("H,Hk,block,window", BANDS)
def test_the_banded_kernel_against_a_plain_masked_attention(H, Hk, block,
                                                            window):
    got = _through(lambda q, k, v: pallas_causal_attention(
        q, k, v, block, window), H, Hk)
    want = _through(lambda q, k, v: _plain_attention(q, k, v, window),
                    H, Hk)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("H,Hk,block,window", [
    band for band in BANDS if band[2] < 64])
def test_two_tiles_a_step_change_no_value(monkeypatch, H, Hk, block, window):
    """A grid step holds two tiles of a walk where the blocks pair up
    (the one that lies outside the walk skipped); one tile a step visits
    the same tiles in the same order: values and the three gradients
    equal bit for bit, and the census counts the same tiles in more
    steps."""
    from distkeras_tpu.ops import pallas_attention

    def run():
        return _through(lambda q, k, v: pallas_causal_attention(
            q, k, v, block, window), H, Hk), pallas_attention.tile_census(
                64, block, window, H // Hk)

    assert pallas_attention._span(64 // block) == 2
    got, paired = run()
    monkeypatch.setattr(pallas_attention, "_span", lambda nq: 1)
    want, single = run()
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    for launch, c in single.items():
        assert c["steps"] == c["interior"] + c["edge"] >= paired[launch][
            "steps"] >= c["steps"] / 2
        assert {**c, "steps": 0} == {**paired[launch], "steps": 0}


def test_the_kernel_refuses_heads_it_cannot_group():
    q = jnp.zeros((1, 16, 4, 128))
    with pytest.raises(ValueError, match="whole group"):
        pallas_causal_attention(q, q[:, :, :3], q[:, :, :3], 16)
    with pytest.raises(ValueError, match="window=0"):
        pallas_causal_attention(q, q, q, 16, 0)


# -- the grouped matmul's gradient (interpret mode) ---------------------------


def test_the_grouped_matmuls_gradients_against_a_per_expert_loop():
    """Four held experts (2..5 of 8), one of them sent no row, one sent
    more than a tile: the launches' value and all five gradients against
    a loop over the sorted pairs."""
    N, D, F, E, k, tile, first = 24, 128, 128, 4, 2, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    x = jax.random.normal(ks[0], (N, D))
    wg, wu = (jax.random.normal(ks[i], (E, D, F)) / np.sqrt(D)
              for i in (1, 2))
    wd = jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)
    experts = np.array(jax.random.randint(ks[4], (N, k), 0, 8))
    experts[experts == 3] = 7  # expert 3 (local 1) is sent nothing
    gates = jax.random.uniform(ks[5], (N, k), jnp.float32, 0.2, 1.0)
    local = experts - first
    key = np.where((local >= 0) & (local < E), local, E).reshape(N * k)
    order = np.argsort(key, kind="stable")
    sizes = (key[:, None] == np.arange(E)).sum(0).astype(np.int32)
    assert sizes[1] == 0 and sizes.max() > tile
    tok = jnp.asarray((order // k).astype(np.int32))
    gw = jax.random.normal(ks[6], (N, D))

    def loop(x, gate, wg, wu, wd):
        y, at = jnp.zeros((N, D)), 0
        for e, n in enumerate(sizes):
            for r in range(at, at + n):
                xr = x[tok[r]]
                h = jax.nn.silu(xr @ wg[e]) * (xr @ wu[e])
                y = y.at[tok[r]].add(gate[r] * (h @ wd[e]))
            at += n
        return y

    def launches(x, gate, wg, wu, wd):
        return ge.grouped_experts(x, tok, gate, jnp.asarray(sizes), wg, wu,
                                  wd, tile=tile, interpret=True)

    def through(fn):
        return jax.value_and_grad(
            lambda x, gates, *w: jnp.sum(fn(
                x, gates.reshape(N * k)[order], *w) * gw),
            (0, 1, 2, 3, 4))(x, gates, wg, wu, wd)

    with jax.default_matmul_precision("highest"):
        got, want = through(launches), through(loop)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=3e-5)
    # the expert that was sent nothing: exactly zero, not what the
    # launch's blocks held
    for g in got[1][2:]:
        assert float(jnp.abs(g[1]).max()) == 0.0


def test_a_result_block_narrows_for_a_training_steps_tokens():
    # a serving tick's rows keep the block PR 43 measured
    assert ge._result_block(2048, 1536, 4096, 2) == ge._block(2048, 1536, 1, 2)
    assert ge._result_block(2048, 1024, 8192, 2) == 512
    assert ge._result_block(2048, 2048, 16384, 2) == 256


# -- what a model has to offer the LM step ------------------------------------


def test_a_model_that_lacks_a_piece_is_refused_by_name():
    class NoFeatures:
        pass

    with pytest.raises(ValueError, match="no features_only field"):
        lm_step_model(NoFeatures())
    model = _model()
    with pytest.raises(ValueError, match="no head subtree with a kernel"):
        lm_step_model(model, {"params": {"embed": {}, "lm_head": {}}})
    assert lm_step_model(model, reference.make_params(
        CONFIG, 1)).features_only


@pytest.mark.parametrize("axis", ["sp", "tp", "pp", "ep"])
def test_the_model_refuses_the_axes_it_cannot_train_on(axis):
    trainer = LMTrainer(_model(), axes={"dp": 1, axis: 2}, batch_size=2)
    with pytest.raises(ValueError, match=f"afmoe_lm cannot be trained "
                                         f"with {axis}=2"):
        trainer.train(PartitionedDataset.from_arrays(
            {"tokens": _corpus(4)}, num_partitions=1))


def test_the_engine_refuses_the_model():
    from distkeras_tpu.serving.engine import ServingEngine

    with pytest.raises(ValueError, match="afmoe_lm cannot be served"):
        ServingEngine(_model(), reference.make_params(CONFIG, 1), slots=2,
                      max_len=32)


# -- the step of the model that was there lowers to the parent's text ---------

# sha256 of the lowered window step of a small ``transformer_lm`` with
# the Pallas attention (interpret mode inlines the kernels' bodies,
# grids and index maps). Taken at PR 45, which changed this step on
# purpose (the launches' enumerated steps of two tiles, the forward's
# state over whole vregs, the dk/dv launch keys-first); from PR 44's
# parent (36ad6f2) to PR 45's it read 1e9776ce...: the band, the grouped
# heads and the step's counters are Python-level branches that this
# model never takes. A deliberate change to that step updates the hash.
PARENT_STEP = "6dc81649b80927be1fe8f87802a635f7b138ade91f47ef57a0a98df1be02499c"


def test_the_gpt_blocks_window_step_lowers_to_the_parents_text():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distkeras_tpu.parallel.spmd import lm_state_shardings

    model = get_model("transformer_lm", vocab_size=64, d_model=256,
                      num_heads=2, num_layers=2, max_len=128,
                      dtype=jnp.bfloat16, attention="pallas")
    mesh = make_mesh({"dp": 1, "sp": 1})
    opt = optax.adam(optax.linear_schedule(2e-5, 2e-4, 100))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    p_sh, o_sh = lm_state_shardings(opt, mesh, params)

    def abstract(tree, sh):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, sh)

    text = make_lm_train_step(model, opt, mesh, window=True).lower(
        abstract(params, p_sh), abstract(jax.eval_shape(opt.init, params),
                                         o_sh),
        jax.ShapeDtypeStruct((2, 2, 128), jnp.int32, sharding=NamedSharding(
            mesh, P(None, "dp")))).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP
