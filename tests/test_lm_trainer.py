"""LMTrainer: the flagship LM path through the standard Trainer API —
dp x sp (x tp) meshes, metrics, checkpoint/resume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import PartitionedDataset
from distkeras_tpu.checkpoint import Checkpointer
from distkeras_tpu.models import get_model
from distkeras_tpu.trainers import LMTrainer

LM_KW = dict(vocab_size=64, d_model=32, num_heads=2, num_layers=2,
             max_len=32, dtype=jnp.float32)


def token_dataset(n=64, T=32, seed=0, partitions=4):
    tokens = np.random.default_rng(seed).integers(
        0, LM_KW["vocab_size"], size=(n, T)
    ).astype(np.int32)
    return PartitionedDataset.from_arrays(
        {"tokens": tokens}, num_partitions=partitions
    )


def test_lm_trainer_dp_sp_trains():
    ds = token_dataset()
    model = get_model("transformer_lm", attention="ring", seq_axis="sp",
                      **LM_KW)
    t = LMTrainer(model, axes={"dp": 4, "sp": 2}, batch_size=16,
                  num_epoch=4, worker_optimizer="adam", learning_rate=1e-2)
    trained = t.train(ds)
    assert trained is not None
    assert len(t.history) == 4 * (64 // 16)
    assert t.history[-1]["loss"] < t.history[0]["loss"] - 0.2
    assert t.get_training_time() > 0


def test_lm_trainer_with_tp():
    ds = token_dataset(seed=1)
    model = get_model("transformer_lm", attention="ring", seq_axis="sp",
                      tp_size=2, tp_axis="tp", **LM_KW)
    t = LMTrainer(model, axes={"dp": 2, "sp": 2, "tp": 2}, batch_size=16,
                  num_epoch=3, worker_optimizer="adam", learning_rate=1e-2)
    t.train(ds)
    assert t.history[-1]["loss"] < t.history[0]["loss"]


def test_lm_trainer_matches_plain_step_math():
    """First-step loss equals the raw SPMD step on the same init/batch."""
    import optax
    from distkeras_tpu.parallel.mesh import make_mesh
    from distkeras_tpu.parallel.spmd import make_lm_train_step

    ds = token_dataset(seed=2)
    model = get_model("transformer_lm", attention="ring", seq_axis="sp",
                      **LM_KW)
    t = LMTrainer(model, axes={"dp": 4, "sp": 2}, batch_size=64,
                  num_epoch=1, worker_optimizer="sgd", learning_rate=0.1)
    t.train(ds)

    std = get_model("transformer_lm", attention="standard", **LM_KW)
    tokens = np.asarray(ds.column("tokens"))
    params = std.init(jax.random.PRNGKey(0),
                      jnp.asarray(tokens[:1, :16], jnp.int32))
    mesh = make_mesh({"dp": 4, "sp": 2})
    optimizer = optax.sgd(0.1)
    step = make_lm_train_step(model, optimizer, mesh)
    _, _, loss = step(params, optimizer.init(params),
                      jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(t.history[0]["loss"], float(loss), rtol=1e-5)


def test_lm_trainer_checkpoint_resume(tmp_path):
    ds = token_dataset(seed=3)
    kw = dict(axes={"dp": 4, "sp": 2}, batch_size=16,
              worker_optimizer="adam", learning_rate=1e-2, seed=7)

    def make_model():
        return get_model("transformer_lm", attention="ring", seq_axis="sp",
                         **LM_KW)

    ck_full = Checkpointer(str(tmp_path / "full"), every_steps=1)
    full = LMTrainer(make_model(), num_epoch=4, checkpointer=ck_full, **kw)
    full_model = full.train(ds)
    ck_full.close()

    ck1 = Checkpointer(str(tmp_path / "res"), every_steps=1)
    t1 = LMTrainer(make_model(), num_epoch=2, checkpointer=ck1, **kw)
    t1.train(ds)
    ck1.close()

    ck2 = Checkpointer(str(tmp_path / "res"), every_steps=1)
    t2 = LMTrainer(make_model(), num_epoch=4, checkpointer=ck2, **kw)
    resumed_model = t2.train(ds)
    ck2.close()

    # resumed trajectory (2 + 2 epochs) == uninterrupted 4 epochs exactly
    assert len(t2.history) == len(full.history) // 2
    for a, b in zip(jax.tree.leaves(full_model.params),
                    jax.tree.leaves(resumed_model.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_lm_trainer_validation_errors():
    ds = token_dataset()
    std = get_model("transformer_lm", attention="standard", **LM_KW)
    with pytest.raises(ValueError, match="ring"):
        LMTrainer(std, axes={"dp": 4, "sp": 2}, batch_size=16).train(ds)
    ring = get_model("transformer_lm", attention="ring", seq_axis="sp",
                     **LM_KW)
    with pytest.raises(ValueError, match="tp"):
        LMTrainer(ring, axes={"dp": 2, "sp": 2, "tp": 2},
                  batch_size=16).train(ds)
    with pytest.raises(ValueError, match="not divisible"):
        bad = token_dataset(T=31)
        LMTrainer(ring, axes={"dp": 4, "sp": 2}, batch_size=16).train(bad)


def test_lm_trainer_moe_dp_ep():
    """An MoE model routes LMTrainer onto the (dp, ep) MoE step."""
    tokens = np.random.default_rng(4).integers(
        0, 64, size=(64, 16)
    ).astype(np.int32)
    ds = PartitionedDataset.from_arrays({"tokens": tokens}, 4)
    model = get_model(
        "moe_lm", vocab_size=64, d_model=32, num_heads=2, num_layers=2,
        max_len=16, dtype=jnp.float32, moe_experts=8, ep_size=4,
        ep_axis="ep",
    )
    t = LMTrainer(model, axes={"dp": 2, "ep": 4}, batch_size=16,
                  num_epoch=6, worker_optimizer="adam", learning_rate=3e-3)
    trained = t.train(ds)
    assert trained is not None
    assert len(t.history) == 6 * 4
    assert t.history[-1]["loss"] < t.history[0]["loss"]


def test_lm_trainer_moe_requires_ep_axis():
    tokens = np.random.default_rng(5).integers(
        0, 64, size=(32, 16)
    ).astype(np.int32)
    ds = PartitionedDataset.from_arrays({"tokens": tokens}, 1)
    model = get_model(
        "moe_lm", vocab_size=64, d_model=32, num_heads=2, num_layers=1,
        max_len=16, dtype=jnp.float32, moe_experts=4, ep_size=4,
    )
    with pytest.raises(ValueError, match="'ep' mesh axis"):
        LMTrainer(model, axes={"dp": 8}, batch_size=16).train(ds)


def test_rope_model_through_trainer_and_decode():
    """pos_emb='rope' flows end to end: LMTrainer trains it (ring sp
    mesh), and the returned Model generates through the KV cache."""
    ds = token_dataset()
    model = get_model("transformer_lm", attention="ring", seq_axis="sp",
                      pos_emb="rope", **LM_KW)
    t = LMTrainer(model, axes={"dp": 2, "sp": 2}, batch_size=8,
                  num_epoch=2, worker_optimizer="adam",
                  learning_rate=1e-2)
    trained = t.train(ds)
    assert t.history[-1]["loss"] < t.history[0]["loss"]
    out = trained.generate(np.asarray([[1, 2, 3]], np.int32), 4)
    assert out.shape == (1, 7)


def test_donation_leaves_caller_params_alive():
    """The donated LM window must never delete buffers the caller still
    owns: user-supplied init params stay usable after train()
    (regression — the first donated call used to consume them)."""
    ds = token_dataset()
    model = get_model("transformer_lm", attention="standard", **LM_KW)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32), jnp.int32))
    t = LMTrainer(model, params=params, axes={"dp": 1}, batch_size=8,
                  num_epoch=1, worker_optimizer="adam", learning_rate=1e-3)
    t.train(ds)
    out = model.apply(params, jnp.zeros((2, 32), jnp.int32))
    assert np.isfinite(np.asarray(out)).all()


class _Stop(Exception):
    pass


def _raise_at_first_row(trainer):
    """Make the loop raise once its first window's losses are drained:
    the state is donated by then, as in any run that dies midway."""
    class Writer:
        records = ()

        def log(self, **row):
            # what the loop holds while it runs: no tree of the trainer's
            assert trainer.params is None
            raise _Stop()

        def throughput(self):
            return None

        def close(self):
            pass

    trainer.record_training_start = lambda: setattr(
        trainer, "metrics_writer", Writer())


@pytest.mark.parametrize("given", [False, True])
def test_the_loop_holds_the_only_tree_and_a_failed_run_says_so(given):
    """``_train`` keeps no second copy of the parameters on the device:
    while the loop runs ``trainer.params`` is None. A run that raises
    leaves it so; the next ``train()`` starts from the seed again, or,
    where the tree was given, refuses with a message instead of
    training other weights in silence."""
    ds = token_dataset()
    model = get_model("transformer_lm", attention="standard", **LM_KW)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32), jnp.int32)) if given else None
    t = LMTrainer(model, params=params, axes={"dp": 1}, batch_size=8,
                  num_epoch=1, worker_optimizer="adam", learning_rate=1e-3)
    start, end = t.record_training_start, t.record_training_end
    _raise_at_first_row(t)
    t.record_training_end = lambda: None
    with pytest.raises(_Stop):
        t.train(ds)
    assert t.params is None
    t.record_training_start, t.record_training_end = start, end
    t.metrics_writer = None
    if given:
        with pytest.raises(RuntimeError, match="set trainer.params again"):
            t.train(ds)
        t.params = params  # the caller's own reference is whole
    t.train(ds)
    assert t.params is not None and len(t.history) == 64 // 8
    t.train(ds)  # and a trained tree is a start like a given one
    assert np.isfinite(t.history[-1]["loss"])
