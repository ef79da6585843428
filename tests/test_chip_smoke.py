"""CPU rehearsal of ``chip_smoke.py``: the phases' control flow, the
parity check and the final line's format at a tiny size, with the
platform check stubbed — and the refusal to run without a TPU."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


def tiny(smoke, **kw):
    return smoke.Sizes(
        lm=dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2,
                max_len=128),
        seq_len=64, lm_batch=2, lm_steps_per_epoch=2, lm_epochs=2,
        cnn_batch=32, cnn_steps_per_epoch=2, cnn_epochs=2, slots=4,
        block_size=32, prompt_lens=(6, 20, 70, 70, 6), shared_prefix=32,
        new_tokens=8, expect_kernels=False, **kw)


def lines_of(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return out, {d["phase"]: d for d in map(json.loads, out[:-1])}


def test_one_chip_phases_rehearsal(smoke, capsys):
    smoke.run(False, 0, sz=tiny(smoke))
    out, phases = lines_of(capsys)
    assert json.loads(out[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}
    assert list(phases) == ["start", "cnn", "train", "serve_reference",
                            "serve_slot", "serve_gate", "serve_paged",
                            "total"]
    assert phases["start"]["compile_cache_dir"].endswith(".jax_cache")
    assert phases["train"]["step_compiles"] == {"jit(device_window)": 1}
    assert phases["train"]["steps"] == 4
    for tag in ("serve_slot", "serve_paged"):
        p = phases[tag]
        # on the CPU backend the contract is bit-identity
        assert p["streams_bit_identical"] == p["streams"] == 5
        assert p["recompiles_after_warmup"] == {}
        # 'auto' on the CPU: no kernel, and the line says so
        assert set(p["attend"].values()) == {"dense"}
    assert set(phases["serve_slot"]["attend"]) == {
        "serve.mixed_tick", "serve.tick"}
    assert set(phases["serve_paged"]["attend"]) == {
        "serve.paged_mixed_tick", "serve.paged_tick"}
    assert phases["serve_paged"]["prefix_hit_tokens"] >= 32


def test_four_chip_phases_rehearsal(smoke, capsys):
    smoke.run(True, 0, sz=tiny(smoke))
    out, phases = lines_of(capsys)
    assert json.loads(out[-1])["ok"] is True
    assert list(phases) == ["start", "four_train", "four_serve_one_chip",
                            "four_serve_tp", "four_serve", "total"]
    assert phases["four_train"]["axes"] == {"dp": 1, "sp": 2, "tp": 2}
    # bf16 on the CPU backend already sits at 1.4e-4 here: the target
    # tolerance is tests/test_spmd.py's f32 one, the bound is the chip's
    assert (phases["four_train"]["first_loss_rel_diff"]
            <= smoke.LOSS_RTOL_CHIP)
    assert phases["four_serve_one_chip"]["streams_bit_identical"] == 5
    # the row-sharded projections sum over shards in another order: in
    # bf16 a near-tie may flip, and the smoke then holds that stream to
    # its logit tolerance instead (run() raised if it were beyond it)
    tp = phases["four_serve_tp"]
    assert tp["streams_bit_identical"] + len(tp["divergences"]) == 5
    assert all(d["max_logit_gap"] <= smoke.LOGIT_TOL
               for d in tp["divergences"])
    assert phases["four_serve"]["shards"]["cache"]["devices_per_leaf"] == 4


def test_divergent_stream_is_held_to_the_logit_tolerance(smoke):
    """The fallback of the parity check: a stream that leaves the
    reference's tokens passes only as a near-tie."""
    sz = tiny(smoke)
    model, params = smoke.serve_model(sz, 0)
    prompts, _ = smoke.make_prompts(sz, 0)
    refs = smoke.reference_streams(model, params, prompts[:1], 8)
    gaps = smoke.greedy_gaps(model, params, prompts[0], refs[0])
    np.testing.assert_array_equal(gaps, 0.0)  # greedy under its own logits
    bad = list(refs[0])
    bad[3] = (bad[3] + 1) % 64
    with pytest.raises(AssertionError, match="left the greedy path"):
        smoke.check_streams("t", model, params, prompts[:1], refs,
                            [(bad, "length")])


def test_kernel_calls_reads_names_from_compiled_text(smoke):
    text = ('%fusion.2 = f32[8]{0} fusion(%p0), kind=kLoop\n'
            '%paged_attention.3 = bf16[8,2]{1,0} custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", operand_layout={}\n'
            'ROOT %splash_prefill = bf16[8]{0} custom-call(%c), '
            'custom_call_target="tpu_custom_call"\n'
            '%paged_attention.7 = bf16[8,2]{1,0} custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call"\n'
            '%other = f32[] custom-call(), custom_call_target="Sharding"\n')
    assert smoke.kernel_calls(text) == {"paged_attention": 2,
                                        "splash_prefill": 1}


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
