"""``FIFOScheduler(size_classes=k)``: admissions dealt round the ``k * k``
size classes of what is waiting (prompt length, then output allowance),
oldest first within a class; 1, the default, is first come, first
served."""

import numpy as np
import pytest

from distkeras_tpu.serving.scheduler import FIFOScheduler, Request


def _req(prompt_len, new):
    return Request(prompt=np.zeros((prompt_len,), np.int32),
                   max_new_tokens=new)


def _sizes(reqs):
    return [(int(r.prompt.size), r.max_new_tokens) for r in reqs]


# eight waiting requests, in order of arrival
WAITING = [(50, 4), (10, 9), (40, 1), (20, 7), (60, 2), (30, 8), (80, 3),
           (70, 6)]


def _filled(**kw):
    sched = FIFOScheduler(**kw)
    for p, n in WAITING:
        sched.submit(_req(p, n))
    return sched


def test_the_default_is_first_come_first_served():
    sched = _filled()
    admitted, expired = sched.pop_admissible(3)
    assert _sizes(admitted) == WAITING[:3] and not expired
    admitted, _ = sched.pop_admissible(8)
    assert _sizes(admitted) == WAITING[3:]
    assert sched.depth() == 0


def test_admissions_go_round_the_classes_of_what_is_waiting():
    """k = 2: the shorter half of the waiting prompts and the longer
    half, each split by its output allowance; turns name (prompt class,
    output class) = (0, 0), (1, 0), (0, 1), (1, 1), ..., over what is
    still waiting at each turn."""
    sched = _filled(size_classes=2)
    admitted, _ = sched.pop_admissible(4)
    # 1: prompts 10-40 are the shorter half; their smaller allowances
    #    are (40, 1) and (20, 7); (40, 1) came first
    # 2: of the seven left, prompts 50-80 the longer four; smaller
    #    allowances (60, 2), (80, 3); (60, 2) came first
    # 3: of six, the shorter three 10, 20, 30: larger allowances
    #    (30, 8), (10, 9); (10, 9) came first
    # 4: of five, the longer three 50, 70, 80: larger allowances
    #    (50, 4), (70, 6); (50, 4) came first
    assert _sizes(admitted) == [(40, 1), (60, 2), (10, 9), (50, 4)]
    assert sched.depth() == 4


def test_no_size_waits_for_ever():
    """A queue kept full of fresh short requests: the longest prompt and
    the largest allowance still enter within a few rounds."""
    sched = FIFOScheduler(size_classes=2)
    long_prompt = sched.submit(_req(900, 5))
    long_output = sched.submit(_req(5, 900))
    for i in range(6):
        sched.submit(_req(10 + i, 10 + i))
    seen = []
    for i in range(12):
        admitted, _ = sched.pop_admissible(1)
        seen += admitted
        sched.submit(_req(20 + i, 20 + i))
    assert any(r is long_prompt for r in seen[:8])
    assert any(r is long_output for r in seen[:8])


def test_a_gated_pop_stays_first_come_first_served():
    sched = _filled(size_classes=2)
    admitted, _ = sched.pop_admissible(2, admissible=lambda r: True)
    assert _sizes(admitted) == WAITING[:2]


def test_a_waiting_request_past_its_deadline_expires_in_its_turn():
    sched = FIFOScheduler(size_classes=2)
    sched.submit(_req(50, 4))
    late = _req(10, 1)
    late.deadline_s = -1.0  # already past when looked at
    sched.submit(late)
    sched.submit(_req(60, 2))
    admitted, expired = sched.pop_admissible(3)
    assert [r is late for r in expired] == [True]
    assert late.stream.tokens(timeout=1) == []
    assert late.stream.finish_reason == "expired"
    assert sorted(_sizes(admitted)) == [(50, 4), (60, 2)]


def test_size_classes_must_be_positive():
    with pytest.raises(ValueError, match="size_classes"):
        FIFOScheduler(size_classes=0)


def test_an_engine_takes_the_classes_from_a_mapping_and_serves_every_size():
    """``scheduler={"size_classes": 2}`` (how a configuration file says
    it) through ``ServingEngine``: twelve requests of mixed sizes over
    two slots all complete, each with its own tokens (greedy streams do
    not depend on the order of admission)."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models import get_model
    from distkeras_tpu.serving import ServingEngine

    model = get_model("transformer_lm", vocab_size=61, d_model=32,
                      num_heads=2, num_layers=1, max_len=64,
                      dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(3)
    sizes = [(int(rng.integers(2, 30)), int(rng.integers(1, 12)))
             for _ in range(12)]
    prompts = [rng.integers(0, 61, size=p).astype(np.int32)
               for p, _ in sizes]

    def serve(scheduler):
        eng = ServingEngine(model, params, slots=2, max_len=64,
                            prefill_chunk=8, scheduler=scheduler)
        reqs = [eng.submit(p, n) for p, (_, n) in zip(prompts, sizes)]
        order = []
        while eng.step():
            for rid in eng.slot_requests:
                if rid is not None and rid not in order:
                    order.append(rid)
        return eng, reqs, order

    eng, reqs, order = serve({"size_classes": 2})
    assert eng.scheduler.size_classes == 2
    assert eng.requests_completed == 12
    fifo_eng, fifo_reqs, fifo_order = serve(None)
    assert fifo_eng.scheduler.size_classes == 1
    # first come, first served enters in order of arrival; the classes
    # deal another order, and the tokens of a request are its own
    assert fifo_order == [r.rid for r in fifo_reqs]
    assert order != [r.rid for r in reqs]
    for r, f, (_, n) in zip(reqs, fifo_reqs, sizes):
        toks = r.stream.tokens(timeout=10)
        assert len(toks) == n and toks == f.stream.tokens(timeout=10)
