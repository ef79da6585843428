"""SPMD training steps over multi-axis device meshes.

This is the multi-chip training path: one program text, sharded over a
named mesh with XLA collectives over ICI — the TPU-native answer to the
reference's driver/executor/socket topology (SURVEY.md §5.8).

Current axes:

- ``dp`` — batch sharding; gradient reduction rides the autodiff-inserted
  psum (the transpose of broadcasting replicated params over ``dp``).
- ``sp`` — sequence sharding for the language-model step: ring attention
  (:mod:`distkeras_tpu.ops.ring_attention`) plus a ``ppermute`` to fetch
  each shard's next-token target across the shard boundary.
- ``tp`` — Megatron-style tensor parallelism: heads + MLP hidden sharded
  per :func:`lm_param_specs`, one forward psum per block pair (inside
  :class:`~distkeras_tpu.models.transformer.TPDenseGeneral`), backward
  conjugates inserted by shard_map's vma-aware autodiff.
- ``ep`` — expert parallelism: Switch-MoE expert banks sharded over ``ep``,
  tokens exchanged with two ``all_to_all``s
  (:mod:`distkeras_tpu.ops.moe`), batch sharded over dp x ep jointly.
- ``pp`` — pipeline parallelism: see :mod:`distkeras_tpu.parallel.pipeline`.

The classifier step (images/labels) uses ``dp`` only and serves any model
in the zoo; the LM step adds ``sp`` (ring attention) and optionally ``tp``;
the MoE step runs dp x ep. All are one program text over a named mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.ops import rules


def make_dp_train_step(apply_fn, loss_fn, optimizer, mesh: Mesh,
                       dp_axis: str = "dp"):
    """Jitted synchronous data-parallel step: batch sharded over ``dp_axis``,
    params replicated, global-mean gradient via the autodiff psum.

    Returns ``step(params, opt_state, x, y) -> (params, opt_state, loss)``.
    """

    def device_step(params, opt_state, x, y):
        def objective(p):
            return loss_fn(apply_fn(p, x), y)

        loss, grads = jax.value_and_grad(objective)(params)
        # replicated params + sharded batch → backward pass already psum'd
        # grads over dp; divide by axis size for the global mean.
        n = jax.lax.psum(1, dp_axis)
        grads = rules.tree_scale(grads, 1.0 / n)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, dp_axis)

    return jax.jit(
        shard_map(
            device_step,
            mesh=mesh,
            in_specs=(P(), P(), P(dp_axis), P(dp_axis)),
            out_specs=(P(), P(), P()),
        )
    )


def lm_param_specs(params, tp_axis: Optional[str] = None,
                   ep_axis: Optional[str] = None):
    """PartitionSpec tree for a :class:`TransformerLM` param pytree under
    tensor and/or expert parallelism: qkv/mlp_up column-sharded, out/
    mlp_down row-sharded over ``tp_axis`` (matching :class:`TPDenseGeneral`),
    SwitchMoE expert banks leading-axis-sharded over ``ep_axis`` (router
    replicated), everything else replicated. Built by parameter *path*, so
    it works on the full-size host init — shard_map then slices each leaf
    onto the mesh."""
    from jax.tree_util import DictKey, tree_map_with_path

    def spec(path, leaf):
        names = [k.key for k in path if isinstance(k, DictKey)]
        parent = names[-2] if len(names) >= 2 else ""
        last = names[-1] if names else ""
        is_kernel = last == "kernel"
        if tp_axis is not None:
            if parent == "qkv":  # kernel [D,3,H,hd], bias [3,H,hd]
                return (P(None, None, tp_axis, None) if is_kernel
                        else P(None, tp_axis, None))
            if parent == "q_proj":  # GQA: kernel [D,H,hd], bias [H,hd]
                return (P(None, tp_axis, None) if is_kernel
                        else P(tp_axis, None))
            if parent == "kv_proj":  # kernel [D,2,Hk,hd], bias [2,Hk,hd]
                return (P(None, None, tp_axis, None) if is_kernel
                        else P(None, tp_axis, None))
            if parent == "out":  # kernel [H,hd,D], bias [D] (post-psum)
                return P(tp_axis, None, None) if is_kernel else P()
            if parent == "mlp_up":  # kernel [D,F], bias [F]
                return P(None, tp_axis) if is_kernel else P(tp_axis)
            if parent == "mlp_down":  # kernel [F,D], bias [D] (post-psum)
                return P(tp_axis, None) if is_kernel else P()
        if ep_axis is not None and parent == "moe":
            if last == "router":  # [D, E] replicated (every shard routes)
                return P()
            # w1 [E,D,F] / b1 [E,F] / w2 [E,F,D] / b2 [E,D]: experts lead
            return P(*((ep_axis,) + (None,) * (leaf.ndim - 1)))
        return P()

    return tree_map_with_path(spec, params)


def serving_cache_specs(cache, tp_axis: str = "model"):
    """PartitionSpec tree for a decode-mode KV-cache pytree under tensor
    parallelism — the serving-side twin of :func:`lm_param_specs`. Both
    cache layouts shard the KV-head axis (dim 2):

    - slot slabs ``cached_key/value [S, L, Hk, hd]`` and paged pools
      ``paged_key/value [num_pages, bs, Hk, hd]`` → ``P(None, None, tp)``
      (+ trailing None);
    - int8 dequant scales ``key/value_scale [.., .., Hk]`` → same;
    - cursor vectors (``cache_index``, ``pos_index``) stay replicated —
      every shard advances the same host-owned positions.

    Built by leaf *path* like :func:`lm_param_specs`, so it works on the
    full-size (tp=1) cache template the engine allocates; ``shard_map``
    then slices each leaf's KV heads onto the mesh."""
    from jax.tree_util import DictKey, tree_map_with_path

    sharded = {"cached_key", "cached_value", "paged_key", "paged_value",
               "key_scale", "value_scale"}

    def spec(path, leaf):
        names = [k.key for k in path if isinstance(k, DictKey)]
        last = names[-1] if names else ""
        if last in sharded:
            return P(*((None, None, tp_axis)
                       + (None,) * (leaf.ndim - 3)))
        return P()

    return tree_map_with_path(spec, cache)


def draft_param_specs(params, *, num_heads: int,
                      num_kv_heads: Optional[int], tp_size: int,
                      tp_axis: str = "model"):
    """PartitionSpec tree for a speculative-decoding DRAFT model's params
    under the serving mesh, plus the tensor-parallel degree the draft
    module should be cloned with: ``(specs, draft_tp)``.

    A draft is deliberately small — its KV-head count often does not
    divide the serving mesh (a 2-head draft on a tp=4 mesh), and unlike
    the flagship it is cheap enough that replication costs almost
    nothing. So: when every head axis divides ``tp_size``, shard it
    exactly like the flagship (:func:`lm_param_specs`, ``draft_tp =
    tp_size``); otherwise return an all-replicated tree (``draft_tp =
    1`` — each shard runs the whole draft redundantly and emits
    identical proposals, which keeps the verify tick's draft-token
    inputs replicated by construction)."""
    hk = num_kv_heads or num_heads
    if tp_size > 1 and num_heads % tp_size == 0 and hk % tp_size == 0:
        return lm_param_specs(params, tp_axis=tp_axis), tp_size
    from jax.tree_util import tree_map

    return tree_map(lambda _: P(), params), 1


def opt_state_specs(optimizer, params, param_specs):
    """PartitionSpec tree for ``optimizer.init(params)``: optimizer states
    embed param-shaped subtrees (mu/nu/trace/...), so each state leaf whose
    tree path ends with a parameter's path inherits that parameter's spec;
    scalars (step counts) stay replicated."""
    from jax.tree_util import tree_flatten_with_path, tree_map_with_path

    flat, _ = tree_flatten_with_path(
        param_specs, is_leaf=lambda x: isinstance(x, P)
    )
    by_path = {tuple(map(repr, path)): s for path, s in flat}
    shapes = jax.eval_shape(optimizer.init, params)

    def match(path, leaf):
        keys = tuple(map(repr, path))
        for i in range(len(keys)):
            s = by_path.get(keys[i:])
            if s is not None:
                return s
        return P()

    return tree_map_with_path(match, shapes)


def lm_state_shardings(optimizer, mesh: Mesh, params,
                       tp_axis: Optional[str] = None,
                       ep_axis: Optional[str] = None):
    """(params, opt_state) ``NamedSharding`` trees in the layout the LM
    steps below keep their state in. A caller that places its freshly
    initialised state this way before the first call hands the step the
    very types the step returns, so the first call and every later one
    are ONE compiled program; state left on a single device has a
    different type (no mesh) and compiled the flagship step twice."""
    pspec = lm_param_specs(params, tp_axis=tp_axis, ep_axis=ep_axis)
    ospec = opt_state_specs(optimizer, params, pspec)

    def named(spec):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                            is_leaf=lambda x: isinstance(x, P))

    return named(pspec), named(ospec)


def lm_step_model(model, params=None):
    """The features-only copy of ``model`` that the LM step applies, or a
    ``ValueError`` that names what ``model`` lacks of what the step
    needs (see :func:`make_lm_train_step`), in place of a ``KeyError``
    deep inside the traced step."""
    name = type(model).__name__
    if not hasattr(model, "features_only"):
        raise ValueError(
            f"{name} cannot be trained by the LM step: it has no "
            f"features_only field (the step applies model.copy("
            f"features_only=True) for the final norm's [B, T, D] output "
            f"and runs the head inside the fused loss)")
    if params is not None and "kernel" not in params.get("params", {}).get(
            "head", {}):
        raise ValueError(
            f"{name} cannot be trained by the LM step: its parameters "
            f"have no head subtree with a kernel [D, V] (the fused loss "
            f"reads params['params']['head']); found "
            f"{sorted(params.get('params', {}))}")
    if getattr(model, "step_counters", ()):
        for method in ("rule_update", "step_metrics"):
            if not callable(getattr(model, method, None)):
                raise ValueError(
                    f"{name} names step_counters and has no {method}("
                    f"params, counters)")
    return model.copy(features_only=True)


def make_lm_train_step(model, optimizer, mesh: Mesh,
                       dp_axis: str = "dp", sp_axis: str = "sp",
                       tp_axis: Optional[str] = None,
                       params_template=None,
                       window: bool = False,
                       fused_ce: bool = True):
    """Jitted language-model training step sharded over data x sequence
    (x tensor, optionally).

    ``tokens`` is ``[B, T]`` with B sharded over ``dp_axis`` and T over
    ``sp_axis``. What the model has to offer (:func:`lm_step_model`
    says which is missing): ``model.copy(features_only=True)`` whose
    apply returns the final norm's ``[B, T, D]`` output, and a ``head``
    subtree of its parameters (``kernel [D, V]``, a ``bias [V]`` or
    none) for the fused loss; for ``sp > 1`` ring attention over
    ``seq_axis=sp_axis`` (:class:`TransformerLM` with
    ``attention='ring'``), so attention is exact over the full sequence
    while each device holds only ``T/sp`` of it. Optionally
    ``step_counters``: names the model sows into the ``"counters"``
    collection every apply. The step then sums them over the mesh,
    calls ``model.rule_update(params, counters)`` after the optimizer
    update (state that a rule moves and no gradient does: a router's
    selection bias) and returns ``model.step_metrics(params, counters)``
    beside the loss: ``loss`` is then a dict ``{"loss": ..., **metrics}``.

    With ``tp_axis`` given (and a ``params_template`` for spec inference),
    the model must also be built with ``tp_size == mesh tp size``: its
    head/MLP params are sharded per :func:`lm_param_specs`, activations stay
    replicated over tp, and the module's row-parallel psum plus the
    vma-transpose collectives shard_map's autodiff inserts make the step
    exact — one program, dp x sp x tp.

    Next-token targets cross the shard boundary: each shard's last position
    is supervised by the *next* shard's first token, fetched with one
    ``ppermute``; the final global position is masked out.

    Returns ``step(params, opt_state, tokens) -> (params, opt_state, loss)``
    where loss is the global mean next-token cross-entropy. With
    ``window=True`` the step takes ``[W, B, T]`` stacked batches and runs
    all W optimizer steps in one dispatch (``lax.scan``), returning the
    ``[W]`` per-step losses.

    ``fused_ce`` (default on, VERDICT r4 next #1) computes the loss with
    :func:`distkeras_tpu.ops.fused_ce.lm_head_loss` — the head matmul and
    softmax-CE run chunk-by-chunk and ``[B, T, V]`` logits never
    materialize (the flagship's largest transient). Identical forward
    math; backward within bf16 rounding (f32 models: identical). Set
    False to run the unfused ``model.apply`` + optax path.
    """
    if sp_axis not in mesh.axis_names:
        raise ValueError(
            f"mesh {mesh.axis_names} has no '{sp_axis}' axis — the LM step "
            "always shards the sequence over sp_axis; use a size-1 axis "
            "for the unsharded-sequence case (e.g. make_mesh({'dp': n, "
            "'sp': 1}))"
        )
    sp_size = int(np.prod([s for a, s in zip(mesh.axis_names, mesh.devices.shape)
                           if a == sp_axis] or [1]))
    if tp_axis is None:
        pspec = ospec = P()
    else:
        if params_template is None:
            raise ValueError(
                "tensor parallelism needs params_template to infer specs"
            )
        tp_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(tp_axis, 1)
        if getattr(model, "tp_size", 1) != tp_size:
            raise ValueError(
                f"model.tp_size={getattr(model, 'tp_size', 1)} != mesh "
                f"{tp_axis} size {tp_size}"
            )
        pspec = lm_param_specs(params_template, tp_axis=tp_axis)
        ospec = opt_state_specs(optimizer, params_template, pspec)

    feat_model = lm_step_model(model, params_template) if fused_ce else None
    counted = bool(getattr(model, "step_counters", ()))
    if counted and not fused_ce:
        raise ValueError("a model with step_counters trains with the "
                         "fused loss (fused_ce=True)")

    def batch_update(params, opt_state, tokens):
        B_l, T_l = tokens.shape
        my_sp = jax.lax.axis_index(sp_axis)
        # neighbor's first column supervises my last position
        perm = [(j, (j - 1) % sp_size) for j in range(sp_size)]
        next_first = jax.lax.ppermute(tokens[:, :1], sp_axis, perm)
        targets = jnp.concatenate([tokens[:, 1:], next_first], axis=1)
        # mask the last global position (its target wrapped around the ring)
        local_pos = my_sp * T_l + jnp.arange(T_l)
        total_T = T_l * sp_size
        mask = (local_pos < total_T - 1).astype(jnp.float32)[None, :]

        def objective(p):
            counters = None
            if fused_ce:
                from distkeras_tpu.ops.fused_ce import lm_head_loss

                if counted:
                    feats, state = feat_model.apply(p, tokens,
                                                    mutable=["counters"])
                    counters = state["counters"]
                else:
                    feats = feat_model.apply(p, tokens)
                # pcast the replicated head params to device-varying HERE,
                # where the axes are known: the fused op's custom VJP
                # returns varying head grads, and the transpose of this
                # pcast is the psum that makes them a correct replicated
                # gradient (the vjp is opaque to shard_map's vma machinery)
                head = jax.tree.map(
                    lambda a: jax.lax.pcast(
                        a, (dp_axis, sp_axis), to="varying"
                    ),
                    p["params"]["head"],
                )
                local_sum, _ = lm_head_loss(
                    feats, head, targets,
                    jnp.broadcast_to(mask, tokens.shape),
                )
                # tie the count's vma to the dp/sp-varying loss so the
                # two-axis psum below typechecks (mask alone varies only
                # over sp)
                local_cnt = jnp.sum(mask) * B_l + local_sum * 0.0
            else:
                logits = model.apply(p, tokens)
                token_loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets
                )
                local_sum = jnp.sum(token_loss * mask)
                # tie the count to token_loss's vma (varying over dp AND
                # sp) so the two-axis psum below typechecks
                local_cnt = jnp.sum((token_loss * 0.0 + 1.0) * mask)
            global_cnt = jax.lax.psum(local_cnt, (dp_axis, sp_axis))
            # objective sums to the global mean across all shards: the
            # autodiff psum over (dp, sp) then yields the exact global grad
            if counted:
                return local_sum / global_cnt, counters
            return local_sum / global_cnt

        if counted:
            (local_obj, counters), grads = jax.value_and_grad(
                objective, has_aux=True)(params)
        else:
            local_obj, grads = jax.value_and_grad(objective)(params)
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        loss = jax.lax.psum(local_obj, (dp_axis, sp_axis))
        if counted:
            # the step's own counts, over every shard's tokens; the rule
            # runs inside the step: no host round trip between steps
            counters = jax.lax.psum(counters, (dp_axis, sp_axis))
            params = model.rule_update(params, counters)
            loss = {"loss": loss, **model.step_metrics(params, counters)}
        return params, opt_state, loss

    if not window:
        return jax.jit(
            shard_map(
                batch_update,
                mesh=mesh,
                in_specs=(pspec, ospec, P(dp_axis, sp_axis)),
                out_specs=(pspec, ospec, P()),
            )
        )

    def device_window(params, opt_state, tokens):
        # tokens [W, B_l, T_l]: scan the per-batch update so W optimizer
        # steps are ONE device dispatch (the host round-trip per step is
        # the bottleneck on remote transports, and non-trivial anywhere)
        def body(carry, tok):
            p, s = carry
            p, s, loss = batch_update(p, s, tok)
            return (p, s), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), tokens
        )
        return params, opt_state, losses

    # donated params/opt_state: the trainer loop rebinds both every call
    # (measured +13% on the flagship — in-place updates instead of copies)
    return jax.jit(
        shard_map(
            device_window,
            mesh=mesh,
            in_specs=(pspec, ospec, P(None, dp_axis, sp_axis)),
            out_specs=(pspec, ospec, P()),
        ),
        donate_argnums=(0, 1),
    )


def make_moe_lm_train_step(model, optimizer, mesh: Mesh,
                           dp_axis: str = "dp", ep_axis: str = "ep",
                           params_template=None, aux_weight: float = 0.01,
                           window: bool = False):
    """Jitted MoE language-model step over a (dp, ep) mesh.

    ``tokens [B, T]`` is sharded over BOTH axes jointly (``P((dp, ep))``) —
    every device carries its own tokens AND its slice of the expert banks,
    so expert capacity scales with the mesh instead of replicating work.
    Routing crosses devices inside the model via the SwitchMoE layer's two
    ``all_to_all``s over ``ep_axis``; everything else is plain data
    parallelism.

    Loss = global mean next-token cross-entropy + ``aux_weight`` x the mean
    Switch load-balancing loss (collected from the modules' sown
    intermediates).

    Returns ``step(params, opt_state, tokens) -> (params, opt_state, loss)``.
    With ``window=True`` the step takes ``[W, B, T]`` stacked batches and
    runs all W optimizer steps in one dispatch, returning ``[W]`` losses.
    """
    if params_template is None:
        raise ValueError("MoE step needs params_template to infer specs")
    ax = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep_size = ax.get(ep_axis, 1)
    if getattr(model, "ep_size", 1) != ep_size:
        raise ValueError(
            f"model.ep_size={getattr(model, 'ep_size', 1)} != mesh "
            f"{ep_axis} size {ep_size}"
        )
    if getattr(model, "tp_size", 1) != 1:
        raise ValueError(
            "the MoE step shards ep only; build the model with tp_size=1 "
            "(tp x ep composition is not supported here)"
        )
    pspec = lm_param_specs(params_template, ep_axis=ep_axis)
    ospec = opt_state_specs(optimizer, params_template, pspec)
    n_shards = ax.get(dp_axis, 1) * ep_size

    def device_step(params, opt_state, tokens):
        def objective(p):
            logits, state = model.apply(
                p, tokens, mutable=["intermediates"]
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]
            ).mean()
            aux_leaves = jax.tree.leaves(state.get("intermediates", {}))
            aux = sum(jnp.sum(a) for a in aux_leaves) / max(len(aux_leaves), 1)
            return ce + aux_weight * aux, ce

        (local_obj, local_ce), grads = jax.value_and_grad(
            objective, has_aux=True
        )(params)
        # every shard weighs equally (same local token count): global mean
        # objective = mean of local objectives; autodiff's vma transpose
        # already psums grads of the replicated params over (dp, ep)
        grads = rules.tree_scale(grads, 1.0 / n_shards)
        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        loss = jax.lax.pmean(local_ce, (dp_axis, ep_axis))
        return params, opt_state, loss

    if not window:
        return jax.jit(
            shard_map(
                device_step,
                mesh=mesh,
                in_specs=(pspec, ospec, P((dp_axis, ep_axis))),
                out_specs=(pspec, ospec, P()),
            )
        )

    def device_window(params, opt_state, tokens):  # [W, B_l, T]
        def body(carry, tok):
            p, st = carry
            p, st, loss = device_step(p, st, tok)
            return (p, st), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), tokens
        )
        return params, opt_state, losses

    # donated: see make_lm_train_step's window jit
    return jax.jit(
        shard_map(
            device_window,
            mesh=mesh,
            in_specs=(pspec, ospec, P(None, (dp_axis, ep_axis))),
            out_specs=(pspec, ospec, P()),
        ),
        donate_argnums=(0, 1),
    )
