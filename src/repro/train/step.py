"""Training step: loss -> grads -> clip -> (optional compressed psum) ->
AdamW, with gradient-accumulation microbatching and remat policies.

The step is mesh-agnostic: under pjit/GSPMD the same code runs on 1 CPU
device (smoke tests) or 512 TPU chips (dry-run) — parallelism comes from
in/out shardings, not from the step logic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunConfig
from ..optim import adamw, clip, compression


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    step: jax.Array
    ef: Any = None            # error-feedback state (compression)


def init_state(model, key, rc: RunConfig, dtype=jnp.float32) -> TrainState:
    params = model.init(key, dtype)
    return TrainState(params=params, opt=adamw.init(params),
                      step=jnp.zeros((), jnp.int32))


def _split_microbatches(batch, n: int):
    return jax.tree_util.tree_map(
        lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch)


def make_train_step(model, rc: RunConfig, total_steps: int = 10_000):
    """Returns step_fn(state, batch) -> (state, metrics)."""

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if rc.microbatch and rc.microbatch > 1:
            mb = _split_microbatches(batch, rc.microbatch)

            def body(acc, micro):
                (l, m), g = grad_fn(params, micro)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g)
                return (acc_g, acc_l + l), m

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), ms = jax.lax.scan(body, (zero, 0.0), mb)
            n = rc.microbatch
            grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
            loss = lsum / n
            metrics = jax.tree_util.tree_map(lambda x: x[-1], ms)
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads

    def step_fn(state: TrainState, batch):
        loss, metrics, grads = compute_grads(state.params, batch)
        grads, gnorm = clip.clip_by_global_norm(grads, rc.grad_clip)
        params, opt = adamw.apply(rc, state.params, grads, state.opt,
                                  total_steps)
        out = TrainState(params=params, opt=opt, step=state.step + 1,
                         ef=state.ef)
        m = {"loss": loss, "grad_norm": gnorm,
             "lr": adamw.schedule(rc, state.step + 1, total_steps)}
        m.update(metrics)
        return out, m

    return step_fn


def make_compressed_dp_step(model, rc: RunConfig, mesh, total_steps=10_000):
    """Explicit shard_map data-parallel step with int8 error-feedback
    gradient all-reduce (the distributed-optimization trick; DP traffic
    shrinks 4x). Batch is sharded over the 'data' axis; params replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_data = mesh.shape["data"]

    def local_step(params, opt_state, ef, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p, b: model.loss(p, b), has_aux=True)(params, batch)
        mean_grads, ef2 = compression.compressed_psum(grads, ef, "data",
                                                      n_data)
        mean_grads, gnorm = clip.clip_by_global_norm(mean_grads, rc.grad_clip)
        params2, opt2 = adamw.apply(rc, params, mean_grads, opt_state,
                                    total_steps)
        loss = jax.lax.pmean(loss, "data")
        return params2, opt2, ef2, {"loss": loss, "grad_norm": gnorm}

    rep = P()  # replicated
    batch_spec = P("data")
    smapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False)

    def step_fn(state: TrainState, batch):
        ef = state.ef if state.ef is not None \
            else compression.init_ef(state.params)
        p, o, ef2, m = smapped(state.params, state.opt, ef, batch)
        return TrainState(params=p, opt=o, step=state.step + 1, ef=ef2), m

    return step_fn
