"""Pipeline parallelism: GPipe-style microbatch schedule over a "stage"
mesh axis via shard_map + ppermute.

The production dry-run mesh is (data, model) per the brief; PP is the
third parallelism feature for deeper-than-memory models and is exercised
by tests on a host-device mesh (and composes with DP by adding a "data"
axis to the mesh passed in).

Schedule: M microbatches through S stages takes M + S - 1 ticks. Each tick
every stage runs its layer block on the activation it received, then
``ppermute``s the result downstream. jax.grad differentiates straight
through (ppermute transposes to the reverse permute), giving GPipe-style
full-activation backward without bespoke adjoint plumbing.

The stage function is built from the SAME per-layer block functions as the
sequential model: ``build_stage_fn`` stacks n_layers/S layers per stage,
so PP output provably equals the sequential forward (tests assert exact
agreement).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stages(layer_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major stacking."""
    def resh(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])
    return jax.tree_util.tree_map(resh, layer_params)


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stage_params, x_mb,
                   axis: str = "stage"):
    """Run the pipeline. x_mb: (M, mb, ...) microbatched input.

    stage_fn(params_for_stage, x) -> y, applied by every stage each tick.
    Returns (M, mb, ...) outputs (as produced by the LAST stage).
    """
    n_stages = mesh.shape[axis]
    M = x_mb.shape[0]
    ticks = M + n_stages - 1

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params, xs):
        # params: this stage's slice (leading stage axis of size 1)
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        s = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs[0])      # activation arriving from upstream
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (while available); others use buf
            inj = jnp.where(t < M, xs[jnp.clip(t, 0, M - 1)], jnp.zeros_like(buf))
            x_in = jnp.where(s == 0, inj, buf)
            y = stage_fn(params, x_in)
            # last stage emits microbatch t - (S-1)
            emit_idx = t - (n_stages - 1)
            do_emit = (s == n_stages - 1) & (emit_idx >= 0)
            outs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_slice(
                    o, y[None].astype(o.dtype), (jnp.maximum(emit_idx, 0),)
                    + (0,) * y.ndim),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage's outs are real; broadcast them to all stages
        # (psum over one-hot keeps the pipeline SPMD-uniform)
        sel = (s == n_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * sel, axis)
        return outs

    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_params, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_mb)


def make_pp_loss(mesh: Mesh, stage_fn, embed_fn, head_fn, n_stages: int):
    """Compose embed -> pipelined stages -> head into a loss usable with
    jax.grad (GPipe backward falls out of autodiff)."""

    def loss_fn(params, batch, labels_fn):
        stage_params, other = params
        x = embed_fn(other, batch)
        y = pipeline_apply(mesh, stage_fn, stage_params, x)
        return head_fn(other, y, batch, labels_fn)

    return loss_fn
