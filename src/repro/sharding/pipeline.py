"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

The layer stack is split into ``n_stages`` contiguous groups; stage s's
parameters live on pod s (leading stage dim sharded over ``pod``).
Microbatches flow through a shard_map'd schedule: at tick t, stage s
processes microbatch t−s and hands its activation to stage s+1 via
``collective_permute`` — inter-pod traffic is exactly one activation
tensor per tick, the right shape for the sparse pod-to-pod links.

Autodiff flows through the ppermutes, so ``jax.grad`` of the pipelined
loss gives GPipe with full activation stash (1F1B scheduling is a future
refinement; the dry-run proves the collective schedule compiles on the
2×16×16 mesh).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn, n_stages: int, n_micro: int, mesh: Mesh,
                   pod_axis: str = "pod", inner_spec: P = P()):
    """Build a pipelined fn(stacked_params, x) -> y.

    stage_fn(stage_params, x_micro) -> x_micro applies ONE stage.
    stacked_params: pytree with leading dim n_stages (sharded over pod).
    x: (n_micro, micro_batch, ...) — microbatch-major input.
    """
    assert mesh.shape[pod_axis] == n_stages

    def shard_fn(params_l, x):
        # params_l leaves: (1, ...) local stage params
        params_s = jax.tree.map(lambda p: p[0], params_l)
        stage = lax.axis_index(pod_axis)
        n_t = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        buf = jnp.zeros_like(x[0])          # current activation at this stage
        outs = jnp.zeros_like(x)            # collected at the last stage

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if valid); others take recv
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(stage == 0,
                             x[mb_idx],
                             buf)
            y = stage_fn(params_s, x_in)
            # pass to the next stage
            nxt = lax.ppermute(y, pod_axis, perm)
            # last stage emits microbatch t-(n_stages-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = lax.cond(
                emit,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, out_idx, axis=0),
                lambda o: o,
                outs)
            return nxt, outs

        buf, outs = lax.fori_loop(0, n_t, tick, (buf, outs))
        # broadcast the last stage's outputs to every pod (loss is computed
        # replicated; cheap relative to the stage compute)
        outs = lax.ppermute(
            outs, pod_axis,
            [(n_stages - 1, i) for i in range(n_stages - 1)]) + jnp.where(
            stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return outs

    param_spec = P(pod_axis)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(param_spec, inner_spec),
        out_specs=inner_spec,
        check_vma=False,
    )
    return fn


def stage_shardings(mesh: Mesh, params_stacked, pod_axis: str = "pod"):
    spec = P(pod_axis)
    return jax.tree.map(
        lambda p: NamedSharding(mesh, spec), params_stacked)
