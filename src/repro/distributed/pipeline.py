"""Opt-in pipeline parallelism over the ``pod`` axis (gpipe-style).

The default multi-pod scheme uses the pod axis for data parallelism (deep
models already scan over layers, so 2-stage PP buys little on this mesh).
For topologies where cross-pod DP all-reduce is the binding term, this
utility re-purposes the pod axis as a 2-stage pipeline: each pod holds half
the layer stack; microbatches stream through with ``ppermute`` hand-offs
(the classic gpipe schedule: fill, steady state, drain).

Provided as a composable wrapper, exercised by tests on a local 2-"pod"
mesh — the launch scripts keep pod-DP as default per DESIGN.md §6.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def pipeline_apply(fn_stage, params_stages, x_mb, *, mesh,
                   pod_axis: str = "pod"):
    """Run ``n_mb`` microbatches through ``n_stage`` pipeline stages.

    Args:
      fn_stage: (stage_params, x) -> x — one stage's forward.
      params_stages: pytree with leading [n_stage] axis on every leaf,
        sharded so stage s lives on pod s (P(pod_axis, ...)).
      x_mb: [n_mb, mb, ...] microbatched input, replicated across pods.
      mesh: mesh containing ``pod_axis`` (size = n_stage).

    Returns [n_mb, mb, ...] outputs (valid on the last stage; replicated
    back via ppermute ring so every pod holds the result).

    Schedule: n_mb + n_stage - 1 ticks; stage s works on microbatch
    (t - s) when 0 <= t - s < n_mb — the gpipe diagonal.
    """
    n_stage = mesh.shape[pod_axis]
    n_mb = x_mb.shape[0]
    mb_shape = x_mb.shape[1:]

    def local(params_stage, x_all):
        # params_stage: this pod's stage params (leading axis stripped to 1)
        params_stage = jax.tree.map(lambda p: p[0], params_stage)
        stage = jax.lax.axis_index(pod_axis)

        def tick(carry, t):
            inbuf, outs = carry
            # receive previous stage's output (shift ring: s-1 -> s)
            perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            recv = jax.lax.ppermute(inbuf, pod_axis, perm)
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < n_mb)
            x_in = jnp.where(
                stage == 0,
                x_all[jnp.clip(mb_idx, 0, n_mb - 1)],
                recv)
            y = fn_stage(params_stage, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage banks its finished microbatch
            done_idx = t - (n_stage - 1)
            bank = (stage == n_stage - 1) & (done_idx >= 0) & (done_idx < n_mb)
            outs = jax.lax.cond(
                bank,
                lambda o: o.at[jnp.clip(done_idx, 0, n_mb - 1)].set(y),
                lambda o: o, outs)
            return (y, outs), None

        zeros = jnp.zeros(mb_shape, x_all.dtype)
        outs0 = jnp.zeros((n_mb,) + mb_shape, x_all.dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (zeros, outs0), jnp.arange(n_mb + n_stage - 1))
        # broadcast final outputs (banked only on the last stage, zeros
        # elsewhere) to all pods
        return jax.lax.psum(outs, pod_axis)

    n_axes = len(mesh.axis_names)
    rep = P(*([None] * (x_mb.ndim)))
    stage_spec = jax.tree.map(
        lambda _: P(pod_axis), params_stages,
        is_leaf=lambda x: hasattr(x, "shape"))
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(stage_spec, rep),
        out_specs=rep,
        check_vma=False,
    )(params_stages, x_mb)
