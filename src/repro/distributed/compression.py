"""Error-feedback gradient compression for slow inter-pod links.

Cross-pod ICI/DCN is the thin pipe of a multi-pod mesh.  The classic remedy
is to compress the cross-pod gradient reduction and carry the quantization
error forward (error feedback keeps the optimizer unbiased in expectation;
Seide et al. 2014, Karimireddy et al. 2019).

``compress``/``decompress`` implement per-tensor-scaled int8 with an error
accumulator (4x fewer bytes on the wire than fp32, 2x vs bf16).
``make_pod_sync`` wires it into a ``shard_map`` over the ``pod`` axis:
pod-local gradients are quantized, ``psum``'d across pods in int32, and
de-quantized — the flag-gated alternative to the plain bf16 all-reduce the
default train step uses.  The error state rides in the optimizer pytree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def compress(x, err):
    """x fp32/bf16 + error carry -> (int8 q, scale, new_err)."""
    x32 = x.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    new_err = x32 - q.astype(jnp.float32) * scale
    return q, scale, new_err


def decompress(q, scale):
    return q.astype(jnp.float32) * scale


def init_error(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_pod_sync(mesh, grad_specs):
    """Returns pod_sync(grads, err) -> (synced fp32 grads, new err).

    grads enter pod-local (already reduced over data/model); the cross-pod
    mean happens here, int8 on the wire.  ``grad_specs``: pytree of
    PartitionSpec for the gradient leaves (pod axis must NOT appear — grads
    are pod-replicated before sync, pod-identical after).
    """
    from jax.sharding import PartitionSpec as P

    npods = mesh.shape["pod"]

    def sync_leaf(g, e):
        x32 = g.astype(jnp.float32) + e
        local_scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12) / 127.0
        scale = jax.lax.pmax(local_scale, "pod")   # shared quantization grid
        q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
        new_err = x32 - q.astype(jnp.float32) * scale
        total = jax.lax.psum(q.astype(jnp.int32), "pod")
        out = total.astype(jnp.float32) * scale / npods
        return out, new_err

    def _tree_sync(grads, err):
        flat_g, treedef = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(err)
        outs = [sync_leaf(g, e) for g, e in zip(flat_g, flat_e)]
        return (jax.tree.unflatten(treedef, [o[0] for o in outs]),
                jax.tree.unflatten(treedef, [o[1] for o in outs]))

    def add_pod(spec):
        return P(*spec)  # same spec; pod axis unmentioned = replicated

    in_specs = (jax.tree.map(add_pod, grad_specs,
                             is_leaf=lambda x: isinstance(x, P)),) * 2
    out_specs = in_specs

    def pod_sync(grads, err):
        return jax.shard_map(_tree_sync, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(grads, err)

    return pod_sync
