"""The one traffic generator: a cell's pool of batches, made on the device
from the seed in one jitted call.

The configuration's ``data.columns`` says what a tuple holds: an ordered
list of columns, each named as the entry passes it to the system
(``groups``, ``keys``, ``timestamps``, ...), with its ``dtype`` (int32
unless stated) and the distribution it is drawn from:

* ``{"dist": "uniform", "low": a, "high": b}``: uniform in ``[a, b)``;
* ``{"dist": "zipf", "high": n, "s": s}``: value ``r`` in ``[0, n)`` with
  probability proportional to ``1 / (r + 1) ** s`` (hot ids first);
* ``{"dist": "constant", "value": v}``;
* ``{"dist": "event_time", "step": d, "late_share": p, "late_max": m}``:
  tuple ``i`` of a push is stamped ``i * d``, and a share ``p`` of the
  tuples, drawn at random, is stamped up to ``m`` earlier (not below 0):
  times relative to the push's start, which the entry offsets by the
  push's place in the stream.

The traffic file says how many tuples a push carries and how many distinct
batches the pool holds.  Every seed gives the same sizes; only the values
differ.  A batch is a tuple of arrays in the order of ``data.columns``.
"""
from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A threefry key that keeps all 64 bits of ``seed`` (``jax.random.key``
    alone keeps only the low 32)."""
    import jax
    s = seed % 2 ** 64
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32), impl="threefry2x32")


def _uniform(key, n, spec, dtype):
    import jax
    return jax.random.randint(key, (n,), spec["low"], spec["high"], dtype)


def _zipf(key, n, spec, dtype):
    import jax
    import jax.numpy as jnp
    w = 1.0 / jnp.arange(1, spec["high"] + 1, dtype=jnp.float32) ** spec["s"]
    cdf = jnp.cumsum(w)
    u = jax.random.uniform(key, (n,), jnp.float32, 0, cdf[-1])
    r = jnp.searchsorted(cdf, u, side="right")
    return jnp.minimum(r, spec["high"] - 1).astype(dtype)


def _constant(key, n, spec, dtype):
    import jax.numpy as jnp
    return jnp.full((n,), spec["value"], dtype)


def _event_time(key, n, spec, dtype):
    import jax
    import jax.numpy as jnp
    kl, kd = jax.random.split(key)
    t = jnp.arange(n, dtype=jnp.int64 if jnp.dtype(dtype).itemsize == 8
                   else jnp.int32) * spec["step"]
    late = jax.random.uniform(kl, (n,)) < spec["late_share"]
    back = jax.random.randint(kd, (n,), 0, spec["late_max"] + 1, t.dtype)
    return jnp.maximum(jnp.where(late, t - back, t), 0).astype(dtype)


DISTRIBUTIONS = {"uniform": _uniform, "zipf": _zipf, "constant": _constant,
                 "event_time": _event_time}


def column_names(config: dict) -> tuple:
    return tuple(c["name"] for c in config["data"]["columns"])


def batches(config: dict, traffic: dict, seed: int) -> list:
    """``traffic['pool']`` batches of ``traffic['push_tuples']`` tuples
    each, on the default device."""
    import jax

    cols = config["data"]["columns"]
    for c in cols:
        if c["dist"] not in DISTRIBUTIONS:
            raise ValueError(f"no generator for distribution {c['dist']!r}")
    n, pool = traffic["push_tuples"], traffic["pool"]

    @jax.jit
    def make(key):
        out = []
        for i in range(pool):
            keys = jax.random.split(jax.random.fold_in(key, i), len(cols))
            out.append(tuple(
                DISTRIBUTIONS[c["dist"]](keys[j], n, c,
                                         np.dtype(c.get("dtype", "int32")))
                for j, c in enumerate(cols)))
        return out

    return [tuple(b) for b in jax.block_until_ready(make(seed_key(seed)))]

