"""The timed call of a configuration whose query runs through
``repro.query.execute``, the system's batch entry point.

The configuration's ``query`` object gives ``repro.query.Query``'s fields
(``window`` those of ``Window``).  Set-up plans the query once, checks that
the plan landed on one of the configuration's ``kernel_backends``, and
compiles ``execute`` with that plan under ``jax.jit``, the batch's columns
passed by their names in ``data.columns`` (a query with ``group_by:
false`` has no ``groups`` column).  On a TPU the lowered program
must hold a Pallas kernel (the interpreter leaves none).
"""
from __future__ import annotations

import numpy as np

import gen
import harness
import trace_reduce

#: what marks a Pallas TPU kernel in a lowered program
KERNEL_MARK = "tpu_custom_call"


def build_query(config: dict):
    from repro.query import Query, Window
    q = dict(config["query"])
    window = Window(**q.pop("window")) if "window" in q else None
    q["ops"] = tuple(q["ops"])
    return Query(window=window, **q)


def to_host(result) -> dict:
    """An ``AggResult`` as NumPy arrays in the reference's layout."""
    return {"groups": np.asarray(result.groups),
            "values": {k: np.asarray(v) for k, v in result.values.items()},
            "valid": np.asarray(result.valid),
            "num_groups": np.asarray(result.num_groups)}


def build(cell, batches, on_tpu: bool) -> harness.System:
    import jax

    from repro.query import execute, plan

    p = plan(build_query(cell.config))
    harness.log(phase="plan", backend=p.backend, note=p.note, path=p.path)
    if p.backend not in cell.config["kernel_backends"]:
        raise harness.SetupError(
            f"planned onto {p.backend!r}, not one of "
            f"{cell.config['kernel_backends']}")
    names = gen.column_names(cell.config)

    def timed(*cols):
        kw = dict(zip(names, cols))
        return execute(p, kw.pop("groups", None), **kw)[0]

    lowered = jax.jit(timed).lower(*batches[0])
    text = lowered.as_text()
    if on_tpu and KERNEL_MARK not in text:
        raise harness.SetupError("the lowered program holds no Pallas "
                                 "kernel")
    compiled = lowered.compile()
    kernels = trace_reduce.kernel_names(text, compiled.as_text())
    harness.log(phase="compile", kernels=kernels)
    return harness.System(call=compiled, to_host=to_host, kernels=kernels)
