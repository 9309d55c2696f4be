"""The timed call of a streaming configuration: one stateful push through
``repro.query.plan`` and ``stream_fn``, the system's streaming step, its
carry threaded from push to push on the device.

The configuration's ``query`` object gives ``repro.query.Query``'s fields
(``window`` those of ``Window``), and its ``stream`` object how the
pushes' draws (``data.columns``) become tuples: ``nexmark_bids`` makes
NEXmark bids from the push's place in the stream, outside the engine's
stages (see ``bench/reference/nexmark_q5.py``).  The call counts pushes
on the device, warm-up included, so push ``p`` of the stream is the
``p``-th call.

Set-up refuses a plan that is not on the pane-partial table (a program
without that route would replay every stored bid at each push), compiles
the step once with the carry donated, and records the compiled text for
the stage readers (``bench/entry_stages.py``).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import entry_stages
import harness
import manifest

#: the plan note of the route this entry measures
ROUTE = "pane-partial table"


class Pushed(NamedTuple):
    """One push's emission: ``[rows, lanes]`` closed windows."""
    groups: object
    values: dict
    valid: object
    num_groups: object
    window_ends: object


def nexmark_bids(push, hot, delay, price, stream: dict):
    """Bids ``i = push * N + lane`` of the stream, in int32 arithmetic from
    the push number and the lane, never forming ``i`` or ``3 i`` (which
    overflow at 2.1 G and 715 M bids).  Returns ``(auction, price,
    stamp)``."""
    import jax.numpy as jnp

    n = hot.shape[0]
    b = stream["bids_per_ms"]
    auctions, bids = stream["auctions"], stream["bids"]
    flight = stream["in_flight"]
    lane = jnp.arange(n, dtype=jnp.int32)
    # i // b = push * (n // b) + (push * (n % b) + lane) // b, with push
    # split by b so that no product outgrows its factors
    nq, nr = divmod(n, b)
    pa, pb = push // b, push % b
    base = push * nq + pa * nr + (pb * nr + lane) // b
    stamp = jnp.maximum(jnp.where(delay >= 0, base - delay, base), 0)
    # last = auctions * i // bids, the same way
    mq, mr = divmod(auctions * n, bids)
    qa, qb = push // bids, push % bids
    last = push * mq + qa * mr + (qb * mr + auctions * lane) // bids
    auction = jnp.where(hot < 0, last // flight * flight,
                        jnp.maximum(last - flight, 0) + hot)
    return auction, price, stamp


STREAMS = {"nexmark_bids": nexmark_bids}


def to_host(out) -> dict:
    return {"groups": np.asarray(out.groups),
            "values": {k: np.asarray(v) for k, v in out.values.items()},
            "valid": np.asarray(out.valid),
            "num_groups": np.asarray(out.num_groups)}


def build(cell, batches, on_tpu: bool) -> harness.System:
    import jax
    import jax.numpy as jnp

    from repro.query import init_stream_state, plan, stream_fn

    execute_entry = manifest.load_module(
        manifest.entry_path({"entry": "execute"}, cell.root))
    p = plan(execute_entry.build_query(cell.config))
    harness.log(phase="plan", backend=p.backend, note=p.note, path=p.path)
    if ROUTE not in p.note:
        raise harness.SetupError(f"the plan is not on the {ROUTE}: "
                                 f"{p.note!r}")
    stream = cell.config["stream"]
    make = STREAMS[stream["kind"]]
    step = stream_fn(p)

    def timed(*args):
        *cols, (push, state) = args
        groups, keys, stamps = make(push, *cols, stream)
        (g, values, valid, num, _rr, ends), state = step(
            groups, keys, state, None, stamps)
        return Pushed(g, values, valid, num, ends), (push + 1, state)

    carry = (jnp.zeros((), jnp.int32), init_stream_state(p, jnp.int32))
    compiled = jax.jit(timed, donate_argnums=(len(batches[0]),)).lower(
        *batches[0], carry).compile()
    entry_stages.record(cell.config, cell.traffic, compiled.as_text())
    harness.log(phase="compile", kernels={})
    held = [carry]

    def call(*cols):
        out, held[0] = compiled(*cols, held[0])
        return out

    return harness.System(call=call, to_host=to_host, kernels={})
