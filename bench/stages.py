"""Device time per stage of the program, for the stage readers in
``bench/metrics/``.

The program names its stages with ``jax.named_scope("repro.<stage>")``
(``repro.obs.trace.stage``).  The scope reaches the ``op_name`` metadata of
every compiled HLO instruction the stage lowers to, so each device op of
the reduced trace (named by its instruction, ``while.78/fusion.27`` for
ops nested in a loop) maps to the innermost ``repro.<stage>`` segment of
its own instruction's ``op_name``.  Loop-control self time goes to the
loop's stage.  An op whose instruction carries no stage is ``unstaged``.

The harness hands the readers the reduced trace but not the compiled
program, so the cell's timed call is compiled again here after the window,
exactly as ``bench/entries/execute.py`` compiles it (the persistent cache
serves it), for configurations whose entry is ``execute``.  A program that
names no stage (a checkout from before the stages) gives an empty map, and
every stage reader then finds nothing to read.
"""
from __future__ import annotations

import json
import re

PREFIX = "repro."

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?'
                    r'metadata=\{[^}]*op_name="([^"]*)"')

_CACHE: dict = {}


def stage_names(compiled_text: str) -> dict:
    """``{instruction: stage}`` for every instruction of the compiled HLO
    text whose ``op_name`` holds a ``repro.<stage>`` segment (the innermost
    wins); instructions without one are left out."""
    out = {}
    for line in compiled_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        scopes = [s for s in m.group(2).split("/") if s.startswith(PREFIX)]
        if scopes:
            out[m.group(1)] = scopes[-1][len(PREFIX):]
    return out


def _compiled_text(config: dict, traffic: dict) -> str | None:
    """The cell's timed call compiled as the ``execute`` entry compiles it;
    None for another entry."""
    if config.get("entry") != "execute":
        return None
    import jax
    import numpy as np

    import gen
    import manifest
    from repro.query import execute, plan

    entry = manifest.load_module(manifest.entry_path(config))
    p = plan(entry.build_query(config))
    names = gen.column_names(config)

    def timed(*cols):
        kw = dict(zip(names, cols))
        return execute(p, kw.pop("groups", None), **kw)[0]

    args = [jax.ShapeDtypeStruct((traffic["push_tuples"],),
                                 np.dtype(c.get("dtype", "int32")))
            for c in config["data"]["columns"]]
    return jax.jit(timed).lower(*args).compile().as_text()


def of_cell(config: dict, traffic: dict) -> dict:
    """``{instruction: stage}`` of the cell's timed call, compiled once per
    process."""
    key = json.dumps([config, traffic], sort_keys=True)
    if key not in _CACHE:
        text = _compiled_text(config, traffic)
        _CACHE[key] = stage_names(text) if text else {}
    return _CACHE[key]


def self_s(trace, names: dict, stage: str | None) -> float:
    """Per-chip device self time of the ops whose own instruction maps to
    ``stage`` (None: to no stage), in seconds."""
    ns = sum(op.self_ns for ops in trace.ops for op in ops
             if names.get(op.name.rsplit("/", 1)[-1]) == stage)
    return ns * 1e-9 / len(trace.ops)


def ms_per_push(ctx, stage: str | None) -> float | None:
    """Device self time per push of ``stage`` (None: of the ops under no
    stage), in ms; None where the program names no such stage."""
    names = of_cell(ctx.config, ctx.traffic)
    if not names or (stage is not None and stage not in names.values()):
        return None
    return self_s(ctx.trace, names, stage) / ctx.trace.pushes * 1e3
