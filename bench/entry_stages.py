"""Stage maps of timed calls that their entry compiles itself, for the
stage readers in ``bench/metrics/``.

``bench/stages.py`` compiles the timed call of ``execute`` entries again
after the window; an entry of another kind records the text of the
program it compiled at set-up here instead (:func:`record`), and its
cell's stage readers read the map from here (:func:`ms_per_push`).  The
map lives as long as the process, one per (configuration, traffic).
"""
from __future__ import annotations

import json

import stages

_MAPS: dict = {}


def _key(config: dict, traffic: dict) -> str:
    return json.dumps([config, traffic], sort_keys=True)


def record(config: dict, traffic: dict, compiled_text: str) -> None:
    """Keep the ``{instruction: stage}`` map of the cell's compiled call."""
    _MAPS[_key(config, traffic)] = stages.stage_names(compiled_text)


def ms_per_push(ctx, stage: str) -> float | None:
    """Device self time per push of ``stage``, in ms; None where no map
    was recorded for the cell or its program names no such stage."""
    names = _MAPS.get(_key(ctx.config, ctx.traffic))
    if not names or stage not in names.values():
        return None
    return stages.self_s(ctx.trace, names, stage) / ctx.trace.pushes * 1e3
