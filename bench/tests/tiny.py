"""The benchmark's cells cut to a size the Pallas interpreter runs in
seconds, for the self-tests and the recorded traces."""
from __future__ import annotations

import copy
import dataclasses

import manifest

#: each cell cut to a size the Pallas interpreter runs in seconds; only
#: scale changes, never the query's kind or the data's distributions
TINY = {
    "enthuse.flat-swag": {"window": {"ws": 256, "wa": 64}, "groups": 8,
                          "push_tuples": 2048},
    "enthuse.per-group": {"window": {"ws": 64, "ws_per_group": 64, "wa": 16,
                                     "capacity": 20},
                          "groups": 4, "push_tuples": 1024},
}


def tiny_cell(name: str) -> manifest.Cell:
    cell = manifest.cell(manifest.load(), name)
    cut = TINY[name]
    config = copy.deepcopy(cell.config)
    config["query"]["window"] = dict(cut["window"])
    groups = next(c for c in config["data"]["columns"]
                  if c["name"] == "groups")
    groups["high"] = cut["groups"]
    traffic = dict(cell.traffic, push_tuples=cut["push_tuples"])
    return dataclasses.replace(cell, config=config, traffic=traffic)
