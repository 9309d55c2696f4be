"""The comparison that decides ``correct``: every element of a sample of
the window's results against the configuration's plain reference, and no
compile inside the window.

The guarantee is exactness, so the limit on mismatched elements is 0.
Compared: ``groups``, ``valid`` and ``num_groups`` everywhere, and every
op's values on the valid lanes (the result layout leaves the others
unspecified).  A result of another shape than the reference's counts
every reference element as mismatched.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: the limit on mismatched elements: the guarantee is exact results
MISMATCH_LIMIT = 0
#: the limit on compile events inside the measured window
COMPILE_LIMIT = 0


def mismatches(got: dict, want: dict) -> int:
    """Elements of ``got`` that differ from ``want`` (see module doc)."""
    size = (want["groups"].size + want["valid"].size
            + want["num_groups"].size
            + len(want["values"]) * int(want["valid"].sum()))
    if (got["groups"].shape != want["groups"].shape
            or got["valid"].shape != want["valid"].shape
            or got["num_groups"].shape != want["num_groups"].shape
            or set(got["values"]) != set(want["values"])
            or any(got["values"][k].shape != v.shape
                   for k, v in want["values"].items())):
        return size
    valid = want["valid"]
    bad = int(np.sum(got["groups"].astype(np.int64) != want["groups"]))
    bad += int(np.sum(got["valid"] != valid))
    bad += int(np.sum(got["num_groups"].astype(np.int64)
                      != want["num_groups"]))
    for name, w in want["values"].items():
        bad += int(np.sum((got["values"][name].astype(np.int64) != w)
                          & valid))
    return bad


@dataclasses.dataclass
class Verdict:
    correct: bool
    failed: int          # sampled pushes with a mismatch
    checks: dict         # each number compared, beside its limit


def compare_sample(reference, query: dict, pool, sample,
                   compiles: int) -> Verdict:
    """``reference``: the configuration's reference module; ``pool``: the
    host batches (``{column: array}``); ``sample``: ``(push, host
    result)`` pairs; ``compiles``: compile events counted inside the
    window."""
    wants = reference.evaluate(pool, [i for i, _ in sample], query)
    bad_elements, failed = 0, 0
    for push, got in sample:
        bad = mismatches(got, wants[push])
        bad_elements += bad
        failed += bad > 0
    checks = {
        "compiles_in_window": {"value": compiles, "limit": COMPILE_LIMIT},
        "pushes_compared": {"value": len(sample), "limit": 1},
        "mismatched_elements": {"value": bad_elements,
                                "limit": MISMATCH_LIMIT},
    }
    correct = (bad_elements <= MISMATCH_LIMIT and len(sample) >= 1
               and compiles <= COMPILE_LIMIT)
    return Verdict(correct, failed, checks)
