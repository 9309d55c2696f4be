"""Record the small TPU traces that ``test_stages.py`` reads: the program's
stage scopes on the chip.  Run on one chip:

    python3 bench/tests/record_stages.py

It records each cell as ``record_trace.py`` does, into
``bench/tests/data/stages/``, and adds to each ``<cell>.json`` the map from
compiled instruction to stage (``stages.of_cell``) of the program it ran.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS))

import record_trace  # noqa: E402
import stages  # noqa: E402
import tiny  # noqa: E402

DATA = TESTS / "data" / "stages"


def main() -> int:
    record_trace.DATA = DATA
    status = record_trace.main()
    if status:
        return status
    for name in record_trace.SECONDS:
        cell = tiny.tiny_cell(name)
        path = DATA / f"{name}.json"
        meta = json.loads(path.read_text())
        meta["stages"] = stages.of_cell(cell.config, cell.traffic)
        path.write_text(json.dumps(meta, indent=1, sort_keys=True) + "\n")
        print(name, sorted(set(meta["stages"].values())), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
