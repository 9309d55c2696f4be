"""Plain references for the benchmark's configurations, in NumPy alone.

Each module exposes, for the query (the ``query`` object of a
configuration file):

* ``evaluate(pool, pushes, query) -> {push: result}``: the exact answer
  of each push in ``pushes``, where push ``i`` carried batch
  ``pool[i % len(pool)]`` (a batch is ``{column name: array}``) and the
  pushes before it, in the engine's result layout (``layout.py``);
* ``control(pool, pushes, query)``: the same with one of the
  configuration's guarantees broken (the check must call it not correct);
* ``bytes_per_push(config, traffic)``: the bytes a push must move through
  HBM, its input read once and its result written once.  The count
  follows from the shapes alone, whatever implements the query.

Nothing here imports the system under test.
"""
