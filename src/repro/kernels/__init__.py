"""Pallas TPU kernels for the paper's compute hot-spots.

  * segscan   — tiled rolling segmented scan (the PRRA scan network)
  * bitonic   — in-VMEM bitonic sorting network (FLiMS adaptation)
  * groupagg  — the FUSED 5-step group-by-aggregate engine (paper Fig. 2)
  * swag      — fused sliding-window sort + aggregate (paper Fig. 4);
                pane variant: sort WA-panes once, bitonic-merge P = WS/WA
                presorted panes per window in VMEM (sort work amortised
                across the P windows sharing each pane)
  * tablemerge — the pane-partial table's merge: the short-distance
                bitonic sweeps and butterfly-compaction rounds, a tile
                of the table per grid step (one module, called from
                ``repro.core.panetable``)

Each subpackage ships ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd wrapper, auto interpret-mode on CPU) and ``ref.py``
(pure-jnp oracle).  ``common.py`` holds the shared in-tile primitives
(Hillis–Steele segscan, reverse-butterfly compaction as shift+select
rounds, reshape-trick bitonic stages — all gather/scatter-free) plus the
``is_cpu``/``default_interpret`` capability probe.  ``registry.py`` is the
backend registry the query planner (``repro.query``) dispatches through:
``reference`` | ``pallas`` | ``pallas-panes`` | ``auto``, overridable via
the ``REPRO_BACKEND`` env var.
"""
