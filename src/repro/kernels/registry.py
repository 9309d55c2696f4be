"""Backend registry for the unified query-plan API (``repro.query``).

One place answers "which implementation runs this query?" — replacing the
per-file ``_is_cpu()`` / ``interpret`` heuristics that used to live in each
``kernels/*/ops.py``:

  * ``reference``     — pure-JAX engine/SWAG in ``repro.core`` (runs anywhere;
                        the oracle every kernel is cross-checked against)
  * ``pallas``        — fused Pallas kernels, each window re-sorted from
                        scratch (group-by via the tiled groupagg kernel)
  * ``pallas-panes``  — fused Pallas pane kernels: WA-panes sorted once,
                        windows assembled by the bitonic merge network
  * ``pallas-panestore`` — per-group windows (``Window(ws_per_group=...)``):
                        pane gather + in-VMEM merge + one shared butterfly
                        compaction per replay row (store bookkeeping in XLA)
  * ``auto``          — capability-probed choice (platform + query shape)

Selection precedence: explicit ``backend=`` argument > the ``REPRO_BACKEND``
environment variable > ``auto``.  The capability probe
(:func:`repro.kernels.common.default_interpret`) picks Pallas interpret mode
on CPU and compiled Mosaic on TPU; ``auto`` keeps reference on CPU (interpret
mode is a validation tool, not a fast path) and prefers the pane kernels on
TPU whenever the window shape allows.

New backends register with :func:`register_backend` — the software analogue
of the paper's "adaptable engine" axis: the :class:`repro.query.Query` spec
stays fixed while engines come and go underneath it.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable

from repro.core.panestore import DIRECT_OPS
from repro.core.swag import pane_compatible
from repro.kernels import common

#: environment variable consulted when no explicit backend is passed
BACKEND_ENV = "REPRO_BACKEND"


@dataclasses.dataclass(frozen=True)
class Backend:
    """One engine implementation the planner can lower a Query onto.

    ``supports(query) -> str | None`` returns a human-readable reason when
    the backend cannot run the query (None = supported).  The runner
    callables are bound lazily (import cost + cycle avoidance) by
    ``repro.query``; the registry only answers capability questions.
    """
    name: str
    supports: Callable[[object], str | None]
    #: kernels run in interpret mode on CPU (capability probe)
    uses_kernels: bool = False


def _ref_supports(q) -> str | None:
    return None  # the reference path is total — it is the oracle


def _pallas_window_common(q) -> str | None:
    """Window-clause checks shared by both global-window kernel backends."""
    if q.window.per_group:
        return ("per-group windows replay from the shared pane store — "
                "use the pallas-panestore backend")
    if q.window.ws & (q.window.ws - 1):
        return f"pallas window kernels need power-of-two WS, got {q.window.ws}"
    if q.presorted:
        return "pallas window kernels always sort in VMEM"
    if q.interpolate:
        return "pallas median is lower-median only (interpolate=False)"
    return None


def _pallas_supports(q) -> str | None:
    if q.streaming:
        return "streaming carries are a reference-backend feature"
    if q.window is not None and q.window.is_time:
        # both time strategies have a kernel rendering: replay frames run
        # the fused sort+tails kernel, the two-stack runs the stack-flip
        # kernel — strategy eligibility is the planner's check
        if q.interpolate:
            return "pallas median is lower-median only (interpolate=False)"
        return None
    if q.window is not None:
        common = _pallas_window_common(q)
        if common is not None:
            return common
        if q.window.panes is True and q.window.wa < q.window.ws:
            # never a silent fallback: an explicit pane force belongs to
            # pallas-panes (wa == ws is exempt — there the pane path *is*
            # the per-window re-sort)
            return ("Window(panes=True) forces the pane path — use the "
                    "pallas-panes backend")
    else:
        if any(op in ("argmin", "argmax") for op in q.ops):
            return ("position-carrying operators lift a global iota; the "
                    "tiled kernel lifts per tile")
        if "median" in q.ops and q.interpolate:
            return "pallas median is lower-median only (interpolate=False)"
    return None


def _pallas_panes_supports(q) -> str | None:
    if q.window is None:
        return "pane kernels are a windowed-query backend"
    if q.window.is_time:
        return ("time-range windows re-frame by timestamp (no shared "
                "count-panes to sort once); use the pallas or reference "
                "backend")
    if q.streaming:
        return "streaming carries are a reference-backend feature"
    common = _pallas_window_common(q)
    if common is not None:
        return common
    ws, wa = q.window.ws, q.window.wa
    if not (pane_compatible(ws, wa) or (ws == wa and ws & (ws - 1) == 0)):
        return (f"pane path needs power-of-two WS/WA with WA dividing WS, "
                f"got ws={ws} wa={wa}")
    if q.window.panes is False:
        return "Window(panes=False) forces the re-sort path"
    return None


def _pallas_panestore_supports(q) -> str | None:
    if q.window is None or not q.window.per_group:
        return ("the pane-store kernel serves per-group windows "
                "(Window(ws_per_group=...)) only")
    if q.streaming:
        return "streaming pane-store carries are a reference-backend feature"
    if q.interpolate:
        return "pallas median is lower-median only (interpolate=False)"
    bad = sorted(op for op in q.op_names if op not in DIRECT_OPS)
    if bad:
        return (f"the pane-store kernel computes {sorted(DIRECT_OPS)} "
                f"directly (partial-fused for the partial-path ops, "
                f"merge-replay otherwise); {bad} need the reference "
                f"backend's engine-tail fallback")
    return None


def pergroup_kernel_path(query, key_dtype=None) -> str:
    """Which regime the pane-store kernel backend would run this per-group
    query in: ``"partial-fused"`` (one fused push+replay launch, ring
    buffers VMEM-resident) when every op rides the per-pane partial path,
    else ``"merge-replay"`` (gather + one merge/compaction launch).  The
    capability surface the planner and tests probe without executing."""
    import jax.numpy as jnp

    from repro.core.panestore import partial_path_names
    psel = partial_path_names(
        list(query.op_names), jnp.int32 if key_dtype is None else key_dtype)
    return "partial-fused" if (psel and all(psel)) else "merge-replay"


_BACKENDS: dict[str, Backend] = {}


def register_backend(backend: Backend) -> None:
    """Extension point: plug a new engine under the fixed Query spec."""
    _BACKENDS[backend.name] = backend


register_backend(Backend("reference", _ref_supports))
register_backend(Backend("pallas", _pallas_supports, uses_kernels=True))
register_backend(Backend("pallas-panes", _pallas_panes_supports,
                         uses_kernels=True))
register_backend(Backend("pallas-panestore", _pallas_panestore_supports,
                         uses_kernels=True))


def available_backends() -> tuple[str, ...]:
    return tuple(_BACKENDS) + ("auto",)


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; have {sorted(available_backends())}"
        ) from None


def unsupported_error(name: str, reason: str) -> ValueError:
    """The error raised when an explicitly requested backend rejects a
    query: names the probe's reason *and* lists the alternatives (never a
    silent fallback — the caller picks, the registry informs)."""
    return ValueError(
        f"backend {name!r} cannot run this query: {reason} "
        f"[available backends: {', '.join(sorted(available_backends()))}]")


def resolve_backend(explicit: str | None = None) -> str:
    """Apply the selection precedence; returns a backend name (may be
    ``"auto"``, which :func:`choose_backend` then resolves per query).

    Both sources are validated **eagerly**: an unknown explicit name and an
    unknown ``REPRO_BACKEND`` env value each raise here, at plan time, with
    the available-backends list — never a late dispatch failure deep in
    execution (the env var is set far from the call site, so its error
    names the variable)."""
    if explicit is not None:
        name = explicit
        if name != "auto":
            get_backend(name)  # validate early
        return name
    name = os.environ.get(BACKEND_ENV) or "auto"
    if name != "auto" and name not in _BACKENDS:
        raise ValueError(
            f"{BACKEND_ENV}={name!r} names no registered backend "
            f"[available backends: "
            f"{', '.join(sorted(available_backends()))}]")
    return name


def choose_backend(query, devices=None, num_shards: int = 1
                   ) -> tuple[str, str]:
    """Resolve ``auto`` for one query: **measured-cost routing** over the
    capability-filtered candidates, with the static probe as fallback.
    Returns ``(backend, how)``, ``how`` naming the rule that chose it
    (``"measured"``, ``"cpu"`` or ``"accelerator"``) — the planner records
    it on the plan's note, so every choice shows.

    The adaptive half: among the backends whose capability probe accepts
    the query, consult :class:`repro.obs.registry.MetricsRegistry` for
    observed tuples/s at this query's fingerprint and pick the fastest —
    but only when **two or more** candidates have measured cells.  A
    single cell proves nothing about the alternatives (and on CPU it
    would usually be the reference path's own telemetry re-electing
    itself), so anything less falls back to the static choice.

    The static probe: on CPU every kernel would run in Pallas interpret
    mode — a correctness tool, orders of magnitude slower than the
    reference path — so ``auto`` stays on ``reference``.  On an
    accelerator the fused kernels win: the pane-store kernel for
    per-group windows, pane kernels when the window shape allows sharing
    sorted panes, the re-sort kernel otherwise.

    ``devices`` makes the probe **device-aware**: pass the devices of the
    mesh a sharded query runs over and the choice reflects *their*
    platform, not the process default — each shard still picks
    ``reference`` | ``pallas`` | ``pallas-panes`` locally, with its
    per-shard kernels unchanged.
    """
    candidates = [name for name in ("pallas-panestore", "pallas-panes",
                                    "pallas", "reference")
                  if get_backend(name).supports(query) is None]

    # measured-cost routing (lazy import: repro.obs must stay importable
    # without the kernels package and vice versa)
    from repro.obs.registry import METRICS, query_fingerprint
    fp = query_fingerprint(query, num_shards=num_shards)
    measured = [name for name in candidates
                if METRICS.tuples_per_s(name, fp)]
    if len(measured) >= 2:
        best = METRICS.best_backend(fp, among=candidates)
        if best is not None:
            return best, "measured"

    if common.is_cpu(devices):
        return "reference", "cpu"
    for name in candidates:
        if name != "reference":
            return name, "accelerator"
    return "reference", "accelerator"
