"""Analytical cost-model plane: compiled-program cards + roofline drift.

Every number the observability plane reported before this module was
*measured* — the cost ledger says how long a program took, never how long
the hardware would have allowed. XLA already computed the missing half at
compile time: ``Compiled.cost_analysis()`` (flops, bytes accessed,
transcendentals) and ``Compiled.memory_analysis()`` (argument/output/temp
bytes) sit unread behind the same ``lower().compile()`` path the AOT layer
uses. This module reads them:

* **Compiled-program cards** (:func:`ensure_card`): every compile site —
  the eager kernel bundle, the fused multi-statistic program, the mesh
  shard_map program, the streaming step programs, and the serve/AOT
  replays — records one card per (label, input
  signature): analytical flops, bytes accessed, memory footprint, an HLO
  hash, the compile wall, and a roofline ``predicted_ms`` against the
  per-platform peak table. The analysis pass lowers and compiles the SAME
  program a second time purely for inspection (never executed, so results
  are bit-identical with the plane on); its compile/trace events are
  routed to ``costmodel.card_*`` counters so ``jax.compiles`` keeps
  meaning what the AOT acceptance criterion needs it to mean. Backends
  whose ``cost_analysis`` raises degrade to a card with
  ``analysis: "unavailable"`` — never an error into the dispatch path.
* **Roofline utilization**: at dispatch time the cost ledger row joins its
  card — achieved GB/s and FLOP/s against :data:`PEAK_TABLE` become the
  ``program.utilization`` / ``program.predicted_ms`` gauges (labeled per
  program on /metrics), and :func:`program_report` is the JSON face
  (``/debug/programs``, ``python -m flox_tpu.telemetry programs``).
* **Drift sentinel** (:func:`drift_report`): programs whose observed
  per-dispatch device time diverges more than
  ``OPTIONS["costmodel_drift_threshold"]``× from the model (roofline
  prediction floored at ``costmodel_overhead_ms`` — tiny programs are
  judged against dispatch overhead, not microsecond analytics) are
  flagged: the "this program silently got 10× slower after a JAX upgrade"
  detector, wired into the bench JSON and the fleet federator.
* **Autotune prior** (:func:`analytic_prior`): when ``autotune.decide``
  finds no measured band, the analytical model supplies a cold-start
  prior for the families it can reason about.

Everything is gated on :func:`enabled` — ``OPTIONS["telemetry"]`` AND
``OPTIONS["costmodel"]`` — and the registry is bounded, registered in
``cache.clear_all`` / ``cache.stats`` (floxlint FLX008).
"""

from __future__ import annotations

import contextvars
import hashlib
import logging
import threading
import time
from typing import Any

from . import telemetry
from .options import OPTIONS

logger = logging.getLogger(__name__)

__all__ = [
    "PEAK_TABLE",
    "analytic_prior",
    "aval_args",
    "card_for",
    "cards",
    "dispatch_marks",
    "drift_report",
    "enabled",
    "ensure_card",
    "program_report",
    "publish_gauges",
    "record_compiled",
    "serve_alias",
    "stamp_capture",
]

#: roofline peaks per chip, keyed by ``device_kind`` as jax reports it:
#: memory bandwidth (GB/s) and compute (GFLOP/s), each row with its source.
#: A device missing here is an error, not a default row.
PEAK_TABLE: dict[str, dict[str, Any]] = {
    "TPU v5 lite": {
        "bw_gbps": 819.0,
        "gflops": 197_000.0,  # bf16
        "source": "Google Cloud TPU documentation, 'TPU v5e'",
    },
    "cpu": {
        "bw_gbps": 20.0,
        "gflops": 100.0,
        "source": "round numbers for the CPU test backend; no datasheet",
    },
}

#: digest -> card: the compiled-program card registry. Bounded by program
#: diversity (same bound as the compiled-program caches); registered in
#: cache.clear_all / cache.stats (floxlint FLX008).
_CARD_REGISTRY: dict[str, dict] = {}
#: program label -> digest of the newest card recorded under that label
#: (serve aliases land here too); cleared with the registry.
_CARD_LABELS: dict[str, str] = {}
_REGISTRY_MAX = 1024
_LOCK = threading.RLock()

#: the serve layer's program label for whatever compiles inside its
#: dispatch: cards recorded (or re-touched) inside a :func:`serve_alias`
#: scope also index under the serving label, so a ``serve[mean#ab12]``
#: ledger row joins the underlying bundle/mesh/fused card.
_ALIAS: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "flox_tpu_costmodel_alias", default=None
)


def enabled() -> bool:
    """Whether the cost-model plane is on: ``OPTIONS["costmodel"]`` AND
    telemetry (cards join the cost ledger, which only exists enabled)."""
    return bool(OPTIONS["costmodel"]) and telemetry.enabled()


def platform_name() -> str:
    """The active jax backend name (``"cpu"`` when jax cannot answer)."""
    try:
        import jax

        return str(jax.default_backend())
    except Exception:  # noqa: BLE001 — identity must never break dispatch
        return "cpu"


def device_kind() -> str:
    """``device_kind`` of the first device: the :data:`PEAK_TABLE` key."""
    import jax

    return str(jax.devices()[0].device_kind)


def peaks_for(kind: str | None = None) -> dict[str, Any]:
    """The :data:`PEAK_TABLE` row for ``kind`` (default: the first
    device's ``device_kind``); raises for a device the table lacks."""
    if kind is None:
        kind = device_kind()
    try:
        return PEAK_TABLE[kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device_kind {kind!r}; add its published "
            f"numbers to costmodel.PEAK_TABLE (known: {sorted(PEAK_TABLE)})"
        ) from None


class serve_alias:
    """Context manager binding the serving layer's program label: any card
    recorded or re-touched inside also indexes under ``label``, so the
    serve ledger row (``serve[mean#ab12]``) joins the card of whatever
    program its dispatch actually compiled."""

    __slots__ = ("_label", "_token")

    def __init__(self, label: str | None) -> None:
        self._label = label
        self._token: contextvars.Token | None = None

    def __enter__(self) -> "serve_alias":
        if self._label is not None:
            self._token = _ALIAS.set(str(self._label))
        return self

    def __exit__(self, *exc: Any) -> bool:
        if self._token is not None:
            _ALIAS.reset(self._token)
            self._token = None
        return False


def _aval_signature(args: tuple, kwargs: dict | None = None) -> str:
    """A stable text signature of the call's abstract values: pytree
    structure + per-leaf (shape, dtype). Cheap — the per-dispatch memo
    check hashes this, so it must cost microseconds, not a trace."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    parts = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            parts.append(repr(leaf))
        else:
            parts.append(f"{tuple(shape)}:{dtype}")
    return "|".join(parts)


def _digest(label: str, sig: str) -> str:
    return hashlib.blake2b(f"{label}\x1f{sig}".encode(), digest_size=12).hexdigest()


def _index(label: str, digest: str) -> None:
    """Point ``label`` (and the active serve alias, if any) at ``digest``.
    Callers hold :data:`_LOCK`."""
    _CARD_LABELS[label] = digest
    alias = _ALIAS.get()
    if alias is not None:
        _CARD_LABELS[alias] = digest


def _cost_totals(compiled: Any) -> dict[str, float]:
    """flops / bytes accessed / transcendentals summed across the
    executable's modules. ``cost_analysis()`` returns a list of dicts on
    older jax and a plain dict on newer — both shapes land here."""
    analysis = compiled.cost_analysis()
    if isinstance(analysis, dict):
        analysis = [analysis]
    totals = {"flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}
    for entry in analysis or []:
        totals["flops"] += float(entry.get("flops", 0.0) or 0.0)
        totals["bytes_accessed"] += float(entry.get("bytes accessed", 0.0) or 0.0)
        totals["transcendentals"] += float(entry.get("transcendentals", 0.0) or 0.0)
    return totals


def _memory_totals(compiled: Any) -> dict[str, int]:
    """argument/output/temp/generated-code bytes from
    ``memory_analysis()`` (zeros where the backend reports none)."""
    out = {"argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
           "generated_code_bytes": 0}
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — per-backend degradation by contract
        return out
    if mem is None:
        return out
    out["argument_bytes"] = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
    out["output_bytes"] = int(getattr(mem, "output_size_in_bytes", 0) or 0)
    out["temp_bytes"] = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    out["generated_code_bytes"] = int(
        getattr(mem, "generated_code_size_in_bytes", 0) or 0
    )
    return out


def _hlo_hash(compiled: Any) -> str | None:
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 — some backends cannot re-render
        return None
    return hashlib.blake2b(str(text).encode(), digest_size=8).hexdigest()


def predicted_ms(card: dict, kind: str | None = None) -> float:
    """Roofline time for one dispatch of the card's program: the larger of
    the bandwidth leg (bytes accessed / peak GB/s) and the compute leg
    (flops / peak GFLOP/s), in milliseconds."""
    peaks = peaks_for(kind or card.get("device_kind"))
    bw_s = float(card.get("bytes_accessed", 0.0)) / (peaks["bw_gbps"] * 1e9)
    fl_s = float(card.get("flops", 0.0)) / (peaks["gflops"] * 1e9)
    return max(bw_s, fl_s) * 1e3


def record_compiled(
    label: str,
    compiled: Any,
    *,
    compile_ms: float = 0.0,
    sig: str = "",
    in_shapes: list | None = None,
) -> str | None:
    """Record one card from an already-compiled executable
    (:func:`ensure_card` builds one).
    Returns the card digest; never raises."""
    try:
        digest = _digest(label, sig)
        platform = platform_name()
        card: dict[str, Any] = {
            "label": label,
            "digest": digest,
            "platform": platform,
            "device_kind": device_kind(),
            "flops": 0.0,
            "bytes_accessed": 0.0,
            "transcendentals": 0.0,
            "compile_ms": round(float(compile_ms), 3),
            "analysis": "ok",
            "in_shapes": in_shapes or [],
            "recorded_at": time.time(),
        }
        try:
            card.update(_cost_totals(compiled))
        except Exception as exc:  # noqa: BLE001 — stat-less backend: a card
            # with analysis "unavailable", never an error into dispatch
            card["analysis"] = f"unavailable:{type(exc).__name__}"
        card.update(_memory_totals(compiled))
        card["peak_bytes"] = (
            card["argument_bytes"] + card["output_bytes"] + card["temp_bytes"]
        )
        card["hlo_hash"] = _hlo_hash(compiled)
        card["predicted_ms"] = round(predicted_ms(card), 6)
        with _LOCK:
            if len(_CARD_REGISTRY) >= _REGISTRY_MAX and digest not in _CARD_REGISTRY:
                # bounded: a pathological label churn drops the card, never
                # grows the registry without bound (counted, not silent)
                telemetry.count("costmodel.cards_dropped")
                return None
            _CARD_REGISTRY[digest] = card
            _index(label, digest)
        telemetry.count("costmodel.cards_recorded")
        return digest
    except Exception as exc:  # noqa: BLE001 — observability never breaks dispatch
        logger.debug("costmodel card for %r failed: %s", label, exc)
        return None


def ensure_card(label: str, fn: Any, args: tuple, kwargs: dict | None = None) -> str | None:
    """Record (once per label + input signature) the analytical card of the
    jitted ``fn`` as called with ``args``/``kwargs``.

    Called from the dispatch sites right where the program executes, with
    the same arguments — the card's program identity matches the program
    actually served. A registry hit is a dict lookup; a miss lowers and
    compiles the program once more purely for analysis, with its compile
    events routed to ``costmodel.card_*`` (``jax.compiles`` untouched).
    Never raises; returns the digest or ``None``.
    """
    if not enabled() or fn is None or not hasattr(fn, "lower"):
        return None
    try:
        sig = _aval_signature(args, kwargs)
        digest = _digest(label, sig)
        with _LOCK:
            if digest in _CARD_REGISTRY:
                _index(label, digest)
                return digest
            if len(_CARD_REGISTRY) >= _REGISTRY_MAX:
                # capacity checked BEFORE the analysis compile: a full
                # registry must not pay a fresh lower+compile on every
                # dispatch just to drop the result (counted, not silent)
                telemetry.count("costmodel.cards_dropped")
                return None
        t0 = time.perf_counter()
        with telemetry.card_compile_accounting():
            lowered = fn.lower(*args, **(kwargs or {}))
            t1 = time.perf_counter()
            compiled = lowered.compile()
        t2 = time.perf_counter()
        compile_ms = (t2 - t0) * 1e3
        # counted here, not from jax's backend_compile event: jax reuses
        # the executable the dispatch just built, so that event may never
        # fire for an analysis compile
        telemetry.METRICS.inc("costmodel.card_compiles")
        telemetry.METRICS.inc("costmodel.card_compile_ms", (t2 - t1) * 1e3)
        # full analysis wall (lowering included), accumulated so wrappers
        # timing a whole dispatch from outside (the serve execute window,
        # AOT warmup) can net it out of their observed device time
        telemetry.METRICS.inc("costmodel.card_analysis_ms", compile_ms)
        shapes = [
            [list(getattr(leaf, "shape", ())), str(getattr(leaf, "dtype", "?"))]
            for leaf in _leaves(args)
        ][:8]
        return record_compiled(
            label, compiled, compile_ms=compile_ms, sig=sig, in_shapes=shapes
        )
    except Exception as exc:  # noqa: BLE001 — observability never breaks dispatch
        logger.debug("costmodel lower/compile for %r failed: %s", label, exc)
        telemetry.count("costmodel.card_errors")
        return None


def aval_args(args: tuple) -> tuple:
    """``args`` with every array leaf replaced by a
    ``jax.ShapeDtypeStruct`` — a lowering-ready snapshot a caller can hold
    past the arrays' lifetime (the streaming path captures its step
    arguments this way and records the card AFTER the timed stream loop,
    so the analysis compile never lands in a pass's dispatch wall)."""
    import jax

    def leaf(x: Any) -> Any:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return x
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    return jax.tree_util.tree_map(leaf, args)


def _leaves(args: tuple) -> list:
    try:
        import jax

        return [
            leaf for leaf in jax.tree_util.tree_leaves(args)
            if hasattr(leaf, "shape")
        ]
    except Exception:  # noqa: BLE001
        return []


def cards() -> dict[str, dict]:
    """A locked copy of the card registry (digest -> card)."""
    with _LOCK:
        return {digest: dict(card) for digest, card in _CARD_REGISTRY.items()}


def card_for(label: str) -> dict | None:
    """The newest card recorded under ``label`` (serve aliases included)."""
    with _LOCK:
        digest = _CARD_LABELS.get(label)
        card = _CARD_REGISTRY.get(digest) if digest is not None else None
        return dict(card) if card is not None else None


def _net_device_ms(entry: dict) -> float:
    """Observed device wall net of the compile wall the same row billed:
    an honest first dispatch pays trace+compile inside its dispatch span,
    and judging THAT against the steady-state roofline would flag every
    cold start as drift. Floored at 0 (a cache-served compile can bill
    more compile_ms than wall on pathological clocks)."""
    return max(
        0.0,
        float(entry.get("device_ms", 0.0)) - float(entry.get("compile_ms", 0.0)),
    )


def _utilization(entry: dict, card: dict) -> dict[str, float]:
    """The roofline join of one ledger row and its card: achieved GB/s and
    GFLOP/s, and utilization = model time / observed time (the fraction of
    the peak-table ceiling the dispatches actually reached). Times are
    compile-net (:func:`_net_device_ms`)."""
    device_ms = _net_device_ms(entry)
    dispatches = int(entry.get("dispatches", 0))
    if device_ms <= 0.0 or dispatches <= 0:
        return {"utilization": 0.0, "achieved_gbps": 0.0, "achieved_gflops": 0.0}
    seconds = device_ms / 1e3
    achieved_gbps = float(card.get("bytes_accessed", 0.0)) * dispatches / seconds / 1e9
    achieved_gflops = float(card.get("flops", 0.0)) * dispatches / seconds / 1e9
    util = float(card.get("predicted_ms", 0.0)) * dispatches / device_ms
    return {
        "utilization": round(util, 6),
        "achieved_gbps": round(achieved_gbps, 6),
        "achieved_gflops": round(achieved_gflops, 6),
    }


def publish_gauges(label: str, entry: dict) -> None:
    """Update the per-program roofline gauges after one dispatch's ledger
    write: ``program.utilization|program=<label>`` (fraction of the peak
    ceiling reached so far) and ``program.predicted_ms|program=<label>``
    (the model's per-dispatch time). No-op without a card for the label."""
    card = card_for(label)
    if card is None or not str(card.get("analysis", "")).startswith("ok"):
        return
    safe = _label_safe(label)
    join = _utilization(entry, card)
    telemetry.METRICS.set_gauge(
        f"program.utilization|program={safe}", join["utilization"]
    )
    telemetry.METRICS.set_gauge(
        f"program.predicted_ms|program={safe}", float(card["predicted_ms"])
    )


def _label_safe(label: str) -> str:
    """A program label safe as a registry ``|key=value`` label value: the
    separator characters fold away (quotes/backslashes are escaped at
    render time by the exposition layer)."""
    return str(label).replace("|", "_").replace("=", "_")[:120]


def program_report(top: int | None = None, program: str | None = None) -> dict:
    """The compiled-program card table joined with the observed cost
    ledger — the payload behind ``/debug/programs`` and the ``programs``
    CLI.

    One row per program label: the card (analytical flops/bytes/footprint/
    predicted time) plus ``observed`` (the ledger row) and the roofline
    join (utilization, achieved GB/s and GFLOP/s, drift ratio vs the
    overhead-floored model). ``program`` filters labels by substring;
    ``top`` keeps the K rows with the most observed device time (rows
    without observations rank last)."""
    ledger = telemetry.cost_by_program()
    with _LOCK:
        labels = dict(_CARD_LABELS)
        registry = {d: dict(c) for d, c in _CARD_REGISTRY.items()}
    overhead = float(OPTIONS["costmodel_overhead_ms"])
    rows: dict[str, dict] = {}
    for label, digest in labels.items():
        card = registry.get(digest)
        if card is None:
            continue
        if program is not None and program not in label:
            continue
        row = dict(card, label=label)
        entry = ledger.get(label)
        row["observed"] = dict(entry) if entry is not None else None
        if entry is not None:
            row.update(_utilization(entry, card))
            dispatches = int(entry.get("dispatches", 0))
            if dispatches > 0:
                obs_ms = _net_device_ms(entry) / dispatches
                model_ms = max(float(card.get("predicted_ms", 0.0)), overhead)
                row["observed_ms_per_dispatch"] = round(obs_ms, 6)
                row["model_ms"] = round(model_ms, 6)
                row["drift_ratio"] = round(obs_ms / model_ms, 6) if model_ms else None
        rows[label] = row
    if top is not None:
        ranked = sorted(
            rows.items(),
            key=lambda kv: (
                -float((kv[1].get("observed") or {}).get("device_ms", 0.0)),
                -int((kv[1].get("observed") or {}).get("dispatches", 0)),
                kv[0],
            ),
        )
        rows = dict(ranked[:top])
    return {
        "programs": rows,
        "peaks": dict(peaks_for()),
        "platform": platform_name(),
        "device_kind": device_kind(),
        "overhead_ms": overhead,
        "drift_threshold": float(OPTIONS["costmodel_drift_threshold"]),
        # per-dataset attribution: device time billed against resident
        # registry entries ({"op": "put_dataset"} names), so the report
        # answers "which pinned dataset is earning its HBM"
        "datasets": telemetry.cost_by_dataset(),
    }


def drift_report(rows: dict | None = None, threshold: float | None = None) -> dict:
    """The predicted-vs-observed drift sentinel.

    A program drifts when its observed per-dispatch device time exceeds
    ``threshold``× the model, where the model is the roofline prediction
    floored at ``OPTIONS["costmodel_overhead_ms"]`` (tiny programs are
    judged against dispatch overhead — an honest CPU run of microsecond
    programs must exit clean, a synthetically delayed dispatch must not).
    Programs with a single observed dispatch are reported but never
    flagged: one cold call is all trace/staging warm-up (the compile wall
    is already netted out, the trace wall is not) — drift is a
    steady-state verdict. ``rows`` defaults to the live
    :func:`program_report` table and also
    accepts a ``/debug/programs`` scrape's ``programs`` mapping, so the
    sentinel runs against a saved scrape of another process. Returns
    ``{"rows": [...], "flagged": [labels], "threshold", "overhead_ms"}``.
    """
    if threshold is None:
        threshold = float(OPTIONS["costmodel_drift_threshold"])
    if rows is None:
        rows = program_report()["programs"]
    out_rows = []
    flagged = []
    for label in sorted(rows):
        row = rows[label]
        ratio = row.get("drift_ratio")
        if ratio is None or not str(row.get("analysis", "")).startswith("ok"):
            continue
        dispatches = int((row.get("observed") or {}).get("dispatches", 0))
        verdict = dispatches >= 2 and float(ratio) > float(threshold)
        out_rows.append(
            {
                "program": label,
                "observed_ms_per_dispatch": row.get("observed_ms_per_dispatch"),
                "predicted_ms": row.get("predicted_ms"),
                "model_ms": row.get("model_ms"),
                "drift_ratio": ratio,
                "flagged": verdict,
            }
        )
        if verdict:
            flagged.append(label)
    if flagged:
        telemetry.count("costmodel.drift_flagged", len(flagged))
    return {
        "threshold": float(threshold),
        "overhead_ms": float(OPTIONS["costmodel_overhead_ms"]),
        "rows": out_rows,
        "flagged": flagged,
    }


# ---------------------------------------------------------------------------
# autotune prior: the analytical model as the cold-start decision
# ---------------------------------------------------------------------------


def analytic_prior(
    family: str,
    fallback: str,
    candidates: tuple,
    *,
    dtype: Any = None,
    ngroups: int = 0,
    nelems: int = 0,
) -> str | None:
    """An analytical prior for an autotune family with no measured band.

    Consulted by ``autotune.decide`` only when the store holds nothing
    close enough. Families the roofline model can reason about get a
    verdict; everything else returns ``None`` (the heuristic fallback
    stands). Counted on ``costmodel.prior_consults`` /
    ``costmodel.prior_decisions``."""
    if not enabled():
        return None
    telemetry.count("costmodel.prior_consults")
    try:
        import numpy as np

        itemsize = np.dtype(dtype).itemsize if dtype is not None else 8
    except (TypeError, ValueError):
        itemsize = 8
    peaks = peaks_for()
    data_bytes = max(0, int(nelems)) * itemsize
    cands = set(candidates)
    choice: str | None = None
    if family == "fused" and {"fused", "sequential"} <= cands:
        # fused reads the data once for the whole statistic set and
        # dispatches once; sequential reads it >= twice and pays >= two
        # dispatch overheads — strictly dominated in the roofline model at
        # every size, and the PR 10 measurements agree (fused won even the
        # small shapes, 5.4x). The analytical prior is unconditional.
        choice = "fused"
    elif family == "highcard" and {"dense", "sort"} <= cands:
        # dense streams the data once and writes ~3 dense (ngroups-sized)
        # intermediates; sort pays ~2 extra passes over the data (the
        # stable binning sort / host unique + compact relabel) but its
        # accumulators track the present groups, bounded above by nelems.
        # Both modeled as bandwidth passes — grouped reductions are
        # memory-bound on every platform in the peak table.
        n_acc = 3
        present_cap_elems = min(max(0, int(nelems)), max(1, int(ngroups)))
        dense_ms = (data_bytes + n_acc * max(1, ngroups) * itemsize) / (
            peaks["bw_gbps"] * 1e9
        ) * 1e3
        sort_ms = (3 * data_bytes + n_acc * present_cap_elems * itemsize) / (
            peaks["bw_gbps"] * 1e9
        ) * 1e3
        choice = "sort" if sort_ms < dense_ms else "dense"
    elif family == "store_query" and {"store", "recompute"} <= cands:
        # serving a finalized read from the durable store scatters the
        # present-groups carry (bounded by the label universe) and
        # finalizes; recomputing re-reduces the FULL history bytes. Both
        # are bandwidth passes; the store wins as soon as history
        # meaningfully exceeds the carry — nelems here is the total
        # history element count, ngroups the store's label universe.
        n_acc = 3
        store_ms = (n_acc * max(1, ngroups) * itemsize) / (
            peaks["bw_gbps"] * 1e9
        ) * 1e3
        recompute_ms = (data_bytes + n_acc * max(1, ngroups) * itemsize) / (
            peaks["bw_gbps"] * 1e9
        ) * 1e3
        choice = "store" if store_ms < recompute_ms else "recompute"
    elif family == "segment_sum" and "matmul" in cands and "scatter" in cands:
        # one-hot GEMM: 2·N·G flops at peak compute vs scatter's serialized
        # updates, modeled as a deeply de-rated bandwidth pass (scatters
        # cannot stream). Matmul wins while the group count is small enough
        # that the redundant flops stay cheaper than the scatter stall.
        matmul_ms = (2.0 * nelems * max(1, ngroups)) / (peaks["gflops"] * 1e9) * 1e3
        scatter_ms = data_bytes / (0.05 * peaks["bw_gbps"] * 1e9) * 1e3
        choice = "matmul" if matmul_ms < scatter_ms else "scatter"
    if choice is None or choice not in cands:
        return None
    telemetry.count("costmodel.prior_decisions")
    return choice


# ---------------------------------------------------------------------------
# capture stamping: tie a profiler capture dir to the programs it saw
# ---------------------------------------------------------------------------


def dispatch_marks() -> dict[str, int]:
    """Per-program-label cumulative dispatch counts from the cost ledger —
    the snapshot :func:`flox_tpu.profiling.start_capture` takes at window
    start so the finished capture can be stamped with exactly the programs
    dispatched inside it."""
    return {
        label: int(entry.get("dispatches", 0))
        for label, entry in telemetry.cost_by_program().items()
    }


def stamp_capture(capture_dir: str, marks: dict[str, int] | None) -> str | None:
    """Write ``programs.json`` into a finished capture dir: the program
    labels dispatched during the window (cumulative ledger dispatches now
    minus ``marks``), each with its card digest where one exists — the
    join key back to ``/debug/costs`` and ``/debug/programs`` rows.
    Best-effort by contract: never raises, returns the path or ``None``."""
    import json
    import os

    try:
        now = dispatch_marks()
        before = marks or {}
        window: dict[str, dict] = {}
        with _LOCK:
            labels = dict(_CARD_LABELS)
        for label, total in now.items():
            delta = total - int(before.get(label, 0))
            if delta <= 0:
                continue
            window[label] = {"dispatches": delta, "digest": labels.get(label)}
        path = os.path.join(str(capture_dir), "programs.json")
        payload = {
            "programs": window,
            "replica": telemetry.replica_instance(),
            "host": telemetry.host_name(),
        }
        os.makedirs(str(capture_dir), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path
    except Exception as exc:  # noqa: BLE001 — stamping must never break a capture
        logger.debug("capture stamp for %s failed: %s", capture_dir, exc)
        return None
