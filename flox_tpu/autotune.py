"""Telemetry-fed autotuner: a persistent per-host measurement store that
closes the loop from measurement to dispatch (ROADMAP item 4).

Every ``auto`` decision in the package — engine selection for host arrays
(``core._choose_engine``), the segment-sum lowering
(``kernels._segment_sum_impl``), quantile sort-vs-select
(``kernels._quantile_impl_choice``), and the streaming slab/prefetch sizing
(``streaming.py`` / ``pipeline.stream_slabs``) — used to be a static
heuristic, while PR 4's telemetry already measured exactly the signals
needed to choose better. This module is the store those signals feed and the
decision point that consults it:

* **Measurement store** (:data:`_AUTOTUNE_CACHE`): observed GB/s per
  candidate, keyed by ``(op-family, platform, dtype, ngroups-band,
  nelems-band)``. Fed by four sources: one-time micro-sweeps at first
  decision (:func:`prime_reduce` — budgeted, so an instrumented test suite
  stays bounded), the bench harnesses' impl sweeps (``bench.py`` records its
  ``impl_sweep_gbps`` / ``quantile_gbps`` winners here), per-pass
  :class:`~flox_tpu.profiling.StreamReport` observations
  (:func:`observe_stream` — throughput and overlap fraction per prefetch
  depth and slab band), and seeding from the repo's committed hardware
  evidence (``BENCH_HISTORY``, :func:`seed`).
* **Decisions** (:func:`decide`): with ``FLOX_TPU_AUTOTUNE=1`` an ``auto``
  policy returns the observed winner for the nearest measured band; without
  a record (or with the tuner off — the default) the existing heuristic
  runs unchanged, so dispatch is bit-identical to the pre-autotune tree.
  Off is *record-only*: observations still accrete (that is what makes the
  first enabled run informed), decisions never change.
* **Persistence**: atomic JSON-on-disk at ``OPTIONS["autotune_cache_path"]``
  (env ``FLOX_TPU_AUTOTUNE_CACHE_PATH``; ``None`` keeps the store
  in-process). A second process on the same host loads the file lazily at
  first consult and makes every measured decision without re-sweeping
  (``sweeps``/``cache_hits`` counters in :func:`decision_record` assert
  this). A corrupt or partial file falls back to heuristics with a warning
  — never an error on the hot path.
* **Trace safety**: decisions are consulted at trace time inside jitted
  programs, so :func:`decision_fingerprint` rides
  ``options.trace_fingerprint()`` — a record that flips a winner bumps the
  store version and invalidates exactly the compiled programs that baked
  the old choice in.
* **Regression sentinel** (:func:`regression_sentinel`): diffs a round's
  per-family GB/s against the store and the last ``BENCH_HISTORY`` round,
  flagging >15 % regressions in the emitted JSON (report-only in CI).

CLI: ``python -m flox_tpu.autotune report`` prints the store;
``python -m flox_tpu.autotune sentinel --current '{"fam": gbps}'`` runs the
sentinel standalone.

The in-memory store and its counters are registered in ``cache.clear_all``
(floxlint FLX008); clearing resets to the unloaded state, so the next
consult reloads from disk (or runs heuristics when no path is configured).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import warnings
from typing import Any, Callable, Iterable, Mapping

logger = logging.getLogger("flox_tpu.autotune")

__all__ = [
    "compare_families",
    "decide",
    "decision_fingerprint",
    "decision_record",
    "enabled",
    "load",
    "lookup",
    "make_key",
    "observe_stream",
    "pick_stream_batch_bytes",
    "pick_stream_prefetch",
    "prime_reduce",
    "record",
    "regression_sentinel",
    "save",
    "seed",
]

#: on-disk format version — a loader seeing another version discards the
#: file (with a warning) instead of misreading bands measured under
#: different key semantics
_FORMAT_VERSION = 1

#: the store: key string -> {"candidates": {name: {"gbps", "n"}}, "source"}.
#: Module-level mutable cache — registered in cache.clear_all (FLX008).
_AUTOTUNE_CACHE: dict[str, dict] = {}

#: process-local tuner state: lazy-load flag, sweep/hit counters, version.
#: A plain dict cleared by cache.clear_all; every read goes through .get()
#: with a default, so the cleared (empty) dict IS the reset state.
_AUTOTUNE_STATE: dict[str, Any] = {}

_LOCK = threading.RLock()

#: per-process ceiling on in-call micro-sweeps: an instrumented test suite
#: meeting hundreds of fresh (dtype, band) keys must stay bounded — keys
#: past the budget fall back to heuristics and measure nothing
_SWEEP_BUDGET = 16

#: micro-sweep workload bounds (elements along the reduced axis / kept rows)
_SWEEP_N_MAX = 8192
_SWEEP_ROWS = 8

#: engine-sweep workload cap: the numpy/jax crossover the sweep probes
#: lives in small-host-array territory, and a sweep this size says nothing
#: about bands beyond the engine tolerance (see :func:`prime_engine`)
_SWEEP_ENGINE_N_MAX = 65536

#: regression threshold for the sentinel: a family is flagged when its
#: GB/s drops below (1 - this) x the comparison point
_REGRESSION_THRESHOLD = 0.15

#: band-distance tolerance for nearest-band lookups, per family. Engine
#: crossover is sharply size-dependent (numpy wins only for small hosts
#: arrays), so its records must not stretch; kernel-lowering winners are
#: stable across decades of size, so seeds from bench-scale workloads may
#: serve interactive-scale calls. The "fused" family (fused-vs-sequential
#: multi-statistic dispatch, fed by bench.py's fused_sweep_gbps) rides the
#: stretchy default for the same reason.
_NEAREST_TOLERANCE = {"engine": 1}
_NEAREST_TOLERANCE_DEFAULT = 6

#: families whose winner is governed by the GROUP band: the highcard
#: dense-vs-sort crossover lives on the ngroups axis, so its nearest-band
#: match additionally bounds the group-band distance — a record swept at
#: the capped 2^20 universe must not decide for workloads on the other
#: side of the crossover. Families absent here keep the legacy behavior
#: (group band is a tiebreak only).
_NEAREST_TOLERANCE_GROUPS = {"highcard": 2}


def enabled() -> bool:
    """Whether autotuned dispatch is on (``OPTIONS["autotune"]``).

    Off (the default) is record-only: the store still accretes
    observations, decisions stay on the static heuristics."""
    from .options import OPTIONS

    return bool(OPTIONS["autotune"])


def cache_path() -> str | None:
    """The configured persistence path (``OPTIONS["autotune_cache_path"]``)."""
    from .options import OPTIONS

    path = OPTIONS["autotune_cache_path"]
    return None if path is None else str(path)


# ---------------------------------------------------------------------------
# key schema
# ---------------------------------------------------------------------------


def _platform() -> str:
    try:
        import jax

        return str(jax.default_backend())
    except Exception:  # noqa: BLE001 — keying must never break dispatch
        return "unknown"


def _gband(ngroups: int) -> int:
    """Group-count band: log2 (0 for unknown/zero)."""
    return int(ngroups).bit_length() if ngroups > 0 else 0


def _eband(nelems: int) -> int:
    """Element-count band: log4 — coarse on purpose, so a test suite's shape
    variety maps to a bounded key population."""
    return (int(nelems).bit_length() + 1) // 2 if nelems > 0 else 0


def make_key(
    family: str,
    *,
    dtype: Any = None,
    ngroups: int = 0,
    nelems: int = 0,
    platform: str | None = None,
) -> str:
    """The store key: ``family|platform|dtype|g<band>|e<band>``."""
    plat = _platform() if platform is None else platform
    dt = "any" if dtype is None else str(dtype)
    return f"{family}|{plat}|{dt}|g{_gband(ngroups)}|e{_eband(nelems)}"


def _split_key(key: str) -> tuple[str, str, str, int, int] | None:
    parts = key.split("|")
    if len(parts) != 5:
        return None
    try:
        return parts[0], parts[1], parts[2], int(parts[3][1:]), int(parts[4][1:])
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


def _ensure_loaded() -> None:
    """Lazy one-time load from the configured path (plus history seeding).

    Runs once per process (per ``clear_all``); a missing file is the normal
    fresh-host case, a corrupt one warns and falls back to heuristics."""
    with _LOCK:
        if _AUTOTUNE_STATE.get("loaded"):
            return
        _AUTOTUNE_STATE["loaded"] = True
        path = cache_path()
        if path is not None:
            load(path)
        if enabled():
            # fold in the repo's committed hardware evidence so the first
            # enabled call is informed (platform-keyed, so a CPU process
            # never serves an on-chip seed and vice versa). Seeds land only
            # under keys without real observations — a disk store holding,
            # say, stream records must not suppress the quantile seed.
            seed()


def load(path: str) -> bool:
    """Merge a persisted store file into the in-memory store.

    Returns whether a valid file was read. Corrupt/partial/alien-version
    files warn and leave the store unchanged — the decision layer then runs
    the plain heuristics, which is always safe."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except FileNotFoundError:
        return False
    except (OSError, ValueError) as exc:
        warnings.warn(
            f"flox_tpu.autotune: cache file {path!r} is unreadable "
            f"({type(exc).__name__}: {exc}); falling back to heuristics",
            RuntimeWarning,
            stacklevel=2,
        )
        return False
    if not isinstance(payload, dict) or payload.get("version") != _FORMAT_VERSION:
        warnings.warn(
            f"flox_tpu.autotune: cache file {path!r} has unsupported format "
            f"{payload.get('version') if isinstance(payload, dict) else type(payload).__name__!r}; "
            "falling back to heuristics",
            RuntimeWarning,
            stacklevel=2,
        )
        return False
    records = payload.get("records")
    if not isinstance(records, dict):
        warnings.warn(
            f"flox_tpu.autotune: cache file {path!r} carries no record table; "
            "falling back to heuristics",
            RuntimeWarning,
            stacklevel=2,
        )
        return False
    merged = 0
    with _LOCK:
        for key, rec in records.items():
            if _split_key(key) is None or not isinstance(rec, dict):
                continue
            cands = rec.get("candidates")
            if not isinstance(cands, dict):
                continue
            clean = {
                str(name): {"gbps": float(c["gbps"]), "n": int(c.get("n", 1))}
                for name, c in cands.items()
                if isinstance(c, dict) and isinstance(c.get("gbps"), (int, float))
            }
            if not clean:
                continue
            # a loaded record wins over nothing but merges under any
            # same-key in-process observations (those are fresher)
            slot = _AUTOTUNE_CACHE.setdefault(
                key, {"candidates": {}, "source": str(rec.get("source", "disk"))}
            )
            for name, c in clean.items():
                slot["candidates"].setdefault(name, c)
            merged += 1
        if merged:
            _AUTOTUNE_STATE["version"] = _AUTOTUNE_STATE.get("version", 0) + 1
    logger.debug("autotune: loaded %d record(s) from %s", merged, path)
    return merged > 0


def save(path: str | None = None) -> str | None:
    """Atomically persist the store as JSON (tmp + rename).

    ``None`` uses the configured ``autotune_cache_path``; with neither, the
    save is a no-op returning ``None``."""
    path = cache_path() if path is None else str(path)
    if path is None:
        return None
    # merge-on-save: a record-only process may never have consulted the
    # store (so the lazy load never ran) — writing just its own records
    # would clobber every other process's persisted measurements. Folding
    # the file in first is safe: in-process observations win on key
    # collisions (load() is setdefault-merge), missing files are the
    # normal fresh-host case.
    load(path)
    with _LOCK:
        # deep-copy down to the candidate slots: json.dump runs outside the
        # lock, and a concurrent record() mutating a live candidates dict
        # mid-serialization would abort the save
        payload = {
            "version": _FORMAT_VERSION,
            "platform": _platform(),
            "records": {
                key: {
                    "candidates": {
                        name: dict(c) for name, c in rec["candidates"].items()
                    },
                    "source": rec.get("source", "observed"),
                }
                for key, rec in _AUTOTUNE_CACHE.items()
            },
        }
    parent = os.path.dirname(path)
    try:
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)  # a crash mid-write never truncates the store
    except OSError as exc:
        logger.warning("autotune: could not persist store to %s: %s", path, exc)
        return None
    return path


def _register_atexit() -> None:
    with _LOCK:  # RLock: safe whether or not the caller already holds it
        if _AUTOTUNE_STATE.get("atexit"):
            return
        _AUTOTUNE_STATE["atexit"] = True
    import atexit

    atexit.register(_save_at_exit)


def _save_at_exit() -> None:
    if _AUTOTUNE_CACHE and cache_path() is not None:
        save()


# ---------------------------------------------------------------------------
# recording + lookup
# ---------------------------------------------------------------------------


def record(
    family: str,
    candidate: str,
    gbps: float,
    *,
    dtype: Any = None,
    ngroups: int = 0,
    nelems: int = 0,
    platform: str | None = None,
    source: str = "observed",
) -> None:
    """Record one observed throughput for ``candidate`` under the banded key.

    Observations fold into an EWMA (alpha 0.3) so a noisy rep cannot flip a
    winner by itself; a flip that does happen bumps the store version (and
    with it ``options.trace_fingerprint``, invalidating compiled programs
    that baked the old winner in). Recording is live in record-only mode
    too — that is the mode's entire point. ``source="seed"`` records defer
    to any measured record already holding the key."""
    if not (isinstance(gbps, (int, float)) and gbps > 0):
        return
    key = make_key(
        family, dtype=dtype, ngroups=ngroups, nelems=nelems, platform=platform
    )
    with _LOCK:
        if source == "seed":
            existing = _AUTOTUNE_CACHE.get(key)
            if existing is not None and existing.get("source") != "seed":
                return  # real observations outrank committed evidence
        rec = _AUTOTUNE_CACHE.setdefault(key, {"candidates": {}, "source": source})
        before = _winner(rec)
        slot = rec["candidates"].get(candidate)
        if slot is None:
            rec["candidates"][candidate] = {"gbps": float(gbps), "n": 1}
        else:
            slot["gbps"] = 0.7 * float(slot["gbps"]) + 0.3 * float(gbps)
            slot["n"] = int(slot["n"]) + 1
        rec["source"] = source
        if _winner(rec) != before:
            _AUTOTUNE_STATE["version"] = _AUTOTUNE_STATE.get("version", 0) + 1
        _AUTOTUNE_STATE["records"] = _AUTOTUNE_STATE.get("records", 0) + 1
    if cache_path() is not None:
        _register_atexit()


def _winner(rec: Mapping[str, Any]) -> str | None:
    cands = rec.get("candidates") or {}
    if not cands:
        return None
    return max(cands, key=lambda name: cands[name]["gbps"])


def lookup(
    family: str,
    *,
    dtype: Any = None,
    ngroups: int = 0,
    nelems: int = 0,
    platform: str | None = None,
) -> dict | None:
    """The record for the exact key, else the nearest measured band within
    the family's tolerance (same family/platform/dtype; element band first,
    group band as tiebreak). ``None`` when nothing close enough exists."""
    _ensure_loaded()
    key = make_key(
        family, dtype=dtype, ngroups=ngroups, nelems=nelems, platform=platform
    )
    with _LOCK:
        rec = _AUTOTUNE_CACHE.get(key)
        if rec is not None:
            return rec
        want = _split_key(key)
        if want is None:
            return None
        tolerance = _NEAREST_TOLERANCE.get(family, _NEAREST_TOLERANCE_DEFAULT)
        gtolerance = _NEAREST_TOLERANCE_GROUPS.get(family)
        best_rec, best_dist = None, None
        for other_key, other in _AUTOTUNE_CACHE.items():
            got = _split_key(other_key)
            if got is None or got[:3] != want[:3]:
                continue
            dist = (abs(got[4] - want[4]), abs(got[3] - want[3]))
            if dist[0] > tolerance:
                continue
            if gtolerance is not None and dist[1] > gtolerance:
                continue
            if best_dist is None or dist < best_dist:
                best_rec, best_dist = other, dist
        return best_rec


def decide(
    family: str,
    fallback: str,
    candidates: Iterable[str],
    *,
    dtype: Any = None,
    ngroups: int = 0,
    nelems: int = 0,
) -> str:
    """The observed winner for the key when the tuner is on and has one
    among ``candidates``; the heuristic ``fallback`` otherwise.

    Safe at trace time: a pure host-side dict lookup, no jax calls."""
    if not enabled():
        return fallback
    rec = lookup(family, dtype=dtype, ngroups=ngroups, nelems=nelems)
    if rec is None:
        # no measured band close enough: the analytical cost model (when
        # its plane is on) supplies a cold-start prior for the families it
        # can reason about — measured observations outrank it the moment
        # one lands in the store
        prior = _analytic_prior(
            family, fallback, tuple(candidates),
            dtype=dtype, ngroups=ngroups, nelems=nelems,
        )
        return prior if prior is not None else fallback
    cands = rec.get("candidates") or {}
    eligible = {name: cands[name]["gbps"] for name in cands if name in set(candidates)}
    if not eligible:
        return fallback
    winner = max(eligible, key=lambda name: eligible[name])
    with _LOCK:
        _AUTOTUNE_STATE["hits"] = _AUTOTUNE_STATE.get("hits", 0) + 1
    if winner != fallback:
        logger.debug(
            "autotune: %s -> %r (heuristic said %r)", family, winner, fallback
        )
    return winner


def _analytic_prior(
    family: str,
    fallback: str,
    candidates: tuple,
    *,
    dtype: Any,
    ngroups: int,
    nelems: int,
) -> str | None:
    """``costmodel.analytic_prior`` behind a guard: the tuner must work
    identically when the cost-model plane is off or unimportable."""
    try:
        from .costmodel import analytic_prior

        return analytic_prior(
            family, fallback, candidates,
            dtype=dtype, ngroups=ngroups, nelems=nelems,
        )
    except Exception:  # noqa: BLE001 — a prior failure is a fallback, never a fault
        return None


def decision_fingerprint() -> tuple:
    """The autotune component of ``options.trace_fingerprint``.

    Constant while the tuner is off (record-only mode must not invalidate
    compiled programs); versioned while on, so a record that flips a winner
    retraces exactly once."""
    if not enabled():
        return (False,)
    return (True, _AUTOTUNE_STATE.get("version", 0))


def decision_record() -> dict:
    """A compact summary for bench rows / the CLI: store size, counters,
    and the current per-family winners."""
    _ensure_loaded()
    with _LOCK:
        winners = {}
        for key, rec in sorted(_AUTOTUNE_CACHE.items()):
            name = _winner(rec)
            if name is not None:
                winners[key] = {
                    "winner": name,
                    "gbps": round(rec["candidates"][name]["gbps"], 3),
                    "source": rec.get("source", "observed"),
                }
        return {
            "enabled": enabled(),
            "cache_path": cache_path(),
            "entries": len(_AUTOTUNE_CACHE),
            "sweeps": _AUTOTUNE_STATE.get("sweeps", 0),
            "cache_hits": _AUTOTUNE_STATE.get("hits", 0),
            "version": _AUTOTUNE_STATE.get("version", 0),
            "winners": winners,
        }


# ---------------------------------------------------------------------------
# seeding from committed hardware evidence
# ---------------------------------------------------------------------------


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seed(root: str | None = None) -> int:
    """Seed the store from the repo's committed measurements: the newest
    ``BENCH_HISTORY`` round. Records land under the bench workload's bands
    with ``source="seed"`` so the nearest-band lookup can serve them until
    real observations replace them. Returns how many records were seeded."""
    root = _repo_root() if root is None else root
    seeded = 0
    path = os.path.join(root, "BENCH_HISTORY", "bench_runs.jsonl")
    try:
        with open(path) as f:
            lines = f.read().strip().splitlines()
        payload = json.loads(lines[-1]) if lines else None
    except (OSError, ValueError):
        payload = None
    if isinstance(payload, dict):
        seeded += _seed_from_bench_record(payload)
    logger.debug("autotune: seeded %d record(s) from bench history", seeded)
    return seeded


def _seed_from_bench_record(payload: Mapping[str, Any]) -> int:
    plat = payload.get("platform")
    if not isinstance(plat, str):
        return 0
    workload = payload.get("workload") or {}
    ntime = int(workload.get("ntime", 26304))
    nspace = int(workload.get("nlat", 181)) * int(workload.get("nlon", 360))
    ngroups = int(workload.get("ngroups", 12))
    nelems = ntime * nspace
    count = 0
    sweep = payload.get("impl_sweep_gbps")
    if isinstance(sweep, Mapping):
        for impl, gbps in sweep.items():
            if isinstance(gbps, (int, float)) and gbps > 0:
                record(
                    "segment_sum", str(impl), float(gbps), dtype="float32",
                    ngroups=ngroups, nelems=nelems, platform=plat, source="seed",
                )
                count += 1
    quantile = payload.get("quantile_gbps")
    if isinstance(quantile, Mapping):
        for impl, gbps in quantile.items():
            if isinstance(gbps, (int, float)) and gbps > 0:
                record(
                    "quantile", str(impl), float(gbps), dtype="float32",
                    ngroups=ngroups, nelems=nelems, platform=plat, source="seed",
                )
                count += 1
    highcard = payload.get("highcard")
    if isinstance(highcard, Mapping):
        # the highcard sweep records its own workload bands (universe size
        # and elements actually timed) so the seed lands where it measured
        hc_ngroups = highcard.get("ngroups")
        hc_nelems = highcard.get("nelems")
        if isinstance(hc_ngroups, int) and isinstance(hc_nelems, int):
            for cand in ("dense", "sort"):
                gbps = highcard.get(f"{cand}_gbps")
                if isinstance(gbps, (int, float)) and gbps > 0:
                    record(
                        "highcard", cand, float(gbps), dtype="float32",
                        ngroups=hc_ngroups, nelems=hc_nelems, platform=plat,
                        source="seed",
                    )
                    count += 1
    fused = payload.get("fused")
    if isinstance(fused, Mapping):
        sweep_f = fused.get("fused_sweep_gbps")
        # the fused sweep may have measured a bounded row subset: its
        # record carries the band it actually timed
        fused_nelems = fused.get("nelems")
        if not isinstance(fused_nelems, int) or fused_nelems <= 0:
            fused_nelems = nelems
        if isinstance(sweep_f, Mapping):
            for cand, gbps in sweep_f.items():
                if isinstance(gbps, (int, float)) and gbps > 0:
                    record(
                        "fused", str(cand), float(gbps), dtype="float32",
                        ngroups=ngroups, nelems=fused_nelems, platform=plat,
                        source="seed",
                    )
                    count += 1
    return count


# ---------------------------------------------------------------------------
# in-call micro-sweeps ("first call measures candidates")
# ---------------------------------------------------------------------------


def _sweep_allowed() -> bool:
    with _LOCK:
        return (
            enabled()
            and not _AUTOTUNE_STATE.get("in_sweep")
            and _AUTOTUNE_STATE.get("sweeps", 0) < _SWEEP_BUDGET
        )


def _needs_sweep(family: str, dtype: Any, ngroups: int, nelems: int) -> bool:
    _ensure_loaded()  # a persisted measurement must pre-empt the re-sweep
    key = make_key(family, dtype=dtype, ngroups=ngroups, nelems=nelems)
    with _LOCK:
        if key in _AUTOTUNE_CACHE:
            return False
        # a nearby measured band within tolerance serves decisions just as
        # well — a fresh process must not re-sweep what lookup() would serve
        if lookup(family, dtype=dtype, ngroups=ngroups, nelems=nelems) is not None:
            return False
        # a failed sweep must not retry every call: the attempt is memoized
        attempted = _AUTOTUNE_STATE.setdefault("attempted", set())
        if key in attempted:
            return False
        attempted.add(key)
        return True


def _time_call(fn: Callable[[], Any], reps: int = 2) -> float:
    """Best-of-``reps`` wall seconds of ``fn()`` after one warm call (the
    warm call absorbs trace+compile)."""
    import time

    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = min(best, dt)
    return best


def _sweep(
    family: str,
    candidates: Iterable[str],
    runner: Callable[[str], Callable[[], Any] | None],
    nbytes: int,
    *,
    dtype: Any,
    ngroups: int,
    nelems: int,
) -> None:
    """Time each candidate's runner on a banded synthetic workload and
    record GB/s. One failing candidate drops out; a sweep that measures
    nothing still counts against the budget (the key is marked attempted
    by the caller)."""
    with _LOCK:
        _AUTOTUNE_STATE["in_sweep"] = True
        _AUTOTUNE_STATE["sweeps"] = _AUTOTUNE_STATE.get("sweeps", 0) + 1
    from . import telemetry

    telemetry.count("autotune.sweeps")
    def measure_one(cand: str) -> None:
        # a failing candidate drops out of the sweep without killing
        # dispatch; this is a one-shot measurement, not a retry loop, so
        # nothing here retries on the swallowed error
        try:
            fn = runner(cand)
            if fn is None:
                return
            seconds = _time_call(fn)
            if seconds > 0:
                record(
                    family, cand, nbytes / seconds / 1e9, dtype=dtype,
                    ngroups=ngroups, nelems=nelems, source="sweep",
                )
        except Exception as exc:  # noqa: BLE001 — a sweep must never kill dispatch
            logger.debug("autotune sweep %s[%s] failed: %s", family, cand, exc)

    try:
        for cand in candidates:
            measure_one(cand)
    finally:
        with _LOCK:
            _AUTOTUNE_STATE["in_sweep"] = False


def _sweep_segment_sum(dtype: Any, ngroups: int, nelems: int) -> None:
    import jax
    import numpy as np

    from .kernels import (
        _on_tpu,
        _pallas_runtime_ok,
        _segment_sum_impl,
        _use_matmul_path,
        generic_kernel,
    )
    from .options import set_options

    n = max(_SWEEP_ROWS, min(_SWEEP_N_MAX, nelems or _SWEEP_N_MAX))
    size = max(1, min(int(ngroups) or 1, n))
    rng = np.random.default_rng(0)
    data = rng.normal(size=(_SWEEP_ROWS, n)).astype(str(dtype), copy=False)
    codes = (np.arange(n, dtype=np.int32) % size).astype(np.int32)
    proxy = jax.ShapeDtypeStruct((n, _SWEEP_ROWS), data.dtype)

    candidates = ["scatter"]
    if _use_matmul_path("sum", proxy, size):
        candidates.append("matmul")
    if _on_tpu() and _pallas_runtime_ok():
        # interpret-mode pallas off-TPU is a debugging aid, never a winner
        with set_options(segment_sum_impl="pallas"):
            if _segment_sum_impl(proxy, size) == "pallas":
                candidates.append("pallas")

    def runner(impl: str) -> Callable[[], Any] | None:
        with set_options(segment_sum_impl=impl):
            if _segment_sum_impl(proxy, size) != impl:
                return None  # guards reroute: timing would mislabel scatter

        # ONE jitted callable per candidate: the impl choice happens at
        # trace time (inside the options context of the first call), and
        # the timed reps then reuse the compiled program — re-jitting per
        # call would time XLA compiles, not the lowering being compared
        jfn = jax.jit(lambda c, v: generic_kernel("nansum", c, v, size=size))

        def run() -> Any:
            with set_options(segment_sum_impl=impl):
                out = jfn(codes, data)
            return np.asarray(out)

        return run

    _sweep(
        "segment_sum", candidates, runner, data.nbytes,
        dtype=dtype, ngroups=ngroups, nelems=nelems,
    )


def _sweep_quantile(dtype: Any, ngroups: int, nelems: int) -> None:
    import jax
    import numpy as np

    from .kernels import generic_kernel
    from .options import set_options

    n = max(_SWEEP_ROWS, min(_SWEEP_N_MAX, nelems or _SWEEP_N_MAX))
    size = max(1, min(int(ngroups) or 1, n))
    rng = np.random.default_rng(0)
    data = rng.normal(size=(_SWEEP_ROWS, n)).astype(str(dtype), copy=False)
    codes = (np.arange(n, dtype=np.int32) % size).astype(np.int32)

    def runner(impl: str) -> Callable[[], Any]:
        # one jitted callable per candidate (see the segment-sum sweep)
        jfn = jax.jit(
            lambda c, v: generic_kernel("nanquantile", c, v, size=size, q=0.5)
        )

        def run() -> Any:
            with set_options(quantile_impl=impl):
                out = jfn(codes, data)
            return np.asarray(out)

        return run

    _sweep(
        "quantile", ("sort", "select"), runner, data.nbytes,
        dtype=dtype, ngroups=ngroups, nelems=nelems,
    )


def _sweep_engine(dtype: Any, nelems: int) -> None:
    import numpy as np

    from .aggregations import generic_aggregate

    n = max(16, min(_SWEEP_ENGINE_N_MAX, nelems or _SWEEP_ENGINE_N_MAX))
    rng = np.random.default_rng(0)
    data = rng.normal(size=n).astype(str(dtype), copy=False)
    size = 16
    codes = (np.arange(n, dtype=np.int64) % size)

    def runner(engine: str) -> Callable[[], Any]:
        def run() -> Any:
            out = generic_aggregate(
                codes, data, engine=engine, func="nansum", size=size, fill_value=0
            )
            return np.asarray(out)

        return run

    # record under the size actually timed (n, not the caller's nelems):
    # the workload is capped, and filing a small-array winner under a
    # large-array band would route big hosts arrays to the numpy engine
    # against the measured crossover
    _sweep(
        "engine", ("numpy", "jax"), runner, data.nbytes,
        dtype=dtype, ngroups=0, nelems=n,
    )


#: highcard-sweep workload caps: the dense-vs-sort crossover is governed by
#: the label-universe size and the present density, so the sweep keeps the
#: caller's density at a capped universe — a 1M-group dense accumulator is
#: only ~8 MB host-side, cheap enough to time honestly
_SWEEP_HIGHCARD_SIZE_MAX = 1 << 20
_SWEEP_HIGHCARD_N_MAX = 1 << 16


def _sweep_highcard(dtype: Any, ngroups: int, n_present: int, nelems: int) -> None:
    """Time the dense jax engine against the sort (present-groups) engine
    on a synthetic workload with the caller's universe size and present
    density (both capped), feeding the "highcard" family the eager
    dense-vs-sort routing consults."""
    import numpy as np

    from .aggregations import generic_aggregate

    n = max(16, min(_SWEEP_HIGHCARD_N_MAX, nelems or _SWEEP_HIGHCARD_N_MAX))
    size = max(2, min(int(ngroups) or 2, _SWEEP_HIGHCARD_SIZE_MAX))
    frac = min(1.0, max(1, int(n_present)) / max(1, int(ngroups)))
    p = max(1, min(int(frac * size), size, n))
    rng = np.random.default_rng(0)
    data = rng.normal(size=n).astype(str(dtype), copy=False)
    present_ids = rng.choice(size, p, replace=False).astype(np.int64)
    codes = present_ids[rng.integers(0, p, n)]

    def runner(engine: str) -> Callable[[], Any]:
        eng = "jax" if engine == "dense" else "sort"

        def run() -> Any:
            out = generic_aggregate(
                codes, data, engine=eng, func="nansum", size=size, fill_value=0
            )
            return np.asarray(out)

        return run

    # record under the universe/elements actually timed (size/n, not the
    # caller's bands): the workload is capped, and a winner measured at the
    # cap must not masquerade as a measurement of a 100x larger universe
    _sweep(
        "highcard", ("dense", "sort"), runner, data.nbytes,
        dtype=dtype, ngroups=size, nelems=n,
    )


def prime_highcard(dtype: Any, ngroups: int, n_present: int, nelems: int) -> None:
    """Highcard-family analogue of :func:`prime_engine`: one budgeted
    dense-vs-sort sweep per banded key, before the routing decision that
    wants to consult it. A no-op unless the tuner is on."""
    if not _sweep_allowed():
        return
    dt = str(dtype)
    if dt not in ("float32", "float64"):
        return
    swept_size = max(2, min(int(ngroups) or 2, _SWEEP_HIGHCARD_SIZE_MAX))
    swept_n = max(16, min(_SWEEP_HIGHCARD_N_MAX, nelems or _SWEEP_HIGHCARD_N_MAX))
    tolerance = _NEAREST_TOLERANCE.get("highcard", _NEAREST_TOLERANCE_DEFAULT)
    if (
        abs(_gband(ngroups) - _gband(swept_size)) > tolerance
        or abs(_eband(nelems) - _eband(swept_n)) > tolerance
    ):
        # the capped sweep could not serve this band anyway (records land
        # under the swept sizes); don't burn budget on it
        return
    try:
        if _needs_sweep("highcard", dt, swept_size, swept_n):
            _sweep_highcard(dt, ngroups, n_present, nelems)
    except Exception as exc:  # noqa: BLE001 — priming must never kill a reduction
        logger.debug("autotune: prime_highcard(%s) failed: %s", dt, exc)


#: reduction families whose chunk kernels ride the additive segment-sum
#: lowering — the ones a segment_sum sweep informs
_ADDITIVE_FAMILIES = frozenset(
    {"sum", "nansum", "mean", "nanmean", "var", "nanvar", "std", "nanstd",
     "count", "len", "nanlen", "any", "all"}
)
_QUANTILE_FAMILIES = frozenset(
    {"quantile", "nanquantile", "median", "nanmedian", "mode", "nanmode"}
)


def prime_reduce(func_name: str, dtype: Any, ngroups: int, nelems: int) -> None:
    """Pre-dispatch hook (non-traced, host side): run the micro-sweeps a
    coming jax-engine reduction will want to consult, once per banded key
    and within the per-process sweep budget. A no-op unless the tuner is
    on."""
    if not _sweep_allowed():
        return
    dt = str(dtype)
    # sweeps synthesize normal floats: other dtypes would burn budget on
    # degenerate workloads whose winner mislabels the real one
    if dt not in ("float32", "float64", "bfloat16"):
        return
    try:
        if func_name in _ADDITIVE_FAMILIES:
            if _needs_sweep("segment_sum", dt, ngroups, nelems):
                _sweep_segment_sum(dt, ngroups, nelems)
        if func_name in _QUANTILE_FAMILIES and _sweep_allowed():
            if _needs_sweep("quantile", dt, ngroups, nelems):
                _sweep_quantile(dt, ngroups, nelems)
    except Exception as exc:  # noqa: BLE001 — priming must never kill a reduction
        logger.debug("autotune: prime_reduce(%s, %s) failed: %s", func_name, dt, exc)


def prime_engine(dtype: Any, nelems: int) -> None:
    """Engine-choice analogue of :func:`prime_reduce` (host arrays only).

    Calls whose element band sits beyond the engine tolerance from the
    capped sweep workload skip the sweep: the measurement could not serve
    them (records land under the swept size), and for arrays that large
    the jax heuristic is already the measured answer."""
    if not _sweep_allowed():
        return
    dt = str(dtype)
    if dt not in ("float32", "float64"):
        return
    swept = max(16, min(_SWEEP_ENGINE_N_MAX, nelems or _SWEEP_ENGINE_N_MAX))
    tolerance = _NEAREST_TOLERANCE.get("engine", _NEAREST_TOLERANCE_DEFAULT)
    if abs(_eband(nelems or swept) - _eband(swept)) > tolerance:
        return
    try:
        if _needs_sweep("engine", dt, 0, nelems):
            _sweep_engine(dt, nelems)
    except Exception as exc:  # noqa: BLE001 — priming must never kill a reduction
        logger.debug("autotune: prime_engine(%s) failed: %s", dt, exc)


# ---------------------------------------------------------------------------
# streaming observations + decisions
# ---------------------------------------------------------------------------


def _bytes_band_candidate(nbytes: int) -> str:
    """Slab sizes are recorded as power-of-two byte candidates ("2^28")."""
    return f"2^{max(0, int(nbytes).bit_length() - 1)}"


def observe_stream(report: Any, *, nbytes: int, nelems: int = 0) -> None:
    """Fold one finished :class:`~flox_tpu.profiling.StreamReport` into the
    store: throughput per prefetch depth and per slab-bytes band, with the
    overlap fraction attached. Record-only safe — runs in every mode."""
    try:
        wall_s = float(report.wall_ms) / 1e3
        if wall_s <= 0 or nbytes <= 0 or not report.slabs:
            return
        gbps = nbytes / wall_s / 1e9
        record(
            "stream_prefetch", str(int(report.prefetch)), gbps,
            nelems=nelems, source="stream",
        )
        slab0 = report.slabs[0]
        slab_bytes = int(nbytes * (slab0.stop - slab0.start) / max(1, _report_span(report)))
        record(
            "stream_slab", _bytes_band_candidate(slab_bytes), gbps,
            nelems=nelems, source="stream",
        )
        from . import telemetry

        if telemetry.enabled():
            telemetry.METRICS.observe("stream.overlap_fraction", report.overlap_fraction)
    except Exception as exc:  # noqa: BLE001 — observation must never break a stream
        logger.debug("autotune: stream observation failed: %s", exc)


def _report_span(report: Any) -> int:
    return sum(int(s.stop) - int(s.start) for s in report.slabs)


def pick_stream_prefetch(default_depth: int, *, nelems: int = 0) -> int:
    """The observed-best prefetch depth for the band (tuner on, record
    known), else ``default_depth``. Prefetch changes only when staging
    happens — never the staged bytes — so adapting it is always
    bit-identical."""
    choice = decide(
        "stream_prefetch", str(int(default_depth)),
        [str(d) for d in (0, 1, 2, 4, 8, 16, 32, 64)], nelems=nelems,
    )
    try:
        return int(choice)
    except ValueError:
        return int(default_depth)


def pick_stream_batch_bytes(default_bytes: int, *, nelems: int = 0) -> int:
    """The observed-best slab byte budget for the band, else the default."""
    fallback = _bytes_band_candidate(default_bytes)
    choice = decide(
        "stream_slab", fallback,
        [f"2^{p}" for p in range(16, 34)], nelems=nelems,
    )
    try:
        power = int(choice.split("^")[1])
    except (IndexError, ValueError):
        return int(default_bytes)
    return 2**power if choice != fallback else int(default_bytes)


# ---------------------------------------------------------------------------
# regression sentinel
# ---------------------------------------------------------------------------


def _history_rounds(history_path: str) -> list[dict]:
    try:
        with open(history_path) as f:
            lines = [line for line in f.read().splitlines() if line.strip()]
    except OSError:
        return []
    rounds = []
    for line in lines:
        try:
            payload = json.loads(line)
        except ValueError:
            continue
        if isinstance(payload, dict):
            rounds.append(payload)
    return rounds


def _last_history_round(
    history_path: str,
    *,
    platform: str | None = None,
    workload: Mapping[str, Any] | None = None,
    skip_rounds: int = 0,
) -> dict | None:
    """The newest round (optionally platform- and workload-matched,
    optionally skipping the last ``skip_rounds`` entries — the CLI compares
    the final round against the one before it). When ``workload`` is given,
    only rounds recording the same shape qualify: GB/s at a CI-smoke shape
    is overhead-dominated and must never read as "a regression" against a
    full-scale round."""
    rounds = _history_rounds(history_path)
    if skip_rounds:
        rounds = rounds[:-skip_rounds] if len(rounds) > skip_rounds else []
    for payload in reversed(rounds):
        if platform is not None and payload.get("platform") != platform:
            continue
        if workload is not None and payload.get("workload") != dict(workload):
            continue
        return payload
    return None


def _history_families(payload: Mapping[str, Any]) -> dict[str, float]:
    """Flatten one bench round into per-family GB/s."""
    out: dict[str, float] = {}
    value = payload.get("value")
    if isinstance(value, (int, float)) and value > 0:
        out["headline"] = float(value)
    for field, prefix in (("impl_sweep_gbps", "segment_sum"), ("quantile_gbps", "quantile")):
        sweep = payload.get(field)
        if isinstance(sweep, Mapping):
            for impl, gbps in sweep.items():
                if isinstance(gbps, (int, float)) and gbps > 0:
                    out[f"{prefix}[{impl}]"] = float(gbps)
    streaming = payload.get("streaming")
    if isinstance(streaming, Mapping):
        for name in ("gbps_sync", "gbps_prefetch"):
            gbps = streaming.get(name)
            if isinstance(gbps, (int, float)) and gbps > 0:
                out[f"streaming[{name.split('_', 1)[1]}]"] = float(gbps)
    return out


def compare_families(
    current: Mapping[str, float],
    previous: Mapping[str, float],
    *,
    threshold: float = _REGRESSION_THRESHOLD,
) -> tuple[dict[str, dict], list[str]]:
    """The verdict core shared by :func:`regression_sentinel` and
    ``benchmarks.sentinel_row``: per-family current-vs-previous rows plus
    the names that dropped below ``(1 - threshold) x previous``."""
    families: dict[str, dict] = {}
    regressed: list[str] = []
    for name, gbps in sorted(current.items()):
        if not (isinstance(gbps, (int, float)) and gbps > 0):
            continue
        prev = previous.get(name)
        row: dict[str, Any] = {"current": round(float(gbps), 3)}
        if isinstance(prev, (int, float)) and prev > 0:
            row["previous"] = round(float(prev), 3)
            row["ratio"] = round(float(gbps) / prev, 3)
            row["regressed"] = float(gbps) < prev * (1.0 - threshold)
            if row["regressed"]:
                regressed.append(name)
        else:
            row["previous"] = None
            row["regressed"] = False
        families[name] = row
    return families, regressed


def regression_sentinel(
    current: Mapping[str, float],
    *,
    history_path: str | None = None,
    threshold: float = _REGRESSION_THRESHOLD,
    platform: str | None = None,
    workload: Mapping[str, Any] | None = None,
    skip_rounds: int = 0,
) -> dict:
    """Diff a round's per-family GB/s against the last ``BENCH_HISTORY``
    round (same platform only — a CPU-fallback round must not be "a
    regression" against an on-chip one; same recorded workload when
    ``workload`` is given — a sub-scale smoke must not be "a regression"
    against a full-size round) and the store's best-known values.
    Returns a report-only verdict dict; the caller decides whether any
    ``regressed`` family fails anything (CI runs it report-only).
    ``skip_rounds`` ignores the newest N history entries — the CLI's
    compare-the-final-round-against-its-predecessor mode."""
    plat = _platform() if platform is None else platform
    history_path = (
        os.path.join(_repo_root(), "BENCH_HISTORY", "bench_runs.jsonl")
        if history_path is None
        else history_path
    )
    prev_round = _last_history_round(
        history_path, platform=plat, workload=workload, skip_rounds=skip_rounds
    )
    previous = {} if prev_round is None else _history_families(prev_round)
    families, regressed = compare_families(current, previous, threshold=threshold)
    return {
        "status": "regression" if regressed else "ok",
        "platform": plat,
        "threshold": threshold,
        "compared_against": history_path if previous else None,
        "regressed": regressed,
        "families": families,
    }


# ---------------------------------------------------------------------------
# CLI: python -m flox_tpu.autotune {report, sentinel}
# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m flox_tpu.autotune",
        description="Inspect the flox_tpu autotune store / run the regression sentinel.",
    )
    sub = parser.add_subparsers(dest="command", required=True)
    rep = sub.add_parser("report", help="print the store's winners and counters")
    rep.add_argument("--path", default=None, help="store file (default: the configured path)")
    sen = sub.add_parser(
        "sentinel", help="diff per-family GB/s against the last bench round (report-only)"
    )
    sen.add_argument(
        "--current", default=None,
        help="JSON object of {family: gbps}; default: the last BENCH_HISTORY round itself",
    )
    sen.add_argument("--history", default=None, help="bench_runs.jsonl path")
    sen.add_argument("--platform", default=None, help="platform tag to compare within")
    args = parser.parse_args(argv)

    if args.command == "report":
        if args.path:
            load(args.path)
        print(json.dumps(decision_record(), indent=1))
        return 0

    history = args.history or os.path.join(
        _repo_root(), "BENCH_HISTORY", "bench_runs.jsonl"
    )
    skip_rounds = 0
    plat = args.platform
    workload = None
    if args.current:
        try:
            current = json.loads(args.current)
        except ValueError as exc:
            parser.error(f"--current is not valid JSON: {exc}")
    else:
        latest = _last_history_round(history)
        if latest is None:
            parser.error(f"no readable rounds in {history}")
        # the final round IS the current measurement: compare it against
        # the round before it, within its own platform and (when the round
        # recorded one) its own workload shape
        current = _history_families(latest)
        plat = plat or latest.get("platform")
        workload = latest.get("workload")
        skip_rounds = 1
    verdict = regression_sentinel(
        current, history_path=history, platform=plat, workload=workload,
        skip_rounds=skip_rounds,
    )
    print(json.dumps(verdict, indent=1))
    # report-only: regressions are a verdict in the JSON, never an exit code
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
