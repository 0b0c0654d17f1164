"""AOT program persistence: compile once, restart warm.

A serving replica's worst request is its first — absent persistence, every
program it serves pays a fresh XLA compile (seconds of wall for a mesh
program) exactly when the replica joins the fleet. Two layers remove that
stall, both rooted at ``OPTIONS["serve_aot_dir"]``:

* **persistent compilation cache** — :func:`configure` points JAX's
  on-disk executable cache (``jax_compilation_cache_dir``) at the AOT
  directory, with the entry-size/compile-time floors lowered so every
  program qualifies (the defaults skip small/fast programs, which on CPU
  test rigs is all of them). Backend compiles — ``jit`` internally runs the
  same ``lower().compile()`` AOT path — are then written through to disk,
  and a restarted process's compiles become cache *retrievals*. The
  telemetry listener nets those retrievals out of ``jax.compiles``, so the
  acceptance counter reads 0 for a warmed program.
* **warmup manifest** — the executable cache is keyed by XLA program hash,
  which a fresh process can only reproduce by *lowering* the same programs
  again, and lowering only happens when a request arrives. The manifest
  (``manifest.json`` in the AOT dir) closes that gap: every dispatch
  records its request spec (:func:`record_reduce` — func, shapes, dtypes,
  group count, option overlay), and :func:`warmup` replays the specs
  against synthetic payloads at startup. Tracing is cheap and host-side;
  the compile lands as a disk hit; the first real request finds a live
  program.

The in-memory manifest memo (:data:`_MANIFEST_MEMO`) is registered in
``cache.clear_all`` / ``cache.stats`` (floxlint FLX008). Persistence is
atomic merge-on-save (tmp + rename, same discipline as the autotune
store): concurrent replicas sharing one AOT dir union their manifests
instead of clobbering each other.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from pathlib import Path
from typing import Any

import numpy as np

# options resolved as a module attribute, never from-bound: tests reload
# flox_tpu.options, and a from-import would read the pre-reload dict while
# set_options writes to the post-reload one
from .. import options, telemetry

logger = logging.getLogger(__name__)

__all__ = ["configure", "deconfigure", "record_reduce", "save_manifest", "warmup"]

_MANIFEST_VERSION = 1
_MANIFEST_NAME = "manifest.json"

#: warmup manifest memo: spec digest -> replayable request spec. Mirrors
#: the on-disk manifest (union of every load + this process's dispatches);
#: registered in cache.clear_all (FLX008) — a clear resets to "never
#: loaded", and the next record/warmup re-reads the disk state.
_MANIFEST_MEMO: dict[str, dict] = {}

# configuration is process-global (jax's cache dir is), so remember what we
# already pointed jax at: re-configuring with the same dir is a no-op,
# switching dirs mid-process is allowed but logged (tests do it; prod won't)
_STATE: dict[str, Any] = {"configured": None, "loaded": None}
_LOCK = threading.Lock()


def _aot_dir(path: Any = None) -> Path | None:
    root = path if path is not None else options.OPTIONS["serve_aot_dir"]
    return Path(root) if root else None


def configure(path: Any = None) -> Path | None:
    """Point JAX's persistent compilation cache at the AOT directory.

    ``path`` defaults to ``OPTIONS["serve_aot_dir"]``; ``None`` there means
    persistence is off and this is a no-op returning ``None``. A
    ``JAX_COMPILATION_CACHE_DIR`` in the environment keeps the executables
    (``device.compile_cache``); the manifest stays in the AOT directory.
    Idempotent per directory; safe to call before every dispatch (the
    dispatcher does).
    """
    root = _aot_dir(path)
    if root is None:
        return None
    with _LOCK:
        if _STATE["configured"] == str(root):
            return root
        root.mkdir(parents=True, exist_ok=True)
        import jax

        from ..device import compile_cache

        compile_cache(root)
        # the default floors skip programs that compile fast or lower
        # small — on a CPU test rig that is every program, and on TPU a
        # skipped "fast" compile is still a first-request stall. Persist
        # everything; the dir is bounded by what the replica serves.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        if _STATE["configured"] is not None:
            logger.info("AOT cache dir moved %s -> %s", _STATE["configured"], root)
        _STATE["configured"] = str(root)
    return root


def deconfigure() -> None:
    """Detach JAX's persistent compilation cache (the config is
    process-global; tests detach between cases so later compiles stop
    writing through to a dead tmp dir). The manifest memo is untouched —
    ``cache.clear_all`` owns that."""
    with _LOCK:
        if _STATE["configured"] is None:
            return
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            import jax

            jax.config.update("jax_compilation_cache_dir", None)
        _STATE["configured"] = None


def _jsonable(value: Any) -> Any:
    """``value`` rendered JSON-serializable, or raise TypeError: ndarrays
    become lists, numpy scalars become items — anything else non-JSON
    (callables, custom Aggregations) disqualifies the spec from the
    manifest (it cannot be replayed from text)."""
    if isinstance(value, np.ndarray):
        return value.tolist()
    if isinstance(value, np.generic):
        return value.item()
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if isinstance(value, type):  # dtype classes like np.float64
        return np.dtype(value).name
    raise TypeError(f"not manifest-serializable: {value!r}")


def record_reduce(
    *,
    func: Any,
    shape: tuple,
    dtype: str,
    by_shape: tuple,
    by_dtype: str,
    ngroups: int,
    agg_kwargs: dict,
    options: dict,
    dataset: str | None = None,
) -> bool:
    """Record one served program's request spec into the warmup manifest.

    Called by the dispatcher after every device dispatch. Returns whether
    the spec was recorded: ``False`` when persistence is off, when the spec
    cannot be replayed from JSON (custom Aggregation objects, callable
    kwargs), or when it is already in the manifest. A *new* spec persists
    the manifest immediately (merge-on-save), so a replica killed mid-run
    still leaves every program it served warmable.

    ``dataset`` stamps registry-referenced dispatches for the operator
    reading the manifest; it is EXCLUDED from the spec digest — program
    identity is shapes/dtypes/ngroups, never residency, so the inline
    warmup replay warms the very program a registry hit runs.
    """
    multi = isinstance(func, (tuple, list)) and all(
        isinstance(f, str) for f in func
    )
    if _aot_dir() is None or not (isinstance(func, str) or multi):
        return False
    try:
        spec = _jsonable(
            {
                "func": list(func) if multi else func,
                "shape": list(shape),
                "dtype": str(dtype),
                "by_shape": list(by_shape),
                "by_dtype": str(by_dtype),
                "ngroups": int(ngroups),
                "agg_kwargs": {k: v for k, v in agg_kwargs.items() if v is not None},
                "options": options,
            }
        )
    except TypeError:
        return False
    digest = spec_digest(spec)
    if dataset is not None:
        # informational only (excluded from the digest above): the replay
        # path ignores it, dedup stays residency-blind
        spec = {**spec, "dataset": str(dataset)}
    with _LOCK:
        if digest in _MANIFEST_MEMO:
            return False
        _MANIFEST_MEMO[digest] = spec
    telemetry.count("serve.aot_recorded")
    save_manifest()
    return True


def spec_digest(spec: dict) -> str:
    """Stable identity of a manifest spec (canonical-JSON blake2b)."""
    import hashlib

    payload = json.dumps(spec, sort_keys=True, separators=(",", ":"))
    return hashlib.blake2b(payload.encode(), digest_size=16).hexdigest()


def _manifest_path(path: Any = None) -> Path | None:
    root = _aot_dir(path)
    return root / _MANIFEST_NAME if root is not None else None


def _load_into_memo(path: Any = None) -> None:
    """Union the on-disk manifest into the memo (corrupt/alien files warn
    and are ignored — a broken manifest must never take serving down)."""
    mpath = _manifest_path(path)
    if mpath is None or not mpath.exists():
        return
    try:
        payload = json.loads(mpath.read_text())
        if payload.get("version") != _MANIFEST_VERSION:
            raise ValueError(f"manifest version {payload.get('version')!r}")
        entries = payload["programs"]
        assert isinstance(entries, dict)
    except Exception as exc:  # noqa: BLE001 — fall back to what we have
        telemetry.record_serve_error(exc, what="aot.load-manifest")
        logger.warning("ignoring unreadable AOT manifest %s: %s", mpath, exc)
        return
    with _LOCK:
        for digest, spec in entries.items():
            _MANIFEST_MEMO.setdefault(digest, spec)


def save_manifest(path: Any = None) -> Path | None:
    """Persist the manifest memo, merged with whatever is on disk.

    Atomic tmp+rename so readers never see a torn file; merge-on-save so
    two replicas sharing the dir union their programs. Returns the path
    written, or ``None`` when persistence is off."""
    mpath = _manifest_path(path)
    if mpath is None:
        return None
    _load_into_memo(path)
    with _LOCK:
        payload = {"version": _MANIFEST_VERSION, "programs": dict(_MANIFEST_MEMO)}
    mpath.parent.mkdir(parents=True, exist_ok=True)
    tmp = mpath.with_name(mpath.name + f".tmp.{os.getpid()}")
    tmp.write_text(json.dumps(payload, sort_keys=True, indent=1))
    tmp.replace(mpath)
    return mpath


def _synthesize(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """Payload + labels with the spec's compiled-program identity.

    Program identity is shapes/dtypes/group-count, never data: zeros for
    the payload, and labels cycling through exactly ``ngroups`` distinct
    values so factorization finds the recorded group count (which fixes
    the output shape the program was compiled for)."""
    arr = np.zeros(tuple(spec["shape"]), dtype=np.dtype(spec["dtype"]))
    nby = int(np.prod(spec["by_shape"])) if spec["by_shape"] else 1
    ngroups = max(1, int(spec["ngroups"]))
    labels = np.arange(nby) % ngroups
    try:
        labels = labels.astype(spec["by_dtype"])
    except (TypeError, ValueError):
        pass  # exotic label dtype: int labels trace the same program
    return arr, labels.reshape(tuple(spec["by_shape"]))


def warmup(path: Any = None) -> int:
    """Replay every manifest spec so the first real request finds a live,
    disk-warmed program.

    Configures the persistent cache, loads the manifest, and runs each
    recorded spec against synthetic payloads under its recorded option
    scope. Compiles triggered here are served from the persistent cache
    when the dir is warm (``jax.compiles`` stays 0 net of retrievals — the
    acceptance counter) and are written through when it is not (first boot
    populates the dir for the fleet). Returns the number of specs warmed;
    a spec that fails to replay is logged and skipped — warmup must never
    take serving down.
    """
    if configure(path) is None:
        return 0
    # bootstrap the compile listener BEFORE the first replay, so warmup
    # compiles are counted (and netted against cache retrievals) rather
    # than silently missed — the zero-compile assertion is only meaningful
    # if counting was live while the compiles could have happened
    with telemetry.span("serve.warmup"):
        _load_into_memo(path)
        with _LOCK:
            specs = list(_MANIFEST_MEMO.values())
        from ..core import groupby_reduce

        # captured ONCE: telemetry toggled on mid-warmup must not make the
        # post-replay block read baselines that were never taken
        tm_on = telemetry.enabled()
        if tm_on:
            compiles0 = telemetry.METRICS.get("jax.compiles")
            compile_ms0 = telemetry.METRICS.get("jax.compile_ms")
            # costmodel card analysis running inside the replays is
            # bookkeeping — netted out of the warmup row's device wall
            analysis0 = telemetry.METRICS.get("costmodel.card_analysis_ms")
            t_warm0 = time.perf_counter()
        warmed = 0
        from ..costmodel import serve_alias

        for spec in specs:
            try:
                arr, labels = _synthesize(spec)
                kwargs = dict(spec.get("agg_kwargs") or {})
                # cards recorded during the replay also index under the
                # warmup ledger label — the replica's standing program set
                # is card-covered BEFORE the first real request arrives
                with serve_alias("serve.warmup"), options.scoped(
                    **(spec.get("options") or {})
                ):
                    if isinstance(spec["func"], list):
                        # multi-statistic spec: warm the fused program
                        from ..fusion import groupby_aggregate_many

                        groupby_aggregate_many(
                            arr, labels, funcs=tuple(spec["func"]), **kwargs
                        )
                    else:
                        groupby_reduce(arr, labels, func=spec["func"], **kwargs)
                warmed += 1
            # noqa: FLX006 — not a retry loop: specs are independent, and a
            # bad one must be skipped (warmup can never take serving down)
            except Exception as exc:  # noqa: FLX006
                telemetry.record_serve_error(exc, what="aot.warmup-spec")
                logger.warning("AOT warmup skipped %s: %s", spec.get("func"), exc)
        telemetry.count("serve.aot_warmed", warmed)
        # warmup just materialized every program the replica will serve:
        # its HBM mark is the replica's standing footprint before traffic
        telemetry.sample_hbm(program="serve.warmup")
        if tm_on:
            # warmup's ledger row: the replica's startup cost in one place
            # (a warm AOT dir reads compiles == 0 here — the acceptance
            # criterion — a cold one shows exactly what the fleet paid)
            telemetry.observe_cost(
                "serve.warmup",
                dispatches=warmed,
                device_ms=max(
                    0.0,
                    (time.perf_counter() - t_warm0) * 1e3
                    - (
                        telemetry.METRICS.get("costmodel.card_analysis_ms")
                        - analysis0
                    ),
                ),
                compiles=int(telemetry.METRICS.get("jax.compiles") - compiles0),
                compile_ms=telemetry.METRICS.get("jax.compile_ms") - compile_ms0,
            )
    return warmed
