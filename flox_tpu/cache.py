"""Host-side memoization (parity: /root/reference/flox/cache.py:3-12).

The reference memoizes chunk-boundary analysis with a cachey cache keyed by
dask tokens, and exposes the cache object so callers (its asv benchmarks,
debugging sessions) can clear it between runs. Here the cached inputs are
hashable tuples (label fingerprints, shard counts), so plain LRUs and
dicts suffice; ``memoize`` keeps the reference's decorator name and
``clear_all`` is the analogue of ``flox.cache.cache.clear()``.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Any, Iterator

memoize = functools.lru_cache(maxsize=512)


class LRUCache:
    """A bounded dict with least-recently-used eviction, for the compiled
    program caches.

    The mesh ``_PROGRAM_CACHE`` and streaming ``_STEP_CACHE`` used to
    wholesale ``.clear()`` past 256 entries — under sustained mixed traffic
    that evicts every HOT compiled program the moment one cold key tips the
    bound, and the next request for each recompiles from scratch (seconds
    of XLA wall per program). LRU keeps the hot set: a ``get`` hit renews
    the entry, inserts evict only the single stalest key, and the eviction
    count is visible in :func:`stats` so a serving process can alarm on
    thrash instead of discovering it as tail latency.

    The mapping surface mirrors what callers already used on the plain
    dicts (``get`` / ``[]=`` / ``len`` / ``clear`` / ``items`` / ``in``);
    a lock keeps renew-on-read safe under the serving dispatcher's
    executor threads.
    """

    def __init__(self, maxsize: int = 256) -> None:
        if maxsize < 1:
            raise ValueError(f"LRUCache maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self.evictions = 0
        self._data: OrderedDict = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key: Any, default: Any = None) -> Any:
        with self._lock:
            try:
                self._data.move_to_end(key)
            except KeyError:
                return default
            return self._data[key]

    def __getitem__(self, key: Any) -> Any:
        with self._lock:
            self._data.move_to_end(key)
            return self._data[key]

    def __setitem__(self, key: Any, value: Any) -> None:
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            while len(self._data) > self.maxsize:
                self._data.popitem(last=False)
                self.evictions += 1

    def __contains__(self, key: Any) -> bool:
        with self._lock:
            return key in self._data

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def __iter__(self) -> Iterator:
        with self._lock:
            return iter(list(self._data))

    def keys(self) -> list:
        with self._lock:
            return list(self._data.keys())

    def values(self) -> list:
        with self._lock:
            return list(self._data.values())

    def items(self) -> list:
        with self._lock:
            return list(self._data.items())

    def pop(self, key: Any, *default: Any) -> Any:
        with self._lock:
            return self._data.pop(key, *default)

    def clear(self) -> None:
        """Drop every entry (eviction counter intact: it counts capacity
        evictions, not deliberate clears)."""
        with self._lock:
            self._data.clear()


def stats() -> dict:
    """Current entry counts of every named host-side cache (plus the
    kernel-bundle LRU's hit/miss counters) — the cache panel of the
    telemetry layer (``telemetry.profile_call`` embeds this, and a bench
    row showing ``bundle_lru.misses`` climbing across same-shaped calls is
    a retrace storm caught red-handed)."""
    from .autotune import _AUTOTUNE_CACHE
    from .cohorts import _COHORTS_CACHE
    from .core import _jitted_bundle
    from .costmodel import _CARD_REGISTRY
    from .factorize import _FACTORIZE_CACHE
    from .fusion import _FUSED_PROGRAM_CACHE
    from .kernels import _PRESENT_CACHE
    from .parallel.mapreduce import _PROGRAM_CACHE
    from .parallel.scan import _SCAN_CACHE
    from .profiling import capture_active
    from .serve.aot import _MANIFEST_MEMO
    from .serve.breaker import breaker_stats
    from .serve.dispatcher import _BATCH_REGISTRY, _COALESCE_CACHE, _PENDING_REGISTRY
    from .serve.registry import registry_stats
    from .serve.stores import stores_stats
    from .slo import slo_stats
    from .streaming import _STEP_CACHE
    from .telemetry import (
        FLIGHT_RECORDER,
        cost_by_dataset,
        cost_by_program,
        cost_by_tenant,
        hbm_by_program,
    )

    info = _jitted_bundle.cache_info()
    return {
        # per-program-key cost ledger (telemetry.observe_cost): dispatches /
        # device_ms / bytes / compiles / hbm peak / last slow trace per
        # compiled-program key, plus the per-tenant axis the serve layer
        # feeds — read through the locked accessors, never the raw table
        "cost_by_program": cost_by_program(),
        "cost_by_tenant": cost_by_tenant(),
        # per-resident-dataset axis of the same ledger: fed only by serve
        # dispatches that referenced a registry entry ("dataset": name)
        "cost_by_dataset": cost_by_dataset(),
        # per-program-key peak HBM: the hbm_peak column of the ledger, kept
        # as its own view (the operator's answer to "which compiled program
        # is eating the chip")
        "hbm_by_program": hbm_by_program(),
        # compiled-program card registry (flox_tpu/costmodel.py): one card
        # per (program label, input signature) holding the analytical
        # flops/bytes/footprint the roofline join divides by
        "costmodel_cards": len(_CARD_REGISTRY),
        "flight_recorder": len(FLIGHT_RECORDER),
        # the on-demand capture guard: whether a jax.profiler capture is
        # running right now (profiling.start_capture / /debug/profile)
        "profile_capture_active": capture_active() is not None,
        "cohorts": len(_COHORTS_CACHE),
        "factorize": len(_FACTORIZE_CACHE),
        # present-group tables of the sort engine (kernels.present_groups):
        # one sorted-unique table per distinct code-content fingerprint
        "present_tables": len(_PRESENT_CACHE),
        "mesh_programs": len(_PROGRAM_CACHE),
        "scan_programs": len(_SCAN_CACHE),
        "stream_steps": len(_STEP_CACHE),
        "fused_programs": len(_FUSED_PROGRAM_CACHE),
        "autotune": len(_AUTOTUNE_CACHE),
        # capacity evictions of the compiled-program LRUs: a serving
        # process alarms on these climbing (program-cache thrash shows up
        # here first, as recompiles second, as tail latency last)
        "evictions": {
            "mesh_programs": _PROGRAM_CACHE.evictions,
            "stream_steps": _STEP_CACHE.evictions,
            "fused_programs": _FUSED_PROGRAM_CACHE.evictions,
        },
        # serving layer: queued/in-flight requests, open coalescing
        # entries + micro-batches, and AOT programs pending manifest save
        "serve_pending": len(_PENDING_REGISTRY),
        "serve_coalesce": len(_COALESCE_CACHE),
        "serve_batches": len(_BATCH_REGISTRY),
        "serve_aot_manifest": len(_MANIFEST_MEMO),
        # resident dataset registry: entry/byte/pin counts, the HBM budget
        # in force, and deliberate budget evictions (the runbook alarm)
        "registry": registry_stats(),
        # durable aggregation stores: open-store count, per-store
        # generations, host-carry bytes, device-cache occupancy
        "stores": stores_stats(),
        # per-program circuit breakers: entry counts per state plus the
        # open/half-open detail (which program labels are being fast-failed
        # and how long their cooldowns have left)
        "serve_breakers": breaker_stats(),
        # SLO plane: spec in force, window-snapshot depth, alert counts per
        # state, canary probe/failure totals — a snapshot, never a fresh
        # evaluation (stats must not move the alert state machine)
        "slo": slo_stats(),
        "bundle_lru": {
            "size": info.currsize, "hits": info.hits, "misses": info.misses
        },
    }


def clear_all() -> None:
    """Drop every host-side cache: cohort-detection memos, compiled mesh
    program/scan caches, and the jitted kernel-bundle LRU — and reset the
    telemetry metrics registry, whose cache-hit/miss and compile counters
    describe exactly the state being dropped (a benchmark that clears
    between timing rounds must not carry stale counts across them). The
    analogue of the reference's ``flox.cache.cache.clear()`` (its asv
    benchmarks clear between timing rounds; ``benchmarks.py`` here does the
    same)."""
    from .autotune import _AUTOTUNE_CACHE, _AUTOTUNE_STATE
    from .cohorts import _COHORTS_CACHE
    from .core import _SPAN_PROGRAMS, _jitted_bundle
    from .costmodel import _CARD_LABELS, _CARD_REGISTRY
    from .factorize import _FACTORIZE_CACHE, _FACTORIZE_CACHE_BYTES
    from .fusion import _FUSED_PROGRAM_CACHE
    from .kernels import (
        _PALLAS_MINMAX_PROBE_RESULT,
        _PALLAS_MULTISTAT_PROBE_RESULT,
        _PALLAS_PROBE_RESULT,
        _PALLAS_RADIXBIN_PROBE_RESULT,
        _PALLAS_SCAN_PROBE_RESULT,
        _PRESENT_CACHE,
    )
    from .parallel.mapreduce import _PROGRAM_CACHE
    from .parallel.scan import _SCAN_CACHE
    from .pipeline import _DONATION_OK, _PREFETCH_INFLIGHT
    from .profiling import _CAPTURE_STATE
    from .resilience import _SNAPSHOTS
    from .serve.aot import _MANIFEST_MEMO
    from .serve.breaker import _BREAKER_REGISTRY
    from .serve.dispatcher import _BATCH_REGISTRY, _COALESCE_CACHE, _PENDING_REGISTRY
    from .streaming import _STEP_CACHE
    from .telemetry import (
        FLIGHT_RECORDER,
        METRICS,
        _COST_LEDGER,
        _TAIL_REGISTRY,
        _TENANT_LABELS,
    )

    _COHORTS_CACHE.clear()
    _FACTORIZE_CACHE.clear()
    _FACTORIZE_CACHE_BYTES[0] = 0
    _PROGRAM_CACHE.clear()
    _SPAN_PROGRAMS.clear()
    _SCAN_CACHE.clear()
    _STEP_CACHE.clear()
    _FUSED_PROGRAM_CACHE.clear()
    _DONATION_OK.clear()
    _SNAPSHOTS.clear()
    # serving layer (flox_tpu/serve/): admission/pending table, coalescing
    # + micro-batch tables, and the AOT warmup-manifest memo. Safe while a
    # dispatcher is live: open batches hold direct references to their own
    # entries, so a clear only prevents NEW requests from joining them.
    _PENDING_REGISTRY.clear()
    _COALESCE_CACHE.clear()
    _BATCH_REGISTRY.clear()
    _MANIFEST_MEMO.clear()
    # resident dataset registry: registry.clear() drops _DATASET_REGISTRY
    # and resets the eviction counter + gauges; in-flight dispatches keep
    # their direct references, so a clear only unpublishes names
    from .serve import registry as serve_registry

    serve_registry.clear()
    # durable store table: stores.clear() drops _STORE_TABLE and resets its
    # gauges; on-disk WAL/segment state is durable and untouched — a later
    # reference reopens (= recovers) it
    from .serve import stores as serve_stores

    serve_stores.clear()
    # circuit-breaker state resets with the program caches it shadows: a
    # cleared process has no failure history, so no breaker stays open
    _BREAKER_REGISTRY.clear()
    # pallas one-time probe memos (floxlint FLX008: every runtime-accreted
    # module-level cache must be reachable from here) — the next reduction
    # after a clear re-validates the backend, which is exactly the fresh
    # state a between-rounds clear promises
    _PALLAS_PROBE_RESULT.clear()
    _PALLAS_MINMAX_PROBE_RESULT.clear()
    _PALLAS_SCAN_PROBE_RESULT.clear()
    _PALLAS_MULTISTAT_PROBE_RESULT.clear()
    _PALLAS_RADIXBIN_PROBE_RESULT.clear()
    # sort-engine present-group tables (content-fingerprint keyed)
    _PRESENT_CACHE.clear()
    # autotune measurement store + its counters/lazy-load flag: clearing
    # returns the tuner to the unloaded state, so the next consult reloads
    # the persisted file (or runs plain heuristics when no path is set) —
    # every accessor reads the state dict through .get() with a default,
    # making the empty dict the reset state
    _AUTOTUNE_CACHE.clear()
    _AUTOTUNE_STATE.clear()
    _jitted_bundle.cache_clear()
    # observability plane (flox_tpu/telemetry.py + profiling.py +
    # pipeline.py): the flight-recorder ring, the per-trace parked
    # tail-detail buffers, the per-program/per-tenant cost ledger (HBM
    # attribution absorbed into it), the on-demand-capture guard, and the
    # prefetch-occupancy gauge counter reset with the metrics they
    # annotate. METRICS.reset() also drops the histograms' exemplar slots
    # — they live inside the registry's histogram state.
    # cost-model plane (flox_tpu/costmodel.py): the compiled-program card
    # registry and its label index reset with the ledger they annotate
    _CARD_REGISTRY.clear()
    _CARD_LABELS.clear()
    FLIGHT_RECORDER.clear()
    _TAIL_REGISTRY.clear()
    _COST_LEDGER.clear()
    _TENANT_LABELS.clear()
    _CAPTURE_STATE.clear()
    _PREFETCH_INFLIGHT[0] = 0
    METRICS.reset()
    # SLO plane (flox_tpu/slo.py): slo.clear() drops the burn-rate window
    # snapshot ring, the alert state table, the canary probe ledger, the
    # freshness tick ledger and the parsed-spec cache (its body references
    # _SNAPSHOT_RING / _ALERT_TABLE / _CANARY_LEDGER / _FRESHNESS_LEDGER /
    # _SPEC_CACHE directly for floxlint FLX008) — alert state must not
    # outlive the counters (just reset above) it judged
    from . import slo as slo_plane

    slo_plane.clear()
