"""Profiling hooks (auxiliary subsystem; SURVEY.md §5).

The reference has no built-in profiler beyond debug logging — profiling is
external (asv, snakeviz). On TPU the native tool is ``jax.profiler``; this
module provides the thin wrappers so users can capture a trace of a grouped
reduction without learning the jax API, plus the streaming-pipeline
instrumentation (:func:`stream_monitor`): every ``streaming_groupby_*``
call emits one :class:`StreamReport` of per-slab load/stage/wait/dispatch
timings from which the prefetch overlap is read directly.

On-demand capture (ISSUE 9): a serving replica cannot wrap its hot loop in
a ``with trace(...)`` block after the fact — the moment an operator wants a
device profile is exactly while the process is misbehaving. The capture
surface (:func:`start_capture`) starts a ``jax.profiler`` trace into a
rotated directory under ``OPTIONS["profile_dir"]`` and stops it after N
seconds on a timer thread, one capture at a time; it is reachable over
HTTP (``/debug/profile?seconds=N`` on the metrics endpoint), over the
serve protocol (``{"op": "profile"}``) and via SIGUSR1
(:func:`install_capture_signal`), and never raises into the serve loop.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator

logger = logging.getLogger("flox_tpu.profiling")

__all__ = [
    "trace",
    "timed",
    "stream_monitor",
    "StreamReport",
    "CaptureBusyError",
    "CaptureUnavailableError",
    "capture_active",
    "install_capture_signal",
    "start_capture",
]


class CaptureBusyError(RuntimeError):
    """A capture is already running — one at a time (the profiler is a
    process-global singleton; HTTP answers 409)."""


class CaptureUnavailableError(RuntimeError):
    """No capture is possible: the backend has no profiler, or no capture
    root is configured (HTTP answers 501)."""


def _default_logdir() -> Any:
    from .options import OPTIONS

    return OPTIONS["profile_dir"]


@contextlib.contextmanager
def trace(logdir: str | None = None):
    """Capture a jax profiler trace (view with TensorBoard / xprof).

    ``logdir`` defaults to ``OPTIONS["profile_dir"]`` (env
    ``FLOX_TPU_PROFILE_DIR``) — the same root the on-demand capture surface
    rotates under; with neither configured this raises ``ValueError``. A
    backend without a working profiler warns and no-ops instead of raising:
    the block still runs, only the trace is missing.

    >>> with flox_tpu.profiling.trace("/tmp/flox-trace"):  # doctest: +SKIP
    ...     groupby_reduce(...)
    """
    import jax

    if logdir is None:
        logdir = _default_logdir()
    if logdir is None:
        raise ValueError(
            "profiling.trace() needs a logdir: pass one explicitly or set "
            "OPTIONS['profile_dir'] (env FLOX_TPU_PROFILE_DIR)"
        )
    logdir = str(logdir)
    try:
        jax.profiler.start_trace(logdir)
    except Exception as exc:  # noqa: BLE001 — a profiler-less backend must
        # not take the profiled workload down with it: warn and run untraced
        logger.warning("profiler unavailable, running untraced: %s", exc)
        yield
        return
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
            logger.info("profiler trace written to %s", logdir)
        except Exception as exc:  # noqa: BLE001 — same contract as start
            logger.warning("profiler stop failed: %s", exc)


# ---------------------------------------------------------------------------
# on-demand capture: bounded, rotated, one at a time
# ---------------------------------------------------------------------------


#: capture-state guard: ``{"active": {...}}`` while a capture runs (dir /
#: seconds / started), plus the rotation sequence counter. One capture at a
#: time — the jax profiler is process-global. Every accessor reads through
#: ``.get()`` with a default, so the empty dict is the reset state
#: (registered in cache.clear_all; a clear during a live capture only
#: forgets the guard — the timer thread still stops the profiler).
_CAPTURE_STATE: dict[str, Any] = {}
_CAPTURE_LOCK = threading.Lock()


def capture_active() -> dict | None:
    """A copy of the live capture's info (dir/seconds/started), or ``None``."""
    with _CAPTURE_LOCK:
        active = _CAPTURE_STATE.get("active")
        return dict(active) if active else None


def _rotate_captures(root: Any, keep: int) -> None:
    """Delete the oldest ``capture-*`` dirs so at most ``keep - 1`` remain
    before a new one is created — an operator poking ``/debug/profile`` in
    a loop must never fill the disk. Timestamped names sort chronologically."""
    import os
    import shutil

    try:
        entries = sorted(
            e for e in os.listdir(str(root)) if e.startswith("capture-")
        )
    except OSError:
        return
    excess = len(entries) - (keep - 1)
    for stale in entries[:excess] if excess > 0 else []:
        shutil.rmtree(os.path.join(str(root), stale), ignore_errors=True)


def start_capture(seconds: float = 5.0, root: Any = None) -> str:
    """Start an on-chip profiler capture; stop it after ``seconds``.

    The capture lands in a fresh ``capture-<stamp>-<seq>`` dir under
    ``root`` (default ``OPTIONS["profile_dir"]``), with old captures
    rotated out past ``OPTIONS["profile_keep"]``. Returns the capture dir
    immediately — the stop runs on a daemon timer thread, so the caller
    (the HTTP handler, the serve loop, a signal handler's helper thread)
    never blocks behind the capture window. Raises
    :class:`CaptureBusyError` while another capture runs,
    :class:`CaptureUnavailableError` when no root is configured or the
    backend has no working profiler, ``ValueError`` for a bad window.
    """
    import os

    from . import telemetry
    from .options import OPTIONS

    seconds = float(seconds)
    if not 0 < seconds <= 3600:
        raise ValueError(f"capture window must be in (0, 3600] seconds, got {seconds}")
    if root is None:
        root = OPTIONS["profile_dir"]
    if root is None:
        raise CaptureUnavailableError(
            "no capture root configured: set OPTIONS['profile_dir'] "
            "(env FLOX_TPU_PROFILE_DIR)"
        )
    with _CAPTURE_LOCK:
        if _CAPTURE_STATE.get("active"):
            raise CaptureBusyError(
                f"capture already running in {_CAPTURE_STATE['active']['dir']}"
            )
        seq = _CAPTURE_STATE.get("seq", 0) + 1
        _CAPTURE_STATE["seq"] = seq
        os.makedirs(str(root), exist_ok=True)
        _rotate_captures(root, int(OPTIONS["profile_keep"]))
        stamp = time.strftime("%Y%m%d-%H%M%S")
        capture_dir = os.path.join(str(root), f"capture-{stamp}-{seq:03d}")
        try:
            import jax

            jax.profiler.start_trace(capture_dir)
        except Exception as exc:  # noqa: BLE001 — no profiler on this backend
            raise CaptureUnavailableError(f"profiler unavailable: {exc}") from exc
        # dispatch-mark snapshot: the finished capture is stamped with
        # exactly the program labels (and card digests) dispatched during
        # the window — the join from a capture dir back to /debug/costs
        # and /debug/programs rows (best-effort; never blocks the start)
        try:
            from .costmodel import dispatch_marks

            marks = dispatch_marks()
        except Exception:  # noqa: BLE001 — stamping is best-effort by contract
            marks = {}
        _CAPTURE_STATE["active"] = {
            "dir": capture_dir, "seconds": seconds, "started": time.time(),
            "marks": marks,
        }

    def _finish() -> None:
        try:
            import jax

            jax.profiler.stop_trace()
            logger.info("on-demand capture written to %s", capture_dir)
        except Exception as exc:  # noqa: BLE001 — stopping is best-effort;
            # the guard must clear either way or no capture ever runs again
            logger.warning("on-demand capture stop failed: %s", exc)
        with _CAPTURE_LOCK:
            active = _CAPTURE_STATE.get("active", {})
            marks_then = active.get("marks") if active.get("dir") == capture_dir else None
            if active.get("dir") == capture_dir:
                _CAPTURE_STATE.pop("active", None)
        if marks_then is not None:
            # stamp the capture with the programs dispatched inside the
            # window (cumulative ledger dispatches minus the start marks),
            # each with its card digest — documented in the capture
            # runbook. A vanished guard (cache.clear_all mid-window) has
            # no baseline: skip rather than attribute history to the window
            try:
                from .costmodel import stamp_capture

                stamp_capture(capture_dir, marks_then)
            except Exception:  # noqa: BLE001 — stamping never breaks a capture
                pass
        telemetry.count("profile.captures")
        telemetry.event("profile.capture", dir=capture_dir, seconds=seconds)

    timer = threading.Timer(seconds, _finish)
    timer.daemon = True
    timer.start()
    telemetry.count("profile.capture_starts")
    return capture_dir


def install_capture_signal() -> None:
    """SIGUSR1 -> a 5-second on-demand capture into the configured root.

    Signal-safe: the handler only spawns a daemon thread (no profiler work,
    no locks in the interrupted frame) and never raises — a busy or
    unconfigured capture is a log line, not a crash. No-op on platforms
    without SIGUSR1 or off the main thread."""
    import signal

    signum = getattr(signal, "SIGUSR1", None)
    if signum is None:
        return

    def _capture_bg() -> None:
        try:
            start_capture(seconds=5.0)
        except (CaptureBusyError, CaptureUnavailableError, ValueError) as exc:
            logger.warning("SIGUSR1 capture not started: %s", exc)

    def _handler(signum: int, frame: Any) -> None:
        threading.Thread(
            target=_capture_bg, name="flox-tpu-capture", daemon=True
        ).start()

    try:
        signal.signal(signum, _handler)
    except (ValueError, OSError):  # not the main thread / exotic platform
        return


@contextlib.contextmanager
def timed(label: str):
    """Wall-clock log line for a block (host-side; includes dispatch)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        logger.info("%s took %.3f ms", label, (time.perf_counter() - t0) * 1e3)


@dataclass
class StreamReport:
    """Per-slab pipeline timings for one streaming pass.

    ``slabs`` holds the :class:`flox_tpu.pipeline.Slab` records in
    consumption order; each carries ``load_ms`` (loader IO), ``stage_ms``
    (pad + device_put), ``wait_ms`` (time the consumer thread was blocked
    waiting for the slab — with prefetch off this IS load+stage, with
    prefetch on it is only the unhidden remainder) and ``dispatch_ms``
    (consumer-side step dispatch, including any throttle sync).

    ``counters`` is the run's ``resilience.StreamCounters``: retry /
    backoff-wait / OOM-split / checkpoint totals, plus the resume cursor
    when the run restored from a snapshot. A multi-pass run (streaming
    quantile) shares ONE counters object across its passes, so each pass's
    report shows the cumulative values."""

    label: str = ""
    prefetch: int = 0
    nbatches: int = 0
    wall_ms: float = 0.0
    slabs: list = field(default_factory=list)
    counters: Any = None

    @property
    def load_ms(self) -> float:
        return sum(s.load_ms for s in self.slabs)

    @property
    def stage_ms(self) -> float:
        return sum(s.stage_ms for s in self.slabs)

    @property
    def wait_ms(self) -> float:
        return sum(s.wait_ms for s in self.slabs)

    @property
    def dispatch_ms(self) -> float:
        return sum(s.dispatch_ms for s in self.slabs)

    @property
    def overlap_fraction(self) -> float:
        """Share of the staging wall (load+stage) hidden off the consumer's
        critical path: 0.0 when every staged byte was waited for inline
        (prefetch off), approaching 1.0 when the pipeline kept staging
        entirely behind dispatch/compute."""
        staged = self.load_ms + self.stage_ms
        if staged <= 0.0:
            return 0.0
        return min(1.0, max(0.0, 1.0 - self.wait_ms / staged))

    @property
    def retries(self) -> int:
        return self.counters.retries if self.counters is not None else 0

    @property
    def backoff_ms(self) -> float:
        return self.counters.backoff_ms if self.counters is not None else 0.0

    @property
    def oom_splits(self) -> int:
        return self.counters.oom_splits if self.counters is not None else 0

    @property
    def checkpoints(self) -> int:
        return self.counters.checkpoints if self.counters is not None else 0

    @property
    def resumed_at(self):
        return self.counters.resumed_at if self.counters is not None else None

    def summary(self) -> str:
        line = (
            f"stream-pipeline [{self.label}] {len(self.slabs)}/{self.nbatches} "
            f"slab(s) prefetch={self.prefetch}: wall {self.wall_ms:.1f} ms, "
            f"load {self.load_ms:.1f} ms, stage {self.stage_ms:.1f} ms, "
            f"wait {self.wait_ms:.1f} ms, dispatch {self.dispatch_ms:.1f} ms, "
            f"overlap {self.overlap_fraction:.0%}"
        )
        if self.retries:
            line += f", retries {self.retries} (backoff {self.backoff_ms:.0f} ms)"
        if self.oom_splits:
            line += f", oom-splits {self.oom_splits}"
        if self.checkpoints:
            line += f", checkpoints {self.checkpoints}"
        if self.resumed_at is not None:
            line += f", resumed@{self.resumed_at}"
        return line


# active stream_monitor collectors (consumer-thread only: reports are
# appended by the stream_slabs generator after each pass completes)
_MONITORS: list[list[StreamReport]] = []


@contextlib.contextmanager
def stream_monitor() -> Iterator[list[StreamReport]]:
    """Collect the :class:`StreamReport` of every streaming pass in scope.

    >>> from flox_tpu import profiling, streaming_groupby_reduce
    >>> with profiling.stream_monitor() as reports:  # doctest: +SKIP
    ...     streaming_groupby_reduce(loader, by, func="nanmean")
    >>> reports[0].overlap_fraction  # doctest: +SKIP
    """
    reports: list[StreamReport] = []
    _MONITORS.append(reports)
    try:
        yield reports
    finally:
        _MONITORS.remove(reports)


def record_stream(report: Any) -> None:
    """Deliver one finished pass to every active monitor (and the log)."""
    for collector in _MONITORS:
        collector.append(report)
    logger.info("%s", report.summary())
