"""JAX-aware profiling hooks: named scopes, a recompile counter keyed by
bucketed shape, and opt-in ``jax.profiler`` trace capture.

Recompile semantics: every XLA backend compile in the process fires
``/jax/core/compile/backend_compile_duration`` through ``jax.monitoring``.
A ``RecompileWatch`` subscribes once (one process-global listener fanning
out to every live watch) and attributes each compile to the *compile
region* active on the compiling thread — a ``contextvars`` label the
serving call sites set around their jit entry points, carrying the
bucketed shape key (``ingest[pipeline b=512]``, ``solve[jit_sum B=32
kmax=8]``). Compiles with no active region land under ``"unattributed"``
(jnp helpers, library warmup, other subsystems).

That attribution is what makes "did this change introduce steady-state
recompiles?" a measurable, gateable quantity: the serve bench resets a
watch after its warmup rounds and asserts the measured rounds compiled
*nothing* (``steady_state_recompiles == 0`` — enforced by
``benchmarks.run --check``). Because the shape key IS the bucket, a
recompile that should have been absorbed by pow-2 bucketing shows up
under the exact bucket label that failed to hold.

``named_scope`` is re-exported here as the one sanctioned *in-trace*
annotation: it tags HLO ops with their source region so profiler traces
and compiled-module dumps read as ``dmmc/blocked_scan``,
``dmmc/precheck``, ``solver/jit_sum`` instead of fusion soup. It is
metadata only — safe under jit/vmap/scan, zero runtime cost.

``profiler_trace`` wraps ``jax.profiler.start_trace/stop_trace`` as an
opt-in context manager (explicit ``enabled=True`` or the
``REPRO_OBS_PROFILE=dir`` environment knob) that never lets profiler
failures take down serving.
"""
from __future__ import annotations

import contextlib
import contextvars
import os
import threading
from typing import Optional

import jax
from jax import named_scope  # re-export: the in-trace annotation

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
UNATTRIBUTED = "unattributed"

_compile_key: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "repro_obs_compile_key", default=None
)


@contextlib.contextmanager
def compile_region(key: str):
    """Attribute any backend compile triggered inside to ``key`` (use the
    bucketed shape as the key so a counter > 0 names the bucket that
    failed to hold). Nested regions: innermost wins."""
    token = _compile_key.set(key)
    try:
        yield
    finally:
        _compile_key.reset(token)


def current_compile_region() -> Optional[str]:
    return _compile_key.get()


_watches: list["RecompileWatch"] = []
_listener_installed = False
_install_mu = threading.Lock()


def _listener(event: str, duration, **kwargs) -> None:
    # jax.monitoring listeners run inside the compile path: never raise.
    if event != BACKEND_COMPILE_EVENT:
        return
    key = _compile_key.get() or UNATTRIBUTED
    for w in tuple(_watches):
        try:
            w._on_compile(key, float(duration))
        except Exception:  # pragma: no cover - defensive
            pass


def _install_listener() -> None:
    global _listener_installed
    with _install_mu:
        if _listener_installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_listener)
        _listener_installed = True


class RecompileWatch:
    """Counts backend compiles per compile-region key.

    ``reset()`` opens a measurement window; ``total()`` / ``by_key()``
    read it. Independent watches over the same process stream count
    independently (the bench keeps one never-reset watch for the full-run
    compile census and one windowed watch for the steady-state gate)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._counts: dict[str, int] = {}
        self._secs: dict[str, float] = {}
        _install_listener()
        _watches.append(self)

    def _on_compile(self, key: str, duration: float) -> None:
        with self._mu:
            self._counts[key] = self._counts.get(key, 0) + 1
            self._secs[key] = self._secs.get(key, 0.0) + duration

    def total(self, *, include_unattributed: bool = True) -> int:
        with self._mu:
            return sum(
                c for k, c in self._counts.items()
                if include_unattributed or k != UNATTRIBUTED
            )

    def by_key(self) -> dict[str, int]:
        with self._mu:
            return dict(self._counts)

    def seconds_by_key(self) -> dict[str, float]:
        with self._mu:
            return dict(self._secs)

    def reset(self) -> None:
        with self._mu:
            self._counts.clear()
            self._secs.clear()

    def close(self) -> None:
        """Stop receiving events (the global listener stays installed —
        jax.monitoring has no per-listener removal — but this watch
        drops out of the fan-out)."""
        try:
            _watches.remove(self)
        except ValueError:
            pass


_default_watch: Optional[RecompileWatch] = None
_default_watch_mu = threading.Lock()


def recompile_watch() -> RecompileWatch:
    """The process-default watch (created + subscribed on first use)."""
    global _default_watch
    if _default_watch is None:
        with _default_watch_mu:
            if _default_watch is None:
                _default_watch = RecompileWatch()
    return _default_watch


@contextlib.contextmanager
def profiler_trace(logdir: Optional[str] = None, *,
                   enabled: Optional[bool] = None):
    """Opt-in ``jax.profiler`` capture around a region (ingest/solve
    sections in the bench). Default resolves from ``REPRO_OBS_PROFILE``:
    unset -> disabled; set -> enabled, its value the log directory unless
    ``logdir`` overrides. Yields True iff a capture is running; profiler
    errors (double-start, unsupported backend) disable the capture
    rather than failing the caller."""
    env = os.environ.get("REPRO_OBS_PROFILE", "")
    on = bool(env) if enabled is None else enabled
    where = logdir or env or "/tmp/repro-jax-trace"
    if not on:
        yield False
        return
    started = False
    try:
        jax.profiler.start_trace(where)
        started = True
    except Exception:
        pass
    try:
        yield started
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:  # pragma: no cover - defensive
                pass
