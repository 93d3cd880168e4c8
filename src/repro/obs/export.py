"""Exporters + one-call observability snapshots.

The registry/tracing modules own their own serialization
(``MetricsRegistry.write_jsonl``/``dump``, ``TraceBuffer.dump``); this
module is the batteries-included layer the bench, the example, and CI
use: grab the metrics and the recompile census in one call, against the
process defaults or explicit instances (the trace: ``obs.dump_trace``).
"""
from __future__ import annotations

from typing import Optional

from .jaxprof import RecompileWatch, recompile_watch
from .metrics import MetricsRegistry, default_registry


def metrics_snapshot(registry: Optional[MetricsRegistry] = None) -> dict:
    return (registry or default_registry()).snapshot()


def write_metrics_jsonl(path: str,
                        registry: Optional[MetricsRegistry] = None) -> str:
    (registry or default_registry()).write_jsonl(path)
    return path


def dump_metrics(registry: Optional[MetricsRegistry] = None, stream=None):
    (registry or default_registry()).dump(stream)


def observability_report(
    registry: Optional[MetricsRegistry] = None,
    watch: Optional[RecompileWatch] = None,
) -> dict:
    """Everything the artifacts embed: the metrics snapshot plus the
    recompile census of the default (or given) watch."""
    w = watch or recompile_watch()
    return {
        "metrics": metrics_snapshot(registry),
        "recompiles_by_key": w.by_key(),
        "recompile_seconds_by_key": {
            k: round(v, 6) for k, v in w.seconds_by_key().items()
        },
    }
