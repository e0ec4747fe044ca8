"""Flight recorder for the scheduler stack (observability layer).

- :mod:`repro.obs.trace`     structured JSONL span/event records + null tracer
- :mod:`repro.obs.decisions` decision-audit records (inputs, alternatives,
  verdict) at every policy/autoscaler/bidder choice point
- :mod:`repro.obs.stats`     streaming P2 quantiles, counters, latency recorder
- :mod:`repro.obs.audit`     trace replayer re-deriving conservation invariants
- :mod:`repro.obs.timeline`  text Gantt renderer over a trace
- :mod:`repro.obs.spans`     causal span graph (lifecycle trees + cause edges)
- :mod:`repro.obs.critical_path` per-job phase decomposition + fleet rollups
- :mod:`repro.obs.profile`   zero-dep self-profiler for the simulator hot path
- :mod:`repro.obs.watchdog`  perf baseline diff + metric-stream anomaly scan
- :mod:`repro.obs.live`      spans and counters of the live JAX trainer, on
  the profiler's clock (imports JAX, so it is not imported here)
"""
from repro.obs.critical_path import (PHASES, FleetPhases, PhaseLedger,
                                     decompose, rollup)
from repro.obs.decisions import DecisionLog, decision_records
from repro.obs.profile import SimProfiler, current_profiler, install_profiler
from repro.obs.spans import (Span, SpanGraph, SpanGraphBuilder, SpanTap,
                             build_span_graph)
from repro.obs.stats import Counters, LatencyRecorder, P2Quantile
from repro.obs.trace import (NULL_TRACER, NullTracer, Tracer, current_tracer,
                             install)
from repro.obs.watchdog import (WatchdogConfig, WatchdogReport,
                                diff_snapshots, rolling_median_spikes)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "install", "current_tracer",
    "DecisionLog", "decision_records",
    "P2Quantile", "Counters", "LatencyRecorder",
    "Span", "SpanGraph", "SpanGraphBuilder", "SpanTap", "build_span_graph",
    "PHASES", "PhaseLedger", "FleetPhases", "decompose", "rollup",
    "SimProfiler", "current_profiler", "install_profiler",
    "WatchdogConfig", "WatchdogReport", "diff_snapshots",
    "rolling_median_spikes",
]
