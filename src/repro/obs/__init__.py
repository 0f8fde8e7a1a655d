"""repro.obs — unified telemetry across the round engine, simulator, serving.

One ``Recorder`` (counters / gauges / histograms / spans) over a pluggable
clock records every engine off the hot path; streams serialize as versioned
JSONL (``ObsStream``) with a shared provenance header, and render as run
reports or Prometheus text. See docs/OBSERVABILITY.md for the full model,
schema and cookbook.

Quickstart::

    from repro.obs import Recorder, VirtualClock, provenance
    rec = Recorder(clock=VirtualClock())
    runner.attach_obs(rec)            # AsyncDFedRW / FleetDFedRW / DFedRW
    runner.run(rounds, key, x_test, y_test)
    rec.save("obs.jsonl", provenance=provenance())
    # then: python tools/obs_report.py obs.jsonl
"""
from .critical import (WindowCriticalPath, critical_paths, render_critical,
                       straggler_table)
from .provenance import PROVENANCE_KEYS, config_hash, provenance
from .recorder import (HIST_RESERVOIR, PausableWallClock, Recorder,
                       VirtualClock, WallClock, jax_profile, quantile_line)
from .report import render_prometheus, render_report
from .scopes import ENGINE_SPANS, FEDSTEP_SCOPES, ROUND_SCOPES
from .stream import (OBS_COMPAT_VERSIONS, OBS_SCHEMA, OBS_SCHEMA_VERSION,
                     ObsError, ObsFormatError, ObsSchemaError, ObsStream,
                     make_obs_header)
from .trace import (SPAN_KINDS, TRACE_COARSE_LIMIT, TraceSpan, TraceTree,
                    build_trees, emit_walk_window, spans_of)

__all__ = [
    "Recorder",
    "WallClock",
    "PausableWallClock",
    "VirtualClock",
    "jax_profile",
    "HIST_RESERVOIR",
    "quantile_line",
    "ObsStream",
    "OBS_SCHEMA",
    "OBS_SCHEMA_VERSION",
    "OBS_COMPAT_VERSIONS",
    "ObsError",
    "ObsFormatError",
    "ObsSchemaError",
    "make_obs_header",
    "provenance",
    "config_hash",
    "PROVENANCE_KEYS",
    "render_report",
    "render_prometheus",
    "SPAN_KINDS",
    "TRACE_COARSE_LIMIT",
    "TraceSpan",
    "TraceTree",
    "spans_of",
    "build_trees",
    "emit_walk_window",
    "WindowCriticalPath",
    "critical_paths",
    "straggler_table",
    "render_critical",
    "ROUND_SCOPES",
    "FEDSTEP_SCOPES",
    "ENGINE_SPANS",
]
