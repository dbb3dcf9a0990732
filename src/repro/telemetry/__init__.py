"""Cycle-resolved, bit-deterministic tracing of the overlay (docs/telemetry.md).

The paper's headline numbers are aggregates; this package instruments *why*
— per-PE occupancy, per-link Hoplite utilization, deflections by cause,
eject-port contention, scheduler ready-set depth, and stall attribution —
without perturbing the model. Opt in via::

    import repro
    from repro.telemetry import TelemetrySpec
    r = repro.run(gm, OverlayConfig(telemetry=TelemetrySpec()))
    r.telemetry.report()                      # p50/p95 link util, stalls, ...
    r.telemetry.export_perfetto("trace.json") # open in ui.perfetto.dev

Traces accumulate as integer tensors *inside* the jitted cycle loop
(:mod:`.trace`), ride the state pytree through all four engines — solo,
batched, sharded, batched-sharded — and through the chunk repair, and
are bit-identical across every engine and
``check_every``. ``telemetry=None`` (the default) compiles to exactly the
untraced program. ``python -m repro.telemetry`` runs a cached fig1 workload
and renders an ASCII heatmap + a Perfetto trace artifact.
"""
from .result import TelemetryResult
from .spec import TelemetrySpec

__all__ = ["TelemetrySpec", "TelemetryResult"]
