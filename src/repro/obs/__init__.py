"""Flight-recorder telemetry (PR 10).  See obs/telemetry.py."""
from repro.obs.telemetry import (  # noqa: F401
    Checkpoint,
    Guardian,
    Histogram,
    NOT_SAMPLED,
    Recorder,
    RequestSpan,
    SweepRound,
    TrainStep,
    percentile,
    profile_ctx,
    read_events,
    span,
)

__all__ = [
    "Checkpoint", "Guardian", "Histogram", "NOT_SAMPLED", "Recorder",
    "RequestSpan", "SweepRound", "TrainStep", "percentile", "profile_ctx",
    "read_events", "span",
]
