"""Serving: continuous-batching engine + ICC priority scheduling."""

from .calibrate import measure_service_time, measured_service_fn
from .engine import (
    EngineCounters,
    GenRequest,
    GenResult,
    InferenceEngine,
    SamplingParams,
)
from .icc import ICCRequest, ICCServer, ServeStats

__all__ = [
    "EngineCounters",
    "GenRequest",
    "GenResult",
    "ICCRequest",
    "ICCServer",
    "InferenceEngine",
    "SamplingParams",
    "ServeStats",
    "measure_service_time",
    "measured_service_fn",
]
