"""loadgen — the open-loop traffic harness.

A *scenario* describes production-shaped load: an open-loop arrival
process (arrivals fire on a precomputed schedule, never gated on
completions, so queue buildup is visible instead of hidden), a
zipf-skewed workload mix of query legs, a background PTS1 ingest leg,
and an optional chaos timeline. One run drives a live node or cluster
over HTTP and emits a machine-readable SLO report: per-QoS-class
p50/p99/p999, shed/quota/hedge/breaker rates, cache hit ratio, ingest
throughput, and p99 exemplar trace ids resolved through
``/debug/queries/<trace-id>`` into full cost profiles.

Run one with ``python -m pilosa_tpu.loadgen <scenario>`` (see
``scenarios.py`` for the built-ins).
"""

from pilosa_tpu.loadgen.arrival import OpenLoopArrivals
from pilosa_tpu.loadgen.engine import run_scenario
from pilosa_tpu.loadgen.mix import WorkloadMix, ZipfPicker, zipf_weights
from pilosa_tpu.loadgen.report import validate_report
from pilosa_tpu.loadgen.scenario import (ChaosAction, IngestLeg, QueryLeg,
                                         Scenario)
from pilosa_tpu.loadgen.scenarios import SCENARIOS, get_scenario
from pilosa_tpu.loadgen.target import AttachedTarget, ManagedTarget

__all__ = [
    "OpenLoopArrivals", "WorkloadMix", "ZipfPicker", "zipf_weights",
    "Scenario", "QueryLeg", "IngestLeg", "ChaosAction",
    "run_scenario", "validate_report", "SCENARIOS", "get_scenario",
    "AttachedTarget", "ManagedTarget",
]
