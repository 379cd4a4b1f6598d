"""Observability: query-lifecycle tracing, metrics, cost-model audit,
cardinality audit, host-transfer ledger and host crossings, flight
recording, SLO burn-rate monitoring and drift detection."""
from .audit import CostAudit
from .boundary import HostBoundary, Tally
from .cardinality import CardinalityAudit, q_error
from .drift import DriftDetector, PageHinkley
from .flight import (FlightRecorder, dump_live_recorders, summarize_outcome,
                     validate_dump)
from .ledger import CAUSES, INTERMEDIATE_CAUSES, TransferLedger
from .metrics import MetricsRegistry
from .slo import SLObjective, SLOMonitor, default_objectives
from .trace import NULL_TRACER, NullTracer, SpanRecord, Tracer

__all__ = ["CAUSES", "CardinalityAudit", "CostAudit", "DriftDetector",
           "FlightRecorder", "HostBoundary", "INTERMEDIATE_CAUSES",
           "MetricsRegistry", "NULL_TRACER", "NullTracer", "PageHinkley",
           "SLObjective", "SLOMonitor", "SpanRecord", "Tally", "Tracer",
           "TransferLedger", "default_objectives", "dump_live_recorders",
           "q_error", "summarize_outcome", "validate_dump"]
