"""Serving-side infrastructure: W8A8 int8 quantized verify path, the
fault-tolerance layer (deterministic injection, round guards,
watchdogs — DESIGN.md §13), and the durable-state / overload layer
(write-ahead round journal, snapshots, admission control — §14), and
the serving loop's spans and counters (``spans``, §16)."""

from repro.serving.admission import (
    CircuitBreaker,
    Overloaded,
    TokenRateLimiter,
    pick_shed_victim,
)
from repro.serving.faults import (
    FAULT_KINDS,
    FaultPlan,
    InjectedFault,
    poison_outcome,
)
from repro.serving.guard import (
    GuardViolation,
    InvalidRequest,
    RoundWatchdog,
    WatchdogTimeout,
    check_packed,
    validate_outcome,
    validate_prompt,
    validate_wz_batch,
)
from repro.serving.journal import RoundJournal, read_journal
from repro.serving.quant import qdot, quantize_params, quantize_weight, verify_step_q
from repro.serving.snapshot import read_snapshot, write_snapshot

__all__ = [
    "FAULT_KINDS",
    "CircuitBreaker",
    "FaultPlan",
    "GuardViolation",
    "InjectedFault",
    "InvalidRequest",
    "Overloaded",
    "RoundJournal",
    "RoundWatchdog",
    "TokenRateLimiter",
    "WatchdogTimeout",
    "check_packed",
    "pick_shed_victim",
    "poison_outcome",
    "qdot",
    "quantize_params",
    "quantize_weight",
    "read_journal",
    "read_snapshot",
    "validate_outcome",
    "validate_prompt",
    "validate_wz_batch",
    "verify_step_q",
    "write_snapshot",
]
