"""Asynchronous shape-bucketed BLAS L3 serving on top of the ADSALA runtime.

    BlasService — submit()/call() front-end, scheduler + bounded worker pool
    FleetService — the same front-end sharded over N executor *processes*
                  (shared-journal decision coherence, fingerprint-resolved
                  artifacts; see ``repro/serving/fleet.py``)
    ServeConfig — bucket/flush knobs (max_batch, linger_ms, workers, ...)
    ServeStats  — service-level counters (per-bucket detail on the runtime)
    Retuner     — drift-aware online retraining loop (opt-in; pass one to
                  BlasService to close the serving→install feedback loop)
    ErrorBudgetLedger — per-(backend, op) rolling failure budgets gating the
                  degradation ladder (over-budget rungs skip their retries)
    FaultPlan   — deterministic seeded fault injection (chaos harness); give
                  one plan to the service/runtime/retuner to drive every
                  failure path on purpose

Failure semantics: every submitted request resolves — result, or a typed
error (ServiceClosedError / DeadlineExpiredError / ExecutionFailedError);
overload is shed synchronously at submit with AdmissionRejectedError.
See ``repro/serving/service.py`` for the life-of-a-request diagram and the
budget-gated degradation ladder, ``repro/serving/budget.py`` for the error
budgets, ``repro/serving/retune.py`` for the drift/refit/hot-swap
semantics, ``repro/serving/faults.py`` for the named injection sites, and
``tests/test_resilience.py`` / ``tests/test_durable.py`` for the seeded
fault and crash-recovery scenarios.
"""

from .budget import BudgetConfig, ErrorBudgetLedger
from .faults import FaultPlan, FaultSpec, InjectedFault
from .fleet import ExecutorDiedError, FleetConfig, FleetService
from .retune import Retuner, RetuneConfig, RetuneStats
from .service import (AdmissionRejectedError, BlasService,
                      DeadlineExpiredError, ExecutionFailedError,
                      ServeConfig, ServeStats, ServiceClosedError, bucket_key)

__all__ = ["BlasService", "ServeConfig", "ServeStats", "bucket_key",
           "FleetService", "FleetConfig", "ExecutorDiedError",
           "Retuner", "RetuneConfig", "RetuneStats",
           "BudgetConfig", "ErrorBudgetLedger",
           "FaultPlan", "FaultSpec", "InjectedFault",
           "ServiceClosedError", "DeadlineExpiredError",
           "ExecutionFailedError", "AdmissionRejectedError"]
