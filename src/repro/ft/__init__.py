"""Fault tolerance (survey §8): detection, recovery, and chaos testing.

- :mod:`repro.ft.anomaly` — statistical detectors (nan/inf, spike, hang)
  plus externally-noted kinds (sdc, ckpt_io);
- :mod:`repro.ft.recovery` — the policy-table recovery driver, restoring
  memory-tier-first (:mod:`repro.checkpoint.memory`) with a verified disk
  walk as the fallback;
- :mod:`repro.ft.preempt` — SIGTERM/SIGUSR1 preemption guard: just-in-time
  snapshot within a grace budget, ``PREEMPTED`` marker, clean resumable
  exit;
- :mod:`repro.ft.flight` — the crash flight recorder: a bounded ring of
  per-step events dumped to JSON on preemption/crash/RecoveryExhausted, and
  ``span``, which times each section of the loop and of a checkpoint save
  on the profiler's clock;
- :mod:`repro.ft.inject` — deterministic seeded fault injection at named
  fault points (the registry is ``inject.FAULT_POINTS``; see that module's
  docstring for how to add a point);
- :mod:`repro.ft.integrity` — device-side SDC checksums cross-checked
  across replicas (``plan.integrity = "audit"``);
- :mod:`repro.ft.straggler` — fail-slow defense: per-rank/per-component
  straggler attribution from host-side timing telemetry, and Malleus-style
  uneven pipeline rebalancing (:func:`choose_pp_layout` →
  ``ParallelPlan.pp_layout``) as the mitigation.
"""

from repro.core.config import RecoveryPolicy
from .anomaly import Anomaly, Monitor
from .flight import FlightRecorder
from .preempt import (PreemptionGuard, clear_marker, read_marker,
                      write_marker)
from .recovery import (RecoveryExhausted, RemeshSpec, RunReport,
                       run_with_recovery)
from .straggler import (Straggler, StragglerDetector, StragglerTimer,
                        choose_pp_layout, effective_layout)

__all__ = ["Anomaly", "FlightRecorder", "Monitor", "PreemptionGuard",
           "RecoveryExhausted", "RecoveryPolicy", "RemeshSpec", "RunReport",
           "Straggler", "StragglerDetector", "StragglerTimer",
           "choose_pp_layout", "clear_marker", "effective_layout",
           "read_marker", "run_with_recovery", "write_marker"]
