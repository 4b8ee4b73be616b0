"""Resilient execution: deterministic fault injection + supervised
crash-recovery.

Two halves, built for multi-hour runs on preemptible accelerators:

- **Fault injection** (``faults.py``): the ``STpu_FAULTS`` registry —
  seeded, replayable fault points threaded through all four device
  engines, the host BFS, the checkpoint writer, the sharded
  all-to-all, and the bench device child. Unset, the whole subsystem
  is one attribute check per wave (``NULL_PLAN``).
- **Supervised recovery** (``supervisor.py``): bounded retry +
  jittered exponential backoff over any engine factory, resuming from
  the newest CRC-valid checkpoint generation (format v3+ keeps the
  last two, so a torn write falls back one generation). Every retry
  is an obs ``retry`` event.
- **Elasticity** (``elastic.py`` + ``membership.py``): the
  coordinator/worker runtime — heartbeat-lease membership, per-shard
  checkpoint generations (format v4), shard migration onto survivors
  under an epoch-versioned rendezvous :class:`OwnerMap`, and mid-run
  join/rebalance. A lost worker is a ``worker_lost`` -> migration,
  not an abort.

Every fault and recovery emits versioned obs events (``fault`` /
``recover`` / ``retry`` / ``degrade`` / ``abort`` / ``worker_lost`` /
``migrate_done`` / ``rebalance``); ``tools/trace_lint.py`` asserts
the pairings, and ``tests/test_resilience.py`` +
``tests/test_elastic.py`` assert every recovered/migrated run's
counts and discoveries are bit-identical to an unfaulted run. See the
Resilience and Elasticity sections of ARCHITECTURE.md.
"""

from .elastic import ElasticChecker, elastic_check
from .faults import (FAULT_POINTS, FAULTS_ENV, ExchangeIntegrityError,
                     FaultPlan, InjectedFault, InjectedOom, NULL_PLAN,
                     fault_plan_from_env, is_oom, reset_fault_plans)
from .membership import Membership, OwnerMap
from .supervisor import Supervisor, newest_valid_checkpoint, supervise

__all__ = [
    "FAULT_POINTS", "FAULTS_ENV", "ExchangeIntegrityError", "FaultPlan",
    "InjectedFault", "InjectedOom", "NULL_PLAN", "fault_plan_from_env",
    "is_oom", "reset_fault_plans",
    "Supervisor", "newest_valid_checkpoint", "supervise",
    "ElasticChecker", "elastic_check", "Membership", "OwnerMap",
]
