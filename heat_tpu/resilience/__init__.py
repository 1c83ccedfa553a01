"""Resilience subsystem: durable sharded state + runtime guards + chaos.

The paper's SPMD execution model (every rank runs the same script,
collectives fire eagerly inside ops) has no recovery story: one failed
host, torn file write, hung reshard, or silently diverged replica poisons
the whole computation. This package adds the production-side
counterweights, split into a *storage* path and a *runtime* path:

Storage (PR 1):

- :mod:`~heat_tpu.resilience.checkpoint` — sharded, checksummed, atomic
  ``save_checkpoint`` / ``load_checkpoint`` with restore-onto-any-mesh;
- :mod:`~heat_tpu.resilience.retry` — :class:`RetryPolicy` exponential
  backoff + jitter, wired into ``core.io`` and checkpoint I/O;
- :mod:`~heat_tpu.resilience.validate` — runtime invariant validation
  (``resilience.validate(x)`` / ``DNDarray.health_check()``).

Runtime guards (PR 2):

- :mod:`~heat_tpu.resilience.guard` — replica-divergence detection:
  ``fingerprint(x)`` per-shard checksums + cross-replica digests,
  ``guarded(...)`` op-boundary verification raising
  :class:`DivergenceError` naming the offending devices;
- :mod:`~heat_tpu.resilience.watchdog` — collective watchdog:
  ``with_deadline(fn, timeout, label)`` and the fleet-wide
  ``deadlines(timeout)`` context bound the blocking host-side
  resharding/assembly paths, raising :class:`CollectiveTimeout` instead
  of hanging;
- :mod:`~heat_tpu.resilience.degrade` — graceful degradation:
  ``mark_unhealthy`` / ``probe`` / ``shrink_to_healthy`` rebuild the
  mesh over the surviving devices and redistribute live arrays (elastic
  restore logic), so a bad device means a smaller mesh, not a dead job.

Supervised execution (PR 6):

- :mod:`~heat_tpu.resilience.supervisor` — the self-healing loop that
  composes all of the above: :class:`Supervisor` /
  :func:`supervise` drive any iterative workload as a checkpointed step
  loop (:class:`CheckpointSchedule` cadence + keep-last-k retention)
  with a fault-classification policy — transient I/O retried, divergence
  and collective timeouts restored from the last good checkpoint, lost
  devices recovered by probe + shrink + elastic restore onto the
  surviving mesh. Recovery activity is counted in
  :data:`RECOVERY_STATS`.

Proactive health + elastic capacity (PR 17):

- :mod:`~heat_tpu.resilience.monitor` — :class:`HealthMonitor` probe
  ticks on a replicated cadence keep a per-device health ledger with
  EWMA straggler detection and flap damping; a damped-then-healed
  device is re-admitted by :func:`~heat_tpu.resilience.grow_to_healthy`
  (the inverse of shrink), so capacity comes BACK. Counters in
  :data:`HEALTH_STATS`.

Chaos (:mod:`~heat_tpu.resilience.chaos`) injects every failure class
deterministically — I/O errors, torn writes, silent corruption,
timeouts, stragglers, replica divergence, device loss — either
probabilistically (:class:`chaos`) or as an exact scripted
:class:`FaultSchedule`, so all of the above is testable on CPU.

Every guard-layer failure derives from :class:`ResilienceError`
(:mod:`~heat_tpu.resilience.errors`); see ``docs/RESILIENCE.md`` for the
failure classes, manifest format, and chaos recipes.
"""
from . import chaos as _chaos_mod  # noqa: F401
from .chaos import FaultSchedule, Injection, chaos
from .checkpoint import (
    CHECKPOINT_FORMAT,
    CheckpointCorruptionError,
    CheckpointError,
    MANIFEST_NAME,
    load_checkpoint,
    read_manifest,
    save_checkpoint,
)
from .degrade import (
    clear_unhealthy,
    grow_to_healthy,
    healthy_devices,
    mark_unhealthy,
    probe,
    shrink_to_healthy,
    unhealthy_devices,
)
from .errors import (
    CollectiveTimeout,
    DegradeError,
    DivergenceError,
    LockstepError,
    NoHealthyDevicesError,
    PoisonRequestError,
    ResilienceError,
    ServeDeadlineError,
    ServeError,
    ServeOverloadError,
)
from .guard import Fingerprint, Guard, fingerprint, guarded
from .guard import check as check_divergence
from .monitor import (
    HEALTH_STATS,
    DeviceHealth,
    HealthMonitor,
    TickReport,
    reset_health_stats,
)
from .retry import DEFAULT_CHECKPOINT_POLICY, NO_RETRY, RetryError, RetryPolicy
from .supervisor import (
    RECOVERY_STATS,
    CheckpointSchedule,
    Supervisor,
    SupervisorError,
    SupervisorResult,
    reset_recovery_stats,
    supervise,
)
from .validate import ValidationError, validate
from .watchdog import deadlines, with_deadline

__all__ = [
    # chaos
    "chaos",
    "Injection",
    "FaultSchedule",
    # checkpoint
    "save_checkpoint",
    "load_checkpoint",
    "read_manifest",
    "CheckpointError",
    "CheckpointCorruptionError",
    "CHECKPOINT_FORMAT",
    "MANIFEST_NAME",
    # retry
    "RetryPolicy",
    "RetryError",
    "NO_RETRY",
    "DEFAULT_CHECKPOINT_POLICY",
    # validation
    "validate",
    "ValidationError",
    # error hierarchy
    "ResilienceError",
    "DivergenceError",
    "CollectiveTimeout",
    "LockstepError",
    "DegradeError",
    "NoHealthyDevicesError",
    "ServeError",
    "ServeOverloadError",
    "ServeDeadlineError",
    "PoisonRequestError",
    # guard
    "fingerprint",
    "Fingerprint",
    "Guard",
    "guarded",
    "check_divergence",
    # watchdog
    "with_deadline",
    "deadlines",
    # degrade
    "mark_unhealthy",
    "clear_unhealthy",
    "unhealthy_devices",
    "healthy_devices",
    "probe",
    "shrink_to_healthy",
    "grow_to_healthy",
    # health monitor
    "HealthMonitor",
    "DeviceHealth",
    "TickReport",
    "HEALTH_STATS",
    "reset_health_stats",
    # supervisor
    "Supervisor",
    "SupervisorError",
    "SupervisorResult",
    "supervise",
    "CheckpointSchedule",
    "RECOVERY_STATS",
    "reset_recovery_stats",
]
