"""Chaos-hardened serving: deterministic fault injection + the
failure-model hardening it forces + a self-healing supervisor.

Three layers (see chaos.py / supervisor.py; the hardening itself
lives in the engine/scheduler/pools, keyed off ServingConfig knobs):

  * **fault injection** (chaos) — ``FaultPlan`` / ``FaultInjector``:
    seeded, deterministic failures at the engine's real seams
    (dispatches, transfers, pool exhaustion, compile storms, poisoned
    callbacks), each fire counted / marker-spanned / fault-logged so
    a chaos run replays from its seed. Armed via
    ``ServingConfig(chaos=...)`` or ``PADDLE_CHAOS``; off by default;
  * **hardening** — per-request deadlines (``add_request(...,
    deadline_ms=)``, timeout retirement SLO-judged), bounded
    dispatch retry (``max_dispatch_retries=`` — rollback via the
    PR-6 leak-free discipline, retried next step), slot quarantine
    after repeated same-slot failures (``quarantine_after=``,
    excluded from admission, visible in ``snapshot()["resilience"]``)
    and graceful drain (``engine.drain()``);
  * **supervisor** (supervisor.EngineSupervisor) — consumes wedge
    verdicts (queue stall, KV-block leak, dispatch failure past the
    retry budget) and performs an in-process restart: rebuilt AOT
    tables, fresh pools, in-flight requests re-queued for re-prefill
    with exact greedy replay; ``/debug/health`` reports ``degraded``
    until the replay drains, then ``healthy`` again.

``tools/chaos_sweep.py`` runs the seeded fault matrix as a CI gate.
"""
from .chaos import (  # noqa: F401
    DEFAULT_RATES, FAULT_SITES, FaultInjector, FaultPlan, FaultSpec,
    InjectedFault, resolve_chaos,
)
from .supervisor import RESTART_ON, EngineSupervisor  # noqa: F401
