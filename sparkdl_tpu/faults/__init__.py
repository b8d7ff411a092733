"""sparkdl_tpu.faults — deterministic fault injection for the scoring
stack.

The reference leaned on Spark's task-retry/straggler machinery for
resilience (SURVEY.md §5; ``utils/retry`` names the analogy); this
package is the other half of that story: a way to PROVE what the
engine, pipeline, serving, and host-I/O layers do when the device or a
worker thread dies mid-flight — without waiting for real hardware to do
it.

* :class:`FaultPlan` — a seeded, deterministic set of rules, parsed
  from a ``SPARKDL_FAULTS`` spec string (grammar in
  :mod:`~sparkdl_tpu.faults.spec`) or constructed directly in tests.
* :func:`inject` — the hook threaded through the hot paths at named
  sites (:data:`~sparkdl_tpu.faults.spec.SITES`).  With no plan active
  it is one global read + ``None`` check (near-zero, the
  ``SPARKDL_TRACE`` disabled-path budget, guarded by run-tests.sh).
* The error classes (:mod:`~sparkdl_tpu.faults.errors`): transient
  (retryable), fatal/decode (deterministic, ``NON_RETRYABLE``), dead
  (sticky — the circuit-breaker trigger).

Quick use::

    from sparkdl_tpu import faults

    plan = faults.FaultPlan.parse(
        "seed=7;engine.dispatch:error:exc=transient,at=2")
    with faults.active(plan):
        run_workload()
    assert plan.fired("engine.dispatch") == 1

or, process-wide, ``SPARKDL_FAULTS="seed=7;engine.dispatch:error:at=2"``.
"""

from sparkdl_tpu.faults.errors import (InjectedDeadDeviceError,
                                       InjectedDecodeError, InjectedFault,
                                       InjectedFatalError,
                                       InjectedTransientError)
from sparkdl_tpu.faults.plan import (FaultPlan, active, clear, configure,
                                     configure_from_env, current_spec,
                                     get_plan, has_rules, inject)
from sparkdl_tpu.faults.sites import SITE_HELP, validate_site
from sparkdl_tpu.faults.spec import (ACTIONS, SITES, FaultRule,
                                     faults_from_env, format_spec,
                                     parse_spec)

__all__ = [
    "FaultPlan",
    "FaultRule",
    "SITES",
    "SITE_HELP",
    "validate_site",
    "ACTIONS",
    "inject",
    "has_rules",
    "active",
    "configure",
    "configure_from_env",
    "clear",
    "get_plan",
    "current_spec",
    "parse_spec",
    "format_spec",
    "faults_from_env",
    "InjectedFault",
    "InjectedTransientError",
    "InjectedDeadDeviceError",
    "InjectedFatalError",
    "InjectedDecodeError",
]
