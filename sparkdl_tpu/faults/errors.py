"""Injected-fault error classes.

Every exception the fault harness raises is a distinct type so the code
under test can be asserted to ROUTE it correctly: transient faults must
be retried (``utils.retry`` treats :class:`InjectedTransientError` like
any retryable runtime error), deterministic faults must fail fast
(:class:`InjectedFatalError` / :class:`InjectedDecodeError` subclass
``ValueError``, which sits in ``utils.retry.NON_RETRYABLE``), and a
sticky dead device (:class:`InjectedDeadDeviceError`) must eventually
trip the engine's circuit breaker rather than retry forever.

All carry ``site`` (the injection point that fired) and ``rule`` (the
canonical spec clause), so a chaos-test failure message names exactly
which planned fault produced it.
"""

from __future__ import annotations


class InjectedFault(RuntimeError):
    """Base class of every fault the harness injects."""

    def __init__(self, message: str, site: str = "", rule: str = ""):
        super().__init__(message)
        self.site = site
        self.rule = rule


class InjectedTransientError(InjectedFault):
    """A one-off device/runtime hiccup: the retryable kind (plain
    ``RuntimeError`` lineage, so retry budgets see it as transient)."""


class InjectedDeadDeviceError(InjectedFault):
    """A sticky device death: once a ``dead`` rule fires, EVERY later
    call at its site raises this — the repeated-identical-failure
    pattern circuit breakers exist to cut short."""


class InjectedFatalError(InjectedFault, ValueError):
    """A deterministic failure (bad shapes/params): subclasses
    ``ValueError`` so ``utils.retry.NON_RETRYABLE`` fails it fast —
    retrying would reproduce the identical error."""


class InjectedDecodeError(InjectedFault, ValueError):
    """A corrupt-input decode failure mid-stream; the host I/O layer's
    drop-to-null contract must absorb it row-wise, never kill the
    stream."""
