"""Request-lifecycle resilience: timeouts, retries with backoff, shedding.

The paper's clients are infinitely patient: no call ever times out, retries,
or is refused, so the sim cannot reproduce the regime where overload becomes
*self-sustaining* (burst -> timeouts -> client retries -> more load -> more
timeouts -- the metastable failure mode of real serving fleets).  This
module makes client/controller resilience a first-class, *declarative*
scenario consumed by both engines:

* :class:`TimeoutSpec` -- a per-request deadline armed at controller
  receive, either ``multiple x max(E[p], floor_s)`` (the same last-10
  controller estimate hedging uses) or absolute.  A *queued* timeout
  cancels the call on its node; a *running* timeout frees the slot and the
  elapsed execution counts as ``wasted_work``.
* :class:`RetryPolicy` -- client retry behavior: up to ``max_attempts``
  submissions, re-arriving either immediately or after capped exponential
  backoff with deterministic per-(request, attempt) jitter (a pure integer
  hash of the request's arrival rank and the attempt number, so both
  engines -- and any worker count -- reproduce the exact same retry
  schedule).  ``retry_on`` selects which fates re-arrive: timeouts, shed
  responses, and/or kill-lost calls.
* :class:`AdmissionPolicy` -- controller-side load shedding: refuse a call
  on arrival when the estimated wait -- total queued E[p] per free slot,
  reusing the estimator rings -- exceeds ``threshold_s``.  Shed responses
  feed the retry path, which is exactly how real retry storms couple.

The reference :class:`~repro.core.cluster.Cluster` implements this with
deadline watch events and backoff re-arrivals on the event loop; the scan
kernel carries a ``res`` feature segment (timeout watches, retry
re-arrival clocks, a queued-E[p] accumulator for shed decisions -- float64
buckets) with bit-identical ``timed_out`` / ``shed`` / ``retries_issued``
accounting.

Pure data + arithmetic: no simulator imports, so both engines (and the
sweep layer) can depend on it without cycles.
"""

from __future__ import annotations

import math

from dataclasses import dataclass

RETRY_MODES = ("immediate", "backoff")
RETRY_CAUSES = ("timeout", "shed", "kill")


def retry_jitter_u(seq: int, attempt: int) -> float:
    """Deterministic jitter draw in [0, 1) for retry ``attempt`` of the
    request with stable arrival rank ``seq``.

    A pure integer hash (no RNG state), chosen so the scan kernel can
    evaluate the *identical* value in int32 arithmetic inside the step:
    products stay far below 2^31 for any realistic burst, and the result
    is a 16-bit integer over 65536 -- exactly representable in float, so
    reference-python and jnp-float64 agree bit-for-bit.  Keep in sync with
    the ``res`` segment of ``fastpath._cell_step``."""
    h = (seq * 7919 + attempt * 104729 + 12345) % 65536
    return h / 65536.0


@dataclass(frozen=True)
class TimeoutSpec:
    """Per-request deadline armed when the controller receives the call.

    ``deadline = now + multiple x max(E[p], floor_s)`` with the
    controller-side last-10 estimate (the same ring hedging reads), or
    ``now + absolute_s`` when ``absolute_s`` is set (absolute wins).  A
    queued timeout cancels the call on its node; a running timeout frees
    the slot mid-execution and the elapsed time counts as wasted work.
    """

    multiple: float = 4.0
    floor_s: float = 0.5
    absolute_s: float | None = None

    def __post_init__(self) -> None:
        if self.absolute_s is not None:
            if not (self.absolute_s > 0 and math.isfinite(self.absolute_s)):
                raise ValueError(f"absolute timeout must be finite > 0, "
                                 f"got {self.absolute_s}")
        if not (self.multiple > 0):
            raise ValueError(f"timeout multiple must be > 0, "
                             f"got {self.multiple}")
        if self.floor_s < 0:
            raise ValueError(f"timeout floor must be >= 0, "
                             f"got {self.floor_s}")

    def deadline(self, now: float, estimate: float) -> float:
        """When the watch armed at ``now`` fires."""
        if self.absolute_s is not None:
            return now + self.absolute_s
        return now + self.multiple * max(estimate, self.floor_s)


@dataclass(frozen=True)
class RetryPolicy:
    """Client retry behavior for timed-out / shed / kill-lost calls.

    A request may be submitted at most ``max_attempts`` times in total
    (first submission included).  ``mode="immediate"`` re-arrives at the
    failure instant -- the naive client that fuels retry storms;
    ``mode="backoff"`` waits ``min(cap_delay_s, base_delay_s * 2^(a-1))``
    after failed attempt ``a``, scaled by ``(1 - jitter) + jitter * u``
    with the deterministic draw :func:`retry_jitter_u`.
    """

    max_attempts: int = 3
    mode: str = "backoff"
    base_delay_s: float = 0.5
    cap_delay_s: float = 8.0
    jitter: float = 0.5
    retry_on: tuple[str, ...] = ("timeout", "shed", "kill")

    def __post_init__(self) -> None:
        object.__setattr__(self, "retry_on",
                           tuple(str(c) for c in self.retry_on))
        if not (1 <= self.max_attempts <= 16):
            raise ValueError(f"max_attempts must be in [1, 16], "
                             f"got {self.max_attempts}")
        if self.mode not in RETRY_MODES:
            raise ValueError(f"unknown retry mode {self.mode!r}; "
                             f"available: {RETRY_MODES}")
        if self.base_delay_s < 0 or self.cap_delay_s < 0:
            raise ValueError("base/cap delay must be >= 0")
        if not (0.0 <= self.jitter <= 1.0):
            raise ValueError(f"jitter must be in [0, 1], got {self.jitter}")
        for c in self.retry_on:
            if c not in RETRY_CAUSES:
                raise ValueError(f"unknown retry cause {c!r}; "
                                 f"available: {RETRY_CAUSES}")

    def retries(self, cause: str) -> bool:
        """Does this policy retry a failure of ``cause``?"""
        return cause in self.retry_on

    def should_retry(self, cause: str, attempt: int) -> bool:
        """May failed submission number ``attempt`` (1-based) re-arrive?"""
        return self.retries(cause) and attempt < self.max_attempts

    def delay(self, seq: int, attempt: int) -> float:
        """Backoff delay after failed submission ``attempt`` (1-based) of
        the request with stable arrival rank ``seq``.  Mirrored term-for-
        term by the scan kernel's ``res`` segment: the power of two is an
        exact integer shift and the jitter draw an exact 16-bit fraction,
        so both engines compute bit-identical re-arrival times."""
        if self.mode == "immediate":
            return 0.0
        base = min(self.cap_delay_s,
                   self.base_delay_s * float(1 << (attempt - 1)))
        u = retry_jitter_u(seq, attempt)
        return base * ((1.0 - self.jitter) + self.jitter * u)


@dataclass(frozen=True)
class AdmissionPolicy:
    """Controller-side load shedding on estimated wait.

    An arriving (or re-arriving) call is refused when
    ``queued_ep / max(free_slots, 1) > threshold_s``, where ``queued_ep``
    is the sum of controller E[p] snapshots of every currently-queued call
    (each snapshot taken once at its enqueue, removed at dispatch or
    cancel -- so both engines accumulate in the identical event order) and
    ``free_slots`` the fleet's total idle cores.  Shed responses feed the
    retry path."""

    threshold_s: float = 2.0

    def __post_init__(self) -> None:
        if not (self.threshold_s >= 0 and math.isfinite(self.threshold_s)):
            raise ValueError(f"shed threshold must be finite >= 0, "
                             f"got {self.threshold_s}")

    def shed(self, queued_ep: float, free_slots: int) -> bool:
        return queued_ep / max(free_slots, 1) > self.threshold_s


@dataclass(frozen=True)
class ResilienceSpec:
    """Bundle of the three lifecycle policies; any subset may be active.

    ``ResilienceSpec()`` with all three ``None`` is the null spec --
    :func:`ResilienceSpec.from_any` collapses it to ``None`` so engine
    code can branch on ``spec is None``."""

    timeout: TimeoutSpec | None = None
    retry: RetryPolicy | None = None
    admission: AdmissionPolicy | None = None

    @property
    def is_null(self) -> bool:
        return (self.timeout is None and self.retry is None
                and self.admission is None)

    @property
    def max_attempts(self) -> int:
        return self.retry.max_attempts if self.retry is not None else 1

    @classmethod
    def from_any(cls, spec) -> "ResilienceSpec | None":
        """Normalize loose inputs (None, a spec, or one of the three
        component policies) to a non-null ``ResilienceSpec`` or ``None``."""
        if spec is None:
            return None
        if isinstance(spec, cls):
            return None if spec.is_null else spec
        if isinstance(spec, TimeoutSpec):
            return cls(timeout=spec)
        if isinstance(spec, RetryPolicy):
            return cls(retry=spec)
        if isinstance(spec, AdmissionPolicy):
            return cls(admission=spec)
        raise TypeError(f"cannot build ResilienceSpec from {spec!r}")

    # -- tensor form (scan kernel) ------------------------------------------
    def arrays(self):
        """``(timeout4, retry6, adm2)`` float64 parameter rows for one scan
        bucket cell: ``timeout4 = [on, multiple, floor, absolute]``
        (absolute <= 0 means estimate-multiple), ``retry6 = [max_attempts,
        base, cap, jitter, on_timeout, on_shed]``, ``adm2 = [on,
        threshold]``.  Immediate mode encodes as base = cap = 0 (delay
        collapses to 0 exactly)."""
        import numpy as np
        to = self.timeout
        rt = self.retry
        ad = self.admission
        t4 = np.zeros(4, dtype=np.float64)
        if to is not None:
            t4[:] = (1.0, to.multiple, to.floor_s,
                     to.absolute_s if to.absolute_s is not None else 0.0)
        r6 = np.zeros(6, dtype=np.float64)
        r6[0] = 1.0
        if rt is not None:
            backoff = rt.mode == "backoff"
            r6[:] = (float(rt.max_attempts),
                     rt.base_delay_s if backoff else 0.0,
                     rt.cap_delay_s if backoff else 0.0,
                     rt.jitter if backoff else 0.0,
                     1.0 if rt.retries("timeout") else 0.0,
                     1.0 if rt.retries("shed") else 0.0)
        a2 = np.zeros(2, dtype=np.float64)
        if ad is not None:
            a2[:] = (1.0, ad.threshold_s)
        return t4, r6, a2
