"""Admission control, typed serving errors, and the arrival-rate EMA.

The overload-hardening layer of `QueryServer` (docs/architecture.md §10):

  * `AdmissionController` — a bounded pending-request budget with
    per-tenant fairness.  Requests carry an optional `tenant` and
    `priority`; a request past the budget (or past its tenant's fair
    share) is rejected with a typed `Overloaded` error *at submit time*
    instead of queueing unboundedly.  Priority > 0 requests bypass the
    tenant cap and may dip into a reserved headroom above the budget, so
    a latency-critical tenant still gets through a burst of bulk traffic.
  * typed errors — `Overloaded` (admission rejection), `DeadlineExceeded`
    (a request's deadline passed before its group executed), and
    `TransientError` (the retryable fault class: the server's bounded
    retry-with-backoff only replays a group whose failure is transient).
  * `RateEMA` — exponentially weighted arrival-interval tracker; drives
    the adaptive coalescing window.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Optional


class Overloaded(RuntimeError):
    """Admission rejected the request: the server's pending budget (or
    this tenant's fair share of it) is exhausted."""

    def __init__(self, message: str, *, tenant: Optional[str] = None,
                 reason: str = "budget"):
        super().__init__(message)
        self.tenant = tenant
        self.reason = reason        # 'budget' | 'fairness'


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed before its group executed."""


class TransientError(RuntimeError):
    """A fault the server may retry: the failed group is replayed (with
    backoff) against the same compiled entry.  Execution never mutates the
    window's request list, so the replay runs it as it was."""


@dataclasses.dataclass
class RateEMA:
    """EMA of inter-arrival times: `interval()` is the smoothed gap
    between requests, from which the server derives its coalescing-window
    length."""
    alpha: float = 0.1
    ema: float = 0.0
    count: int = 0
    last: Optional[float] = None

    def observe(self, now: float) -> None:
        if self.last is None:
            self.last = now
            return
        dt = max(now - self.last, 1e-9)
        self.last = now
        self.ema = dt if self.count == 0 \
            else (1.0 - self.alpha) * self.ema + self.alpha * dt
        self.count += 1

    def interval(self) -> Optional[float]:
        return self.ema if self.count else None

    def rate(self) -> float:
        """Smoothed arrivals per second (0.0 until two arrivals seen)."""
        return 1.0 / self.ema if self.count else 0.0


class AdmissionController:
    """Bounded pending budget with per-tenant fairness and priorities.

    Contract (docs §10):

      * at most `budget` requests may be pending (admitted, future not yet
        resolved) at once; request `budget + 1` is rejected with
        `Overloaded(reason='budget')`;
      * a named tenant may hold at most `ceil(tenant_frac * budget)`
        pending slots, so one bulk tenant cannot starve the others even
        below the global budget — excess is rejected with
        `Overloaded(reason='fairness')`.  Anonymous requests
        (`tenant=None`) are exempt from the per-tenant cap and bounded
        only by the global budget;
      * `priority > 0` requests bypass the tenant cap and may use a
        reserved `headroom` above the budget (default budget/4), so
        latency-critical traffic is the last to be shed.

    Thread-safe: `admit`/`release` take an internal lock (releases run on
    future done-callbacks, i.e. arbitrary threads).
    """

    def __init__(self, budget: int = 256, tenant_frac: float = 0.5,
                 headroom: Optional[int] = None):
        if budget < 1:
            raise ValueError(f"budget must be >= 1 (got {budget})")
        self.budget = budget
        self.tenant_cap = max(1, math.ceil(tenant_frac * budget))
        self.headroom = budget // 4 if headroom is None else headroom
        self._lock = threading.Lock()
        self._pending = 0
        self._per_tenant: dict[Optional[str], int] = {}

    def admit(self, tenant: Optional[str] = None, priority: int = 0) -> int:
        """Claim one pending slot (returns the pre-admission pending
        count) or raise `Overloaded`.  Callers MUST pair every successful
        admit with exactly one `release` — the server wires it to the
        request future's done-callback, which fires on every resolution
        path (result, error, rejection at close)."""
        with self._lock:
            limit = self.budget + (self.headroom if priority > 0 else 0)
            if self._pending >= limit:
                raise Overloaded(
                    f"pending budget exhausted ({self._pending} >= {limit})",
                    tenant=tenant, reason="budget")
            if tenant is not None and priority <= 0 and \
                    self._per_tenant.get(tenant, 0) >= self.tenant_cap:
                raise Overloaded(
                    f"tenant {tenant!r} at its fair share "
                    f"({self.tenant_cap} of {self.budget})",
                    tenant=tenant, reason="fairness")
            before = self._pending
            self._pending += 1
            self._per_tenant[tenant] = self._per_tenant.get(tenant, 0) + 1
            return before

    def release(self, tenant: Optional[str] = None) -> None:
        with self._lock:
            self._pending = max(self._pending - 1, 0)
            n = self._per_tenant.get(tenant, 0) - 1
            if n <= 0:
                self._per_tenant.pop(tenant, None)
            else:
                self._per_tenant[tenant] = n

    def load(self) -> float:
        """Current pending fraction of the budget (>= 1.0 = saturated).
        The degradation ladder keys its rungs off this value."""
        with self._lock:
            return self._pending / self.budget

    def pending(self) -> int:
        with self._lock:
            return self._pending
