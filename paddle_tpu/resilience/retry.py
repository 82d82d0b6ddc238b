"""retry — deadline + exponential-backoff-with-jitter retry engine.

The reference stack assumed flaky transports everywhere (pserver RPC
retries, grpc deadlines, brpc backup requests); the jax_graft rebuild
talks to coordinators, remote services and shared filesystems that
flake the same way. This is the ONE policy object the rest of the repo wraps
those seams with — fleet init/barrier, telemetry spool I/O, inference
compile — instead of ad-hoc sleep loops.

Semantics:

- `RetryPolicy(max_attempts, base_delay_s, multiplier, max_delay_s,
  jitter, deadline_s)` — attempt k (1-based) sleeps
  `min(base * multiplier**(k-1), max_delay) * U(1-jitter, 1+jitter)`
  before attempt k+1. `deadline_s` bounds the WHOLE call (attempts +
  sleeps): a retry never starts past the deadline.
- Typed classification: raise `Fatal` (or wrap your exception) to stop
  retrying immediately; `Retryable` always retries. Anything else goes
  through the policy's `classify` predicate — the default
  (`transient`) retries OS/connection/timeout errors and messages that
  smell like transport flake (UNAVAILABLE, DEADLINE_EXCEEDED, ...),
  and refuses everything else, so wrapping a seam never turns a real
  bug into a silent 5x slowdown.
- Telemetry: `resilience.retry.attempts` / `.retries` / `.giveups`
  counters plus a `resilience.retry` span per sleep, tagged with the
  call's `name` — visible in tpustat like every other subsystem.

`sleep` and `rng` are injectable for deterministic tests (the backoff
timing-bounds test records the exact delays instead of sleeping).
"""
import random
import time

from .. import telemetry as _tm

__all__ = ["Retryable", "Fatal", "RetryError", "RetryPolicy",
           "call", "retryable", "transient", "DEFAULT_POLICY"]


class Retryable(Exception):
    """Always retried (until attempts/deadline run out)."""


class Fatal(Exception):
    """Never retried — stop immediately and re-raise the cause."""


class RetryError(RuntimeError):
    """Attempts/deadline exhausted. `last` is the final exception,
    `attempts` how many were made."""

    def __init__(self, name, attempts, last, why):
        self.name = name
        self.attempts = attempts
        self.last = last
        super().__init__(
            f"{name}: gave up after {attempts} attempt(s) ({why}): "
            f"{type(last).__name__}: {last}")


_TRANSIENT_MARKERS = ("unavailable", "deadline_exceeded", "deadline "
                      "exceeded", "connection reset", "connection "
                      "refused", "temporarily unavailable", "timed out",
                      "timeout", "broken pipe", "try again",
                      # elastic re-form: while every surviving rank
                      # tears down and rebinds, jax.distributed
                      # .initialize races the coordinator's restart —
                      # failed-to-connect and the old socket lingering
                      # in TIME_WAIT are transport flake, not bugs
                      "address already in use", "failed to connect",
                      "coordination service")


def transient(exc):
    """Default classifier: is `exc` worth retrying? Typed markers win;
    otherwise OS-level transport errors and transport-smelling messages
    retry, everything else (real bugs) does not."""
    if isinstance(exc, Fatal):
        return False
    if isinstance(exc, Retryable):
        return True
    # programming errors are never transport flake, whatever the
    # message smells like — a TypeError from calling
    # jax.distributed.initialize wrong must surface on attempt 1, not
    # eat the retry budget during an elastic re-form
    if isinstance(exc, (TypeError, AttributeError, NameError)):
        return False
    if isinstance(exc, (ConnectionError, TimeoutError, InterruptedError,
                        BrokenPipeError)):
        return True
    if isinstance(exc, OSError):
        return True
    msg = str(exc).lower()
    return any(m in msg for m in _TRANSIENT_MARKERS)


class RetryPolicy:
    """One resolved retry policy (see module docstring)."""

    def __init__(self, max_attempts=3, base_delay_s=0.1, multiplier=2.0,
                 max_delay_s=5.0, jitter=0.25, deadline_s=None,
                 classify=transient):
        if max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if base_delay_s < 0 or max_delay_s < 0:
            raise ValueError("delays must be >= 0")
        if not 0.0 <= jitter < 1.0:
            raise ValueError("jitter must be in [0, 1)")
        self.max_attempts = int(max_attempts)
        self.base_delay_s = float(base_delay_s)
        self.multiplier = float(multiplier)
        self.max_delay_s = float(max_delay_s)
        self.jitter = float(jitter)
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.classify = classify

    def backoff(self, attempt, rng=None):
        """Sleep before attempt+1, given `attempt` just failed
        (1-based). Deterministic when jitter == 0."""
        d = min(self.base_delay_s * self.multiplier ** (attempt - 1),
                self.max_delay_s)
        if self.jitter:
            d *= 1.0 + self.jitter * (2.0 * (rng or random).random() - 1.0)
        return d

    def __repr__(self):
        return (f"RetryPolicy(max_attempts={self.max_attempts}, "
                f"base_delay_s={self.base_delay_s}, "
                f"multiplier={self.multiplier}, "
                f"max_delay_s={self.max_delay_s}, "
                f"jitter={self.jitter}, deadline_s={self.deadline_s})")


DEFAULT_POLICY = RetryPolicy()


def call(fn, *args, policy=None, name="call", on_retry=None,
         sleep=time.sleep, rng=None, clock=time.monotonic, **kwargs):
    """Run `fn(*args, **kwargs)` under `policy`. Returns fn's value or
    raises RetryError (from the last exception) / the cause directly
    when it is Fatal-classified on the first attempt's failure path."""
    policy = policy or DEFAULT_POLICY
    tm_on = _tm.enabled()
    start = clock()
    attempt = 0
    while True:
        attempt += 1
        if tm_on:
            _tm.counter("resilience.retry.attempts").inc()
        try:
            return fn(*args, **kwargs)
        except Exception as e:            # noqa: BLE001 — classified below
            cause = e.__cause__ if isinstance(e, Fatal) and e.__cause__ \
                else e
            if not policy.classify(e):
                if tm_on:
                    _tm.counter("resilience.retry.fatal").inc()
                raise
            if attempt >= policy.max_attempts:
                if tm_on:
                    _tm.counter("resilience.retry.giveups").inc()
                raise RetryError(name, attempt, cause,
                                 "attempts exhausted") from e
            delay = policy.backoff(attempt, rng=rng)
            if policy.deadline_s is not None and \
                    clock() - start + delay > policy.deadline_s:
                if tm_on:
                    _tm.counter("resilience.retry.giveups").inc()
                raise RetryError(name, attempt, cause,
                                 f"deadline {policy.deadline_s}s "
                                 "exceeded") from e
            if tm_on:
                _tm.counter("resilience.retry.retries").inc()
            if on_retry is not None:
                on_retry(attempt, e, delay)
            with _tm.span("resilience.retry", call=name,
                          attempt=attempt, delay_s=round(delay, 4)):
                sleep(delay)


def retryable(policy=None, name=None):
    """Decorator form of call()."""
    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            return call(fn, *args, policy=policy,
                        name=name or fn.__name__, **kwargs)
        return wrapped
    return deco
