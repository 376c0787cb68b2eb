"""Call-level retry for transient failures.

``retry_with_backoff`` retries a call with exponential backoff when it
raises a retryable error (flaky I/O, a preempted worker); the selection
service wraps each engine run in it so one wobble never fails a job.
"""

from __future__ import annotations

import logging
import time
from typing import Callable

logger = logging.getLogger("repro.resilience")


class TransientError(RuntimeError):
    """A failure expected to succeed on retry (flaky I/O, preemption).

    Raise it — or pass your own exception types via ``retry_on`` — to mark
    work as retryable; anything else propagates immediately.
    """


def retry_with_backoff(
    fn: Callable[[], object],
    *,
    max_attempts: int = 3,
    base_delay_s: float = 0.1,
    max_delay_s: float = 30.0,
    backoff: float = 2.0,
    retry_on=(TransientError,),
    on_retry: Callable[[int, BaseException, float], None] | None = None,
    sleep: Callable[[float], None] = time.sleep,
):
    """Call ``fn()``; on a retryable exception, back off and re-call.

    Delay before attempt ``k+1`` is ``min(base * backoff**(k-1), max)``.
    Non-retryable exceptions — and the last retryable one once
    ``max_attempts`` calls have failed — propagate to the caller.
    ``on_retry(attempt, exc, delay_s)`` observes each retry (the selection
    service uses it to count attempts per job); ``sleep`` is injectable
    for tests.
    """
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")
    attempt = 1
    while True:
        try:
            return fn()
        except retry_on as e:
            if attempt >= max_attempts:
                raise
            delay = min(base_delay_s * backoff ** (attempt - 1), max_delay_s)
            logger.warning(
                "transient failure (attempt %d/%d), retrying in %.2fs: %s",
                attempt, max_attempts, delay, e,
            )
            if on_retry is not None:
                on_retry(attempt, e, delay)
            sleep(delay)
            attempt += 1

