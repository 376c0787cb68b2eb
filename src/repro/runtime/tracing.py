"""Named spans of the streamed fit, on the JAX profiler's clock.

Every layer boundary of a streamed fit opens a
:class:`jax.profiler.TraceAnnotation` named ``mrmr.<layer>``.  With no
profiler session open a span costs about a microsecond of host time; under
``jax.profiler.trace`` (or ``start_trace``) the spans land in the same
trace as the device's operations, so idle time on the chip can be named
by what the program was doing.

Each span carries the ids of the work it belongs to as arguments: ``fit``
(a process-wide fit counter), ``pass`` (the scoring pass within the fit,
0 = relevance) and ``block`` (the block within the pass).  Spans opened on
the staging and read-ahead threads carry the ids of the pass that caused
them, so a trace can be cut by fit and pass whatever thread did the work.

The spans, outermost first:

* ``mrmr.fit``: one front-door fit of a source, planning to result;
* ``mrmr.plan``: the front door before the engine (score and its stats
  scan, plan, mesh); and, where the front door left the default score to
  the engine, the engine sizing it: the reduce over the blocks it keeps on
  the device and its copy to the host, or the stats scan where it streams;
* ``mrmr.pass``: one scoring pass (args ``kind``, ``batch`` and
  ``resident``, 1 where the pass counts from device-resident blocks and
  reads nothing);
* ``mrmr.read``: one raw block read from the source, on whichever thread
  reads;
* ``mrmr.stage``: target extraction, pad and mask of one block, the host
  half of placement;
* ``mrmr.cut``: in a pass counted from device-resident blocks, the
  dispatch of one block's target cut on the device, in place of the
  stage and the transfers;
* ``mrmr.feed_wait``: the consumer blocked on the staging or read-ahead
  thread;
* ``mrmr.place``: the host-to-device transfers of one staged block;
* ``mrmr.accumulate``: host dispatch of one block's accumulate, which
  returns before the device is done;
* ``mrmr.finalize``: one pass's finalize and the copy of its scores to
  the host;
* ``mrmr.pick``: one greedy step (arg ``pick``): the fold of the last
  pick's redundancy, the objective, its copy to the host and the argmax.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools

import jax

FIT = "mrmr.fit"
PLAN = "mrmr.plan"
PASS = "mrmr.pass"
READ = "mrmr.read"
STAGE = "mrmr.stage"
FEED_WAIT = "mrmr.feed_wait"
PLACE = "mrmr.place"
CUT = "mrmr.cut"
ACCUMULATE = "mrmr.accumulate"
FINALIZE = "mrmr.finalize"
PICK = "mrmr.pick"
SPANS = (
    FIT, PLAN, PASS, READ, STAGE, FEED_WAIT, PLACE, CUT, ACCUMULATE,
    FINALIZE, PICK,
)

_FIT_IDS = itertools.count(1)
_OPEN_FIT: contextvars.ContextVar = contextvars.ContextVar(
    "mrmr_open_fit", default=None
)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A span named ``name`` with ``ids`` as its arguments."""
    return jax.profiler.TraceAnnotation(name, **ids)


@contextlib.contextmanager
def fit_span():
    """Open ``mrmr.fit`` under a fresh fit id; yields the id.  An engine
    run inside it takes the same id (:func:`fit_id`)."""
    fit = next(_FIT_IDS)
    token = _OPEN_FIT.set(fit)
    try:
        with span(FIT, fit=fit):
            yield fit
    finally:
        _OPEN_FIT.reset(token)


def fit_id() -> int:
    """The id of the fit open in this context, or a fresh one where none
    is (an engine called directly, not through the front door)."""
    fit = _OPEN_FIT.get()
    return next(_FIT_IDS) if fit is None else fit


def traced_reads(blocks, rows: int, **ids):
    """``blocks``, an iterator of ``(X, y)`` host blocks covering ``rows``
    rows, with each read in a ``mrmr.read`` span (``ids`` plus the block's
    index).  The call that ends the iterator reads nothing and opens no
    span, so the spans count the blocks read."""
    it = iter(blocks)
    seen = 0
    for block in itertools.count():
        if seen >= rows:
            break
        with span(READ, block=block, **ids):
            item = next(it, None)
        if item is None:
            return
        seen += item[0].shape[0]
        yield item
    yield from it
