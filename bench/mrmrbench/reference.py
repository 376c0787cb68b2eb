"""Plain reference for greedy mRMR on discrete data, and the comparison that
decides ``correct``.

Nothing here imports the program.  Counts are exact integers, taken from
the dataset files in row blocks; mutual information is then computed in
float64 (or, for the control, with every operation rounded to bfloat16).

Definitions (natural logarithms throughout):

* ``rel[f] = I(x_f; y)``, the plug-in estimate from the counts;
* ``red[s][f] = I(x_f; x_s)`` and ``cond[s][f] = I(x_f; x_s | y) =
  sum_c p(c) I(x_f; x_s | y = c)``;
* objective at step ``l`` over the picks ``s_0 .. s_{l-1}``, with
  ``d = max(l, 1)``:
  ``mid``: ``rel - sum_i red[s_i] / d``;
  ``jmi``: ``rel + sum_i (cond[s_i] - red[s_i]) / d``;
  picked features are out of the running, and a greedy fit takes the
  argmax (lowest id among ties).

The comparison is teacher-forced: at each step the reference scores every
candidate given the picks the checked answer made before it, so a tie
broken the other way costs no more than the gap it really is.
"""

from __future__ import annotations

import dataclasses

import numpy as np

CRITERIA = ("mid", "jmi")


@dataclasses.dataclass
class Answer:
    """What one fit returned: picks in order, their gains, and the
    relevance of every feature."""

    ids: np.ndarray
    gains: np.ndarray
    relevance: np.ndarray

    def key(self) -> bytes:
        return b"".join(
            np.ascontiguousarray(a).tobytes()
            for a in (self.ids, self.gains, self.relevance)
        )


@dataclasses.dataclass
class Tables:
    """Exact counts: ``rel`` (F, V, C) of (x_f, y); ``pair[s]`` (F, V, V, C)
    of (x_f, x_s, y) for every feature ``s`` asked for."""

    rel: np.ndarray
    pair: dict


def count_tables(X, y, cols, num_values: int, num_classes: int) -> Tables:
    """One pass over row blocks of ``X`` (N, F) and ``y`` (N,).

    Each block becomes a 0/1 target matrix ``Z`` with one column per class
    and one per (value of ``x_s``, class) for every ``s`` in ``cols``;
    ``[x_f == v]^T @ Z`` then counts, in float32 whose sums stay exact
    below 2^24 rows per block.  The value-0 counts follow from the column
    totals of ``Z``.
    """
    V, C = int(num_values), int(num_classes)
    cols = [int(s) for s in cols]
    n, F = X.shape
    width = C + len(cols) * V * C
    acc = np.zeros((V, F, width), np.int64)
    block = max(256, min(65536, 2**26 // max(F, 1)))
    for lo in range(0, n, block):
        xb = np.asarray(X[lo : lo + block])
        yb = np.asarray(y[lo : lo + block]).astype(np.int64)
        if xb.min() < 0 or xb.max() >= V or yb.min() < 0 or yb.max() >= C:
            raise ValueError("values outside the configured categories")
        rows = np.arange(xb.shape[0])
        Z = np.zeros((xb.shape[0], width), np.float32)
        Z[rows, yb] = 1.0
        for i, s in enumerate(cols):
            Z[rows, C + i * V * C + xb[:, s].astype(np.int64) * C + yb] = 1.0
        rest = np.zeros((F, width), np.int64)
        for v in range(1, V):
            got = np.rint((xb == v).astype(np.float32).T @ Z).astype(np.int64)
            acc[v] += got
            rest += got
        acc[0] += Z.sum(axis=0).astype(np.int64)[None, :] - rest
    rel = np.moveaxis(acc[:, :, :C], 0, 1)  # (F, V, C)
    pair = {}
    for i, s in enumerate(cols):
        blk = acc[:, :, C + i * V * C : C + (i + 1) * V * C]  # (V, F, V*C)
        pair[s] = np.moveaxis(blk, 0, 1).reshape(F, V, V, C)
    return Tables(rel, pair)


def _exact(a):
    return a


def bf16_round(a):
    """Round to the nearest bfloat16 and back (the control's precision)."""
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def mutual_info(counts: np.ndarray, rnd=_exact) -> np.ndarray:
    """``(..., V, W)`` counts -> ``(...,)`` plug-in MI in nats; ``rnd``
    rounds after every operation."""
    c = rnd(counts.astype(np.float64))
    total = rnd(np.maximum(c.sum(axis=(-1, -2), keepdims=True), 1.0))
    p = rnd(c / total)
    px = rnd(p.sum(axis=-1, keepdims=True))
    py = rnd(p.sum(axis=-2, keepdims=True))
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = rnd(p / rnd(px * py))
        terms = np.where(p > 0, rnd(p * rnd(np.log(ratio))), 0.0)
    return rnd(terms.sum(axis=(-1, -2)))


def conditional_mutual_info(counts: np.ndarray, rnd=_exact) -> np.ndarray:
    """``(..., V, W, C)`` counts -> ``(...,)`` ``I(x; w | y)`` in nats."""
    per_class = mutual_info(np.moveaxis(counts, -1, -3), rnd)  # (..., C)
    mass = rnd(counts.sum(axis=(-3, -2)).astype(np.float64))  # (..., C)
    total = rnd(np.maximum(mass.sum(axis=-1, keepdims=True), 1.0))
    return rnd(rnd(per_class * rnd(mass / total)).sum(axis=-1))


class Scorer:
    """Relevance and pair terms from :class:`Tables` at one precision."""

    def __init__(self, tables: Tables, criterion: str, rnd=_exact):
        if criterion not in CRITERIA:
            raise ValueError(f"no reference for criterion {criterion!r}")
        self.tables, self.criterion, self.rnd = tables, criterion, rnd
        self.rel = mutual_info(tables.rel, rnd)
        self._terms: dict = {}

    def term(self, s: int) -> np.ndarray:
        """What pick ``s`` adds to every candidate's running sum."""
        if s not in self._terms:
            counts = self.tables.pair[s]
            red = mutual_info(counts.sum(axis=-1), self.rnd)
            if self.criterion == "mid":
                t = -red
            else:
                t = self.rnd(conditional_mutual_info(counts, self.rnd) - red)
            self._terms[s] = t
        return self._terms[s]

    def objective(self, picks) -> np.ndarray:
        """Every candidate's objective after ``picks``; picked ones -inf."""
        run = np.zeros_like(self.rel)
        for s in picks:
            run = self.rnd(run + self.term(int(s)))
        g = self.rnd(self.rel + self.rnd(run / max(len(picks), 1)))
        g = np.array(g, np.float64)
        g[np.asarray(picks, np.int64)] = -np.inf
        return g

    def greedy(self, num_select: int) -> Answer:
        """The greedy fit at this precision (the control runs this)."""
        picks, gains = [], []
        for _ in range(num_select):
            g = self.objective(picks)
            k = int(np.argmax(g))
            picks.append(k)
            gains.append(g[k])
        return Answer(np.array(picks), np.array(gains), np.asarray(self.rel))


def compare(answer: Answer, ref: Scorer) -> dict:
    """The numbers compared for one answer, in nats.

    ``select_gap``: over the steps, the larger of how far the reported
    gain lies from the reference's objective of that pick, and how far
    that objective lies below the reference's best candidate.
    ``relevance_gap``: the widest gap between the reported relevance of a
    feature and the reference's.
    """
    ids = np.asarray(answer.ids, np.int64)
    gains = np.asarray(answer.gains, np.float64)
    rel = np.asarray(answer.relevance, np.float64)
    n = ref.rel.shape[0]
    if rel.shape != (n,) or len(set(ids.tolist())) != len(ids) or (
        ids.min() < 0 or ids.max() >= n
    ):
        return dict(select_gap=float("inf"), relevance_gap=float("inf"))
    select = 0.0
    for l, k in enumerate(ids):
        g = ref.objective(ids[:l])
        select = max(select, abs(gains[l] - g[k]), g.max() - g[k])
    relevance = float(np.max(np.abs(rel - ref.rel)))
    if not (np.isfinite(select) and np.isfinite(relevance)):
        return dict(select_gap=float("inf"), relevance_gap=float("inf"))
    return dict(select_gap=float(select), relevance_gap=relevance)


def control_numbers(control: Scorer, ref: Scorer, ids) -> dict:
    """The numbers of :func:`compare` for the control put in the program's
    place, teacher-forced on the program's picks ``ids``: at each step the
    control's own first choice and its gain, read against the reference."""
    ids = np.asarray(ids, np.int64)
    select = 0.0
    for l in range(len(ids)):
        g_ctl = control.objective(ids[:l])
        g_ref = ref.objective(ids[:l])
        k = int(np.argmax(g_ctl))
        select = max(select, abs(g_ctl[k] - g_ref[k]), g_ref.max() - g_ref[k])
    relevance = float(np.max(np.abs(np.asarray(control.rel) - ref.rel)))
    return dict(select_gap=float(select), relevance_gap=relevance)


# Limits in nats, from the chip readings in PERF.md (section 2): sound fits
# on a TPU v5e read at most 2.2e-5 over a dozen seeds per cell, and the
# bfloat16 control at least 2.0e-3.
LIMITS = dict(select_gap=3e-4, relevance_gap=3e-4)


def judge(numbers: dict, limits: dict = LIMITS) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
