"""The comparison that decides ``correct``.

What the timed jobs themselves returned is compared, row by row, with
the plain reference run over the same images with the same seeded
weights (``reference/``).  Two numbers, each with a limit of its own:

* ``rows_off``: over the compared jobs, the rows that are missing,
  surplus or null.  Every image of this traffic decodes, so the limit
  is 0, exactly.
* ``feature_gap``: the worst row's ``max|got - want| / max|want|`` — the
  widest gap of one of its 2048 features to the reference's, as a share
  of that row's feature scale.  A row in the wrong place, a transposed
  or BGR image, a dropped batch-norm mean or padding read as data is of
  order 1; the limit (the configuration's ``limits.feature_gap``) lies
  between what the program at its stated precision reads and what it
  reads one precision lower (``PERF.md`` section 2 has the readings).

Compared are ALL rows of a sample of the window's finished jobs: the
first, the last, and up to ``SAMPLED_BETWEEN`` drawn from the seed.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

SAMPLED_BETWEEN = 4
REFERENCE_BLOCK = 128
#: the gap of a job that cannot be read row by row (JSON has no infinity)
UNREADABLE = 1e30


class KeptJob(NamedTuple):
    index: int          # which job of the window
    input_index: int    # which of the traffic's inputs it ran over
    frame: Any          # what ``transform`` returned


class JobSample:
    """Keeps the first job, the latest, and a seeded reservoir of the
    ones between, so that a window of any length holds a bounded number
    of result frames."""

    def __init__(self, seed: int):
        self._rng = random.Random(seed)
        self._first: Optional[KeptJob] = None
        self._last: Optional[KeptJob] = None
        self._between: List[KeptJob] = []
        self._seen_between = 0

    def offer(self, job: KeptJob) -> None:
        if self._first is None:
            self._first = job
            return
        if self._last is not None:
            self._seen_between += 1
            if len(self._between) < SAMPLED_BETWEEN:
                self._between.append(self._last)
            else:
                slot = self._rng.randrange(self._seen_between)
                if slot < SAMPLED_BETWEEN:
                    self._between[slot] = self._last
        self._last = job

    def jobs(self) -> List[KeptJob]:
        kept = [self._first] + self._between + [self._last]
        return sorted((j for j in kept if j is not None),
                      key=lambda j: j.index)


def reference_features(reference_name: str, weights: Dict[str, Any],
                       images: np.ndarray, precision=None,
                       block: int = REFERENCE_BLOCK,
                       operands: Optional[str] = None) -> np.ndarray:
    """The plain reference over uint8 RGB ``images`` at the model's input
    size, in blocks of rows so that it fits beside nothing else.
    ``operands`` makes it a CONTROL: every convolution's operands held
    in that lower precision (``reference.net.OPERANDS``)."""
    import jax

    from benchmark.flops import reference_module
    from benchmark.reference.net import Net

    ref = reference_module(reference_name)
    block = min(block, len(images))

    @jax.jit
    def run(w, x):
        return ref.forward(Net(w, precision=precision, operands=operands),
                           ref.preprocess(x))

    out = []
    for off in range(0, len(images), block):
        part = images[off:off + block]
        n = len(part)
        if n < block:     # one compiled shape: pad the tail, drop its rows
            part = np.concatenate(
                [part, np.zeros((block - n,) + part.shape[1:], part.dtype)])
        out.append(np.asarray(run(weights, part))[:n])
    return np.concatenate(out)


def row_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: the widest gap of a feature, over the row's scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want).max(axis=1), 1e-30)
    return np.abs(got - want).max(axis=1) / scale


def compare(kept: Sequence[KeptJob], row_sources: Sequence[np.ndarray],
            reference: np.ndarray, limits: Dict[str, float],
            output_col: str = "features",
            in_place_of_program: Optional[np.ndarray] = None):
    """``({name: {"value", "limit"}} of each number compared, the count
    of rows that were read against the reference)``.
    ``in_place_of_program`` (a control's features of the distinct
    images) stands in the program's place, row by row."""
    rows_off = 0
    worst = 0.0
    rows_compared = 0
    for job in kept:
        sources = row_sources[job.input_index]
        col = job.frame.table.column(output_col)
        nulls = col.null_count
        rows_off += abs(len(col) - len(sources)) + nulls
        if len(col) != len(sources) or nulls:
            worst = UNREADABLE      # no row-by-row reading of this job
            continue
        got = (job.frame.column_to_numpy(output_col)
               if in_place_of_program is None
               else in_place_of_program[sources])
        if got.ndim != 2 or got.shape[1] != reference.shape[1]:
            rows_off += len(sources)
            worst = UNREADABLE
            continue
        gaps = row_gaps(got, reference[sources])
        gaps = np.where(np.isfinite(gaps), gaps, UNREADABLE)
        worst = max(worst, float(gaps.max()))
        rows_compared += len(sources)
    if not kept:
        worst = UNREADABLE
    return {
        "rows_off": {"value": rows_off, "limit": 0},
        "feature_gap": {"value": worst,
                        "limit": float(limits["feature_gap"])},
    }, rows_compared


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
