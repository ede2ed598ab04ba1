"""The comparison that decides ``correct``: the answers the timed path
produced, against the plain reference (``reference.py``).

Every number here is a count of wrong answers, each with the limit 0: the
configurations state an exact result (every pair with D <= δ, each once),
so one wrong pair is a wrong result.

- ``malformed_pairs``: pairs out of range, out of order (a self-join's
  pairs are i < j, sorted, unique) or repeated.
- ``pairs_beyond_delta``: pairs whose exact squared distance exceeds the
  threshold, over every pair the timed path returned.
- ``answers_differing``: rows (self-join) or queries (serving), drawn from
  the seed among those answered in the window, whose set of partners
  differs from the reference's. This is the only number that sees a
  missing pair.
"""
from __future__ import annotations

import numpy as np

from bench import reference

LIMITS = {"malformed_pairs": 0, "pairs_beyond_delta": 0, "answers_differing": 0}


def _out_of_order(keys: np.ndarray) -> int:
    return int((np.diff(keys) <= 0).sum()) if keys.size > 1 else 0


def _differing(got_q: np.ndarray, got_r: np.ndarray, ref_q: np.ndarray, ref_r: np.ndarray) -> np.ndarray:
    """The answer positions whose partner sets differ; both sides are
    (position, partner) pairs."""
    width = np.int64(max(int(got_r.max(initial=0)), int(ref_r.max(initial=0))) + 1)
    got = np.unique(got_q * width + got_r)
    ref = np.unique(ref_q * width + ref_r)
    return np.unique(np.setxor1d(got, ref, assume_unique=True) // width)


def self_join(pairs: np.ndarray, data: np.ndarray, threshold: float, sample: np.ndarray,
              *, control: bool = False) -> dict[str, int]:
    """Numbers for one self-join result ``pairs`` ((n, 2) int64, i < j).
    ``sample`` holds the row ids whose partner sets are compared in full.
    With ``control`` the reference's bfloat16 answers for the sampled rows
    take the place of ``pairs``."""
    n = data.shape[0]
    if control:
        q, r = reference.partners(data[sample], data, threshold, control=True)
        a, b = sample[q], r
        keep = a != b
        lo, hi = np.minimum(a[keep], b[keep]), np.maximum(a[keep], b[keep])
        pairs = np.unique(np.stack([lo, hi], 1), axis=0)
    i, j = pairs[:, 0].astype(np.int64), pairs[:, 1].astype(np.int64)
    bad_range = (i < 0) | (j >= n) | (i >= j)
    malformed = int(bad_range.sum()) + _out_of_order(i * n + j)
    ok = ~bad_range
    d2 = reference.pair_squared_distances(data, data, i[ok], j[ok])
    beyond = int((d2 > threshold).sum())

    pos = np.full(n, -1, np.int64)
    pos[sample] = np.arange(sample.size)
    gq, gr = [], []
    for a, b in ((i[ok], j[ok]), (j[ok], i[ok])):
        hit = pos[a] >= 0
        gq.append(pos[a[hit]])
        gr.append(b[hit])
    rq, rr = reference.partners(data[sample], data, threshold)
    self_hit = sample[rq] == rr
    differing = _differing(np.concatenate(gq), np.concatenate(gr), rq[~self_hit], rr[~self_hit])
    return {"malformed_pairs": malformed, "pairs_beyond_delta": beyond, "answers_differing": differing.size}


def range_queries(pairs: np.ndarray, queries: np.ndarray, data: np.ndarray, threshold: float,
                  sample: np.ndarray, *, control: bool = False) -> tuple[dict[str, int], int]:
    """Numbers for range-query answers ``pairs`` ((n, 2) int64: data row,
    query id into ``queries``), over every query answered in the window.
    ``sample`` holds the query ids whose answers are compared in full.
    Also returns how many queries were found answered wrongly."""
    n, nq = data.shape[0], queries.shape[0]
    if control:
        q, r = reference.partners(queries[sample], data, threshold, control=True)
        pairs = np.stack([r, sample[q]], 1)
    i, j = pairs[:, 0].astype(np.int64), pairs[:, 1].astype(np.int64)
    bad_range = (i < 0) | (i >= n) | (j < 0) | (j >= nq)
    ok = ~bad_range
    keys = np.sort(j[ok] * n + i[ok])
    repeated = keys[1:][np.diff(keys) == 0]
    malformed = int(bad_range.sum()) + repeated.size
    d2 = reference.pair_squared_distances(data, queries, i[ok], j[ok])
    beyond = int((d2 > threshold).sum())

    pos = np.full(nq, -1, np.int64)
    pos[sample] = np.arange(sample.size)
    hit = pos[j[ok]] >= 0
    rq, rr = reference.partners(queries[sample], data, threshold)
    differing = _differing(pos[j[ok][hit]], i[ok][hit], rq, rr)
    wrong = np.unique(np.concatenate([
        j[bad_range], repeated // n, j[ok][d2 > threshold], sample[differing]]))
    nums = {"malformed_pairs": malformed, "pairs_beyond_delta": beyond, "answers_differing": differing.size}
    return nums, int(wrong.size)
