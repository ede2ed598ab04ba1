"""Data of the benchmark.

The rows are the source's own: ann-benchmarks' ``random_bitstring``
recipe (``make_blobs`` thresholded at 0, then ``train_test_split``), rerun
with the source's sizes and ``random_state``, so every run holds the same
rows. ``--seed`` draws only what varies between runs: the serving cells'
queries (fresh draws of the source's own process) and the rows or queries
whose answers are compared in full.
"""
from __future__ import annotations

import numpy as np

SEED_WORDS = 2  # --seed is split into two 32-bit words, so any whole number up to 2**64 works
STREAM_DELTA = 2  # the stream of the recipe's random_state that draws the rows K is chosen on


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed; every consumer of the
    seed draws from its own stream, so adding one changes no other."""
    s = int(seed)
    if s < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    words = [(s >> (32 * i)) & 0xFFFFFFFF for i in range(SEED_WORDS)]
    if s >> (32 * SEED_WORDS):
        raise ValueError(f"--seed must be below 2**{32 * SEED_WORDS}, got {seed}")
    return np.random.default_rng(np.random.SeedSequence(words + list(stream)))


def blob_bits(source: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """ann-benchmarks' ``random_bitstring(n_dims, n_samples, n_queries)``:
    ``make_blobs(n_samples, n_dims, centers=n_queries, random_state)``
    thresholded at 0, split by ``train_test_split(test_size=n_queries,
    random_state)``. Returns the base rows and the queries as float32 0/1
    arrays, and the blob centres."""
    from sklearn.datasets import make_blobs
    from sklearn.model_selection import train_test_split

    y, _, centres = make_blobs(
        n_samples=source["n_samples"], n_features=source["n_dims"], centers=source["n_queries"],
        random_state=source["random_state"], return_centers=True,
    )
    bits = (y > 0).astype(np.float32)
    base, queries = train_test_split(bits, test_size=source["n_queries"],
                                     random_state=source["random_state"])
    return base, queries, centres


def fresh_bits(rng: np.random.Generator, centres: np.ndarray, n: int) -> np.ndarray:
    """(n, m) float32 0/1 rows drawn as the source draws its points: a
    uniformly chosen centre plus N(0, 1) noise (``make_blobs``'
    ``cluster_std``), thresholded at 0."""
    c = centres[rng.integers(0, centres.shape[0], n)]
    return (c + rng.standard_normal(c.shape) > 0).astype(np.float32)


def choose_threshold(
    data: np.ndarray, rng: np.random.Generator, neighbours: float, sample: int
) -> tuple[int, float]:
    """The least integer K at which ``sample`` random rows have on average
    at least ``neighbours`` others with squared distance <= K, and that
    average. δ² = K + ½ then lies halfway between two integers, so no pair
    of integer rows sits within rounding of the threshold. The squared
    distances are exact: the rows hold small integers, whose products and
    sums float32 holds exactly."""
    rows = rng.choice(data.shape[0], sample, replace=False)
    sq = np.einsum("ij,ij->i", data, data)
    d2 = np.rint(sq[rows][:, None] + sq[None, :] - 2.0 * (data[rows] @ data.T))
    flat = d2.ravel()
    want = int(np.ceil((neighbours + 1.0) * sample))  # each row is its own neighbour
    k = int(np.partition(flat, want - 1)[want - 1])
    return k, float((flat <= k).sum()) / sample - 1.0


def cell_rows(cfg: dict) -> tuple[np.ndarray, np.ndarray, int, float]:
    """A configuration's rows (the first ``rows`` base rows its ``recipe``
    makes), the recipe's blob centres, and the configuration's K with the
    mean neighbours it gives (``choose_threshold`` on a stream of the
    recipe's ``random_state``, so K is the same in every run)."""
    base, _, centres = blob_bits(cfg["recipe"])
    rows = base[: cfg["rows"]]
    k, mean = choose_threshold(rows, rng_for(cfg["recipe"]["random_state"], STREAM_DELTA),
                               cfg["neighbours"], cfg["delta_sample"])
    return rows, centres, k, mean
