"""δ-range batches against a resident index: ``index.build_index``, then
``to_distributed`` on a mesh of the cell's chips, then ``query_batch``."""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, data

STREAM_CHECK, STREAM_QUERIES = 3, 4
WARM_STEADY = 8  # warm-up ends after this many batches in a row make no new program


class System:
    """``__init__`` makes the data and touches no program; ``start`` builds
    and pins the index, ``warm`` compiles, ``draw`` and ``send`` are the
    operations the traffic drives, ``release`` frees the program's state
    and ``check`` compares.

    The index rows are the configuration's (``data.cell_rows``), the same
    in every run, so every run serves the same index and does the same
    work.
    Queries are fresh draws of the source's own process, so each has about
    as many neighbours in the index as the source's queries. Batch ``k`` of
    a run (warm-up included) is drawn from its own stream of ``--seed``, so
    no query repeats within a run and the run needs no pool."""

    def __init__(self, run):
        self.run = run
        cfg = run.cell.config
        self.data, self.centres, k, mean_nb = data.cell_rows(cfg)
        self.threshold = k + 0.5
        self.delta = float(np.sqrt(self.threshold))
        self.batches = 0  # batches drawn so far, warm-up included
        self.answers: list[tuple[np.ndarray, np.ndarray]] = []  # (pairs, queries) per batch
        run.records.update(rows=self.data.shape[0], dims=self.data.shape[1], threshold_k=k,
                           mean_neighbours=mean_nb, delta=self.delta)

    def draw(self, n: int) -> np.ndarray:
        """The next batch of ``n`` queries."""
        q = data.fresh_bits(data.rng_for(self.run.seed, STREAM_QUERIES, self.batches), self.centres, n)
        self.batches += 1
        return q

    def start(self) -> None:
        from repro.core import index as index_lib  # the system under test
        from repro.core import spjoin
        from repro.launch import mesh as mesh_lib

        join_cfg = spjoin.JoinConfig(delta=self.delta, **self.run.cell.config["join"])
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.build_index"):
            host_index = index_lib.build_index(self.data, join_cfg)
            self.index = host_index.to_distributed(mesh_lib.make_host_mesh(self.run.cell.chips))
        self.run.records.update(build_s=time.perf_counter() - t0, cap_v=int(self.index.cap_v))

    def send(self, q: np.ndarray) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.query_batch"):
            pairs = self.index.query_batch(q)
        t1 = time.perf_counter()
        self.answers.append((pairs, q))
        return {"start": t0, "end": t1, "attempted": int(q.shape[0]), "pairs": int(pairs.shape[0])}

    def warm(self, mix: dict) -> None:
        """Batches of the mix until ``WARM_STEADY`` in a row make no new
        program (``run.executables``, JAX's count of programs compiled or
        loaded from its cache): each W capacity the batches reach compiles
        a serve stage of its own."""
        b = int(mix["batch"])
        steady, n = 0, 0
        while steady < WARM_STEADY:
            before = self.run.executables
            self.send(self.draw(b))
            n += 1
            steady = steady + 1 if self.run.executables == before else 0
        self.answers.clear()
        self.run.records.update(warm_batches=n)

    def release(self) -> None:
        self.index = None

    def check(self, *, control: bool = False) -> tuple[dict[str, int], int]:
        """``compare.range_queries`` over every answer of the window, with
        ``check_queries`` of the answered queries, drawn from the seed,
        compared in full; and how many queries were answered wrongly. With
        ``control`` the reference in bfloat16 answers as many queries of
        the mix's first batches in the program's place."""
        cfg = self.run.cell.config
        rng = data.rng_for(self.run.seed, STREAM_CHECK)
        if control:
            b = int(self.run.cell.traffic["batch"])
            queries = np.concatenate([self.draw(b) for _ in range(-(-cfg["check_queries"] // b))])
            sample = np.sort(rng.choice(queries.shape[0], cfg["check_queries"], replace=False))
            return compare.range_queries(None, queries, self.data, self.threshold, sample, control=True)
        queries = np.concatenate([q for _, q in self.answers])
        offsets = np.cumsum([0] + [q.shape[0] for _, q in self.answers])
        pairs = np.concatenate(
            [np.stack([p[:, 0], p[:, 1] + o], 1) for (p, _), o in zip(self.answers, offsets)]
        ).astype(np.int64)
        sample = np.sort(rng.choice(queries.shape[0], min(cfg["check_queries"], queries.shape[0]),
                                    replace=False))
        return compare.range_queries(pairs, queries, self.data, self.threshold, sample)
