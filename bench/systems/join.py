"""One-shot self-joins through ``spjoin.join``, from the host array to the
host pair set. Its operation is ``join()``."""
from __future__ import annotations

import hashlib
import time

import jax
import numpy as np

from bench import compare, data

STREAM_CHECK = 3


class System:
    """``__init__`` makes the data and touches no program; ``start`` sets
    the program up, ``warm`` compiles, ``join`` is the operation the
    traffic drives, ``release`` frees the program's state and ``check``
    compares.

    The rows are the configuration's (``data.cell_rows``), the same in
    every run, so every run does the same work; ``--seed`` draws the rows
    whose partner sets are compared in full."""

    def __init__(self, run):
        self.run = run
        cfg = run.cell.config
        self.data, _, k, mean_nb = data.cell_rows(cfg)
        self.threshold = k + 0.5
        self.delta = float(np.sqrt(self.threshold))
        self.results: dict[str, np.ndarray] = {}  # distinct pair sets, by digest
        run.records.update(rows=self.data.shape[0], dims=self.data.shape[1], threshold_k=k,
                           mean_neighbours=mean_nb, delta=self.delta)

    def start(self) -> None:
        from repro.core import spjoin  # the system under test

        self.join_cfg = spjoin.JoinConfig(delta=self.delta, **self.run.cell.config["join"])
        self._join = spjoin.join

    def join(self) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.join"):
            res = self._join(self.data, self.join_cfg)
        t1 = time.perf_counter()
        vs = res.verify_stats
        digest = hashlib.sha256(res.pairs.tobytes()).hexdigest()
        self.results.setdefault(digest, res.pairs)
        return {
            "start": t0, "end": t1, "attempted": 1, "pairs": int(res.n_pairs), "digest": digest,
            "sample_s": res.sample_time_s, "map_s": res.map_time_s,
            "verify_s": res.verify_time_s, "backend": vs.backend if vs else None,
            "tiles": vs.n_tiles if vs else 0, "tiles_pruned": vs.n_tiles_pruned if vs else 0,
            "overflow_retries": vs.n_overflow_retries if vs else 0,
        }

    def warm(self, mix: dict) -> None:
        """One whole join compiles every shape the window's joins use:
        they join the same rows under the same plan."""
        self.run.records["warm"] = self.join()
        self.results.clear()

    def release(self) -> None:
        self._join = None

    def check(self, *, control: bool = False) -> tuple[dict[str, int], int]:
        """The numbers of ``compare.self_join`` for each distinct pair set
        the window produced (joins of one input agree, so usually one), the
        worst of each; and how many joins were wrong. With ``control`` the
        reference in bfloat16 answers in the program's place."""
        cfg = self.run.cell.config
        rng = data.rng_for(self.run.seed, STREAM_CHECK)
        sample = np.sort(rng.choice(self.data.shape[0], cfg["check_rows"], replace=False))
        if control:
            return compare.self_join(None, self.data, self.threshold, sample, control=True), 0
        worst = dict.fromkeys(compare.LIMITS, 0)
        wrong = set()
        for digest, pairs in self.results.items():
            nums = compare.self_join(pairs, self.data, self.threshold, sample)
            worst = {k: max(worst[k], v) for k, v in nums.items()}
            if any(v > compare.LIMITS[k] for k, v in nums.items()):
                wrong.add(digest)
        failed = sum(op["digest"] in wrong for op in self.run.records.get("ops", []))
        return worst, failed
