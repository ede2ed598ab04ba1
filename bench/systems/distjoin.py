"""One-shot self-joins through ``distributed.distributed_join`` over a
one-axis mesh of the cell's chips, from the host array to the host pair
set. Its operation is ``join()``; the data, the warm-up and the check are
those of ``join.System``."""
from __future__ import annotations

import hashlib
import time

import jax

from bench.systems import join as join_system


class System(join_system.System):
    """``start`` builds the mesh and empties the program's cache of join
    stages, so that the warm-up join compiles, or loads from JAX's cache,
    every stage the window's joins reuse; ``release`` records each device's
    peak memory and drops the stages."""

    def start(self) -> None:
        from repro.core import distributed  # the system under test
        from repro.launch import mesh as mesh_lib

        self._distributed = distributed
        distributed.clear_join_stages()
        self.join_cfg = {"mesh": mesh_lib.make_host_mesh(self.run.cell.chips), "delta": self.delta,
                         **self.run.cell.config["join"]}
        self._join = distributed.distributed_join

    def join(self) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.join"):
            res = self._join(self.data, **self.join_cfg)
        t1 = time.perf_counter()
        digest = hashlib.sha256(res.pairs.tobytes()).hexdigest()
        self.results.setdefault(digest, res.pairs)
        self.run.records.update(
            duplication=res.duplication, makespan_ratio=res.makespan_ratio,
            device_rows=res.device_rows.tolist(), n_overflow_retries=res.n_overflow_retries,
            emit=res.emit,
        )
        return {"start": t0, "end": t1, "attempted": 1, "pairs": int(res.pairs.shape[0]),
                "digest": digest}

    def release(self) -> None:
        self.run.records["peak_bytes_in_use"] = [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()
        ]
        self._join = None
        self._distributed.clear_join_stages()
