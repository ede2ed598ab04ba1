"""Each cell's run on the CPU at a tiny size, through ``bench/run.py``'s
``run_cell`` with the look for a chip skipped: the comparison passes on the
program's answers (Pallas kernels in interpret mode), fails on the
control's (the reference in bfloat16), and fails with the timed path
broken underneath."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

SEED = 2**40 + 271828
CELLS = ["hamming256-join", "hamming256-serve-b256"]


def _overrides(workload: str, backend: str) -> dict:
    """The source's recipe at 2,048 base rows around 64 centres."""
    cell = harness.find_cell(harness.load_spec(), workload)
    recipe = {**cell.config["recipe"], "n_samples": 2112, "n_queries": 64}
    return {"recipe": recipe, "rows": 2048, "check_rows": 256, "check_queries": 256,
            "join": {**cell.config["join"], "backend": backend}}


def _run(workload: str, backend: str = "pallas", break_path=None) -> dict:
    result, lines = bench_run.run_cell(
        workload, SEED, 0.05, False, chip=False,
        config_overrides=_overrides(workload, backend), break_path=break_path,
    )
    assert lines[-3:] == [f"check {k}: {c['value']} (limit {c['limit']})" for k, c in result["checks"].items()]
    return result


@pytest.mark.parametrize("workload", CELLS)
def test_cell_is_correct_at_a_tiny_size(workload):
    result = _run(workload)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    metrics = harness.find_cell(harness.load_spec(), workload).end_to_end
    assert set(result["metrics"]) == {m["name"] for m in metrics}
    assert all(v["value"] > 0 for v in result["metrics"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    nums = control.control_numbers(workload, SEED, config_overrides=_overrides(workload, "numpy"))
    # On 0/1 rows bfloat16 moves a squared distance by at most 1, and only
    # one of odd parity: at an odd K it drops pairs, at an even K it adds
    # pairs beyond delta. Either way the sampled answers differ.
    assert nums["answers_differing"] > 0


def _alter_join(system, fault):
    join = system._join

    def broken(x, cfg):
        res = join(x, cfg)
        p = res.pairs.copy()
        if fault == "answer":
            far = int(np.argmax(((x - x[p[-1, 0]]) ** 2).sum(1)))
            p[-1] = sorted((int(p[-1, 0]), far))
            p = np.unique(p, axis=0)
        else:  # half of the rows left out
            p = p[p[:, 1] < x.shape[0] // 2]
        res.pairs = p
        return res

    system._join = broken


def _alter_serve(system, fault):
    index = system.index
    query_batch = index.query_batch

    def broken(q, *a, **k):
        p = query_batch(q, *a, **k).copy()
        if fault == "answer":
            p[0, 0] = (p[0, 0] + system.data.shape[0] // 2) % system.data.shape[0]
        else:  # half of the batch left out
            p = p[p[:, 1] < q.shape[0] // 2]
        return p

    index.query_batch = broken


@pytest.mark.parametrize("fault", ["answer", "half"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_path_is_not_correct(workload, fault):
    alter = _alter_join if workload == "hamming256-join" else _alter_serve
    result = _run(workload, "numpy", lambda system: alter(system, fault))
    assert not result["correct"] and result["failed"] > 0
