"""The four-chip join cell's run on the CPU at a tiny size, through
``bench/run.py``'s ``run_cell`` with the look for a chip skipped, on four
virtual CPU devices in a child process (the device-count flag must not
reach the rest of the suite): the comparison passes on the program's
answers (Pallas kernels in interpret mode), fails on the control's (the
reference in bfloat16), and fails with the timed path broken underneath.
The cell's per-layer readers read a small trace of one join whose values
are worked out by hand in its header."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, dist_spans, harness, trace  # noqa: E402

CELL = "hamming256-dist4-join"
TRACE = Path(__file__).with_name("small_dist_trace.pbtxt")
SEED = 2**40 + 271828
FAULTS = ["answer", "half"]

CHILD = """
import json, sys
import numpy as np
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
from bench import run as bench_run

CELL, SEED = {cell!r}, {seed!r}


def overrides(backend):
    cell = harness.find_cell(harness.load_spec(), CELL)
    recipe = {{**cell.config["recipe"], "n_samples": 2112, "n_queries": 64}}
    return {{"recipe": recipe, "rows": 2048, "check_rows": 256,
            "join": {{**cell.config["join"], "backend": backend}}}}


def alter(system, fault):
    join = system._join

    def broken(x, **kw):
        res = join(x, **kw)
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


def run(backend, fault=None):
    brk = None if fault is None else (lambda system: alter(system, fault))
    result, lines = bench_run.run_cell(CELL, SEED, 0.05, False, chip=False,
                                       config_overrides=overrides(backend), break_path=brk)
    return {{"result": result, "lines": lines}}


out = {{"program": run("pallas")}}
out.update({{fault: run("numpy", fault) for fault in {faults!r}}})
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs() -> dict:
    """The cell's runs on four virtual devices: the program's own, then one
    for each fault of the timed path."""
    prog = textwrap.dedent(CHILD).format(root=str(ROOT), src=str(ROOT / "src"), cell=CELL,
                                         seed=SEED, faults=FAULTS)
    env = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True, text=True,
                         timeout=900, env=env, cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_cell_is_correct_at_a_tiny_size(runs):
    result, lines = runs["program"]["result"], runs["program"]["lines"]
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert lines[0] == "compiles_in_window=0"
    assert result["device"]["count"] == 4
    metrics = harness.find_cell(harness.load_spec(), CELL).end_to_end
    assert set(result["metrics"]) == {m["name"] for m in metrics} == {"join_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    notes = dict(line.split("=", 1) for line in lines if "=" in line and not line.startswith("check"))
    assert notes["emit"] == "compact" and notes["n_overflow_retries"] == "0"
    assert len(json.loads(notes["device_rows"])) == 4
    assert sum(json.loads(notes["device_rows"])) == 2048
    assert len(json.loads(notes["peak_bytes_in_use"])) == 4
    assert float(notes["duplication"]) >= 1.0 and float(notes["makespan_ratio"]) >= 1.0


def test_control_fails():
    cell = harness.find_cell(harness.load_spec(), CELL)
    recipe = {**cell.config["recipe"], "n_samples": 2112, "n_queries": 64}
    nums = control.control_numbers(CELL, SEED, config_overrides={
        "recipe": recipe, "rows": 2048, "check_rows": 256,
        "join": {**cell.config["join"], "backend": "numpy"}})
    assert nums["answers_differing"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_path_is_not_correct(runs, fault):
    result = runs[fault]["result"]
    assert not result["correct"] and result["failed"] > 0


def _planes(text: str | None = None):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text or TRACE.read_text()).planes


def _read(monkeypatch, text: str | None = None) -> dict:
    """The cell's per-layer metrics on the small trace of one join."""
    monkeypatch.setattr(dist_spans, "read_dir", lambda _: dist_spans.from_planes(_planes(text)))
    dist_spans._read.update(reduction=None)
    cell = harness.find_cell(harness.load_spec(), CELL)
    run = harness.Run(cell=cell, seed=1, seconds=1.0, reduction=trace.reduce_planes(_planes(text)))
    return {k: v["value"] for k, v in harness.read_metrics(run, cell.per_layer).items()}


def test_dist_spans_nest_under_the_join_with_their_counters():
    spans = dist_spans.from_planes(_planes())
    assert spans.window == (1_000_000, 1_100_000)
    kids = [s.name for s in spans.spans if s.parent == 0]
    assert spans.spans[0].name == "dist.join" and spans.spans[0].parent == -1
    assert kids == ["dist.put", "dist.sample", "dist.plan", "dist.stage", "dist.readback", "dist.merge"]
    assert spans.spans[0].counts == {"request": 1, "rows": 1000, "devices": 2, "p": 8}
    assert spans.named("dist.plan")[0].counts == {
        "cap_v": 10, "cap_w": 20, "pair_cap": 64, "duplication": 4.0, "makespan_ratio": 1.25}
    assert spans.total("dist.merge", "n_pairs") == (50.0, 1)


@pytest.mark.parametrize("metric, value", [
    ("idle_share.dist4", 1 - 18e-6 / 100e-6),  # busy 24 and 12 µs of a 100 µs window
    ("exchange_s.dist4", 3e-6),  # all-to-all 4 + 2 µs over two devices
    ("verify_kernel_s.dist4", 13e-6),  # pairdist 16 + 10 µs over two devices
    ("device_imbalance.dist4", 22 / 17),  # busy in the stage 22 and 12 µs
    ("sample_s.dist4", 10e-6),
    ("plan_s.dist4", 10e-6),
    ("stage_s.dist4", 34e-6),  # stage 30 + readback 4 µs
    ("merge_s.dist4", 12e-6),
])
def test_readers_on_the_small_trace(monkeypatch, metric, value):
    assert _read(monkeypatch)[metric] == pytest.approx(value)


def test_span_readers_read_nothing_without_the_programs_spans(monkeypatch):
    """The parent program opens no ``dist.*`` span: those readers leave
    their metrics out, and the device readers still read the trace."""
    got = _read(monkeypatch, TRACE.read_text().replace('name: "dist.', 'name: "other.'))
    assert set(got) == {"idle_share.dist4", "exchange_s.dist4", "verify_kernel_s.dist4"}


def test_every_new_metric_reads_only_the_new_cell():
    spec = harness.load_spec()
    new = [m for m in spec["per_layer"] if m["name"].endswith(".dist4")]
    assert len(new) == 8
    assert all(m["workloads"] == [CELL] and m["moves"] == "join_s" for m in new)
    (join_s,) = [m for m in spec["end_to_end"] if m["name"] == "join_s"]
    assert join_s["workloads"] == ["hamming256-join", CELL]
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "join-back-to-back"
