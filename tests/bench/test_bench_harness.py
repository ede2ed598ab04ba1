"""The benchmark harness (``bench/``): discovery by name, determinism of
the data, the work function and the trace reduction."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import data, harness, reference, trace, work  # noqa: E402

TRACE = Path(__file__).with_name("small_trace.pbtxt")


def test_every_cell_resolves_by_name():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cell = harness.find_cell(spec, w["name"])
        assert cell.config["name"] == w["config"]
        assert harness.system(cell.config["system"]).System
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(m["name"]))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.find_cell(harness.load_spec(), "no-such-cell")


def _digest(root: Path) -> dict[str, bytes]:
    return {str(p.relative_to(root)): p.read_bytes() for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_config_mix_and_metric_need_no_edit(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and new entries are found, and no file already there changes."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path / "bench")
    cfg = json.loads((tmp_path / "bench/configs/random-l-256-hamming-selfjoin.json").read_text())
    cfg.update(name="small-selfjoin", rows=4096)
    (tmp_path / "bench/configs/small-selfjoin.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/join-twice.json").write_text(json.dumps({"loop": "twice"}))
    (tmp_path / "bench/traffic/loops/twice.py").write_text(
        "def drive(mix, system, seconds):\n    return [system.join(), system.join()]\n")
    (tmp_path / "bench/metrics/joins_run.join.py").write_text(
        "def read(run):\n    return len(run.records.get('ops', [])) or None\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "small-selfjoin", "source": "test", "file": "bench/configs/small-selfjoin.json",
                            "reduced": ["rows"], "why": "test"})
    spec["workloads"].append({"name": "small-join", "config": "small-selfjoin", "traffic": "join-twice",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "joins_run.join", "unit": "joins", "better": "higher",
                              "source": "host_clock", "layer": "test", "moves": "join_s",
                              "workloads": ["small-join"]})
    spec["end_to_end"][0].setdefault("workloads", []).append("small-join")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(harness.load_spec(tmp_path), "small-join", tmp_path)
    assert cell.config["rows"] == 4096 and cell.traffic["loop"] == "twice"
    joins = harness.loop("twice", tmp_path).drive(cell.traffic, _FakeJoin(), 1.0)
    assert [op["attempted"] for op in joins] == [1, 1]
    assert [m["name"] for m in cell.per_layer] == ["joins_run.join"]
    run = harness.Run(cell=cell, seed=1, seconds=0.0, records={"ops": [{}, {}]})
    assert harness.read_metrics(run, cell.per_layer, tmp_path) == {
        "joins_run.join": {"value": 2.0, "unit": "joins"}}
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_reader_that_finds_nothing_is_left_out():
    cell = harness.find_cell(harness.load_spec(), "hamming256-join")
    run = harness.Run(cell=cell, seed=1, seconds=1.0)
    assert harness.read_metrics(run, cell.per_layer) == {}


class _FakeJoin:
    """A system whose operation takes 0.4 s on a fake clock."""

    def __init__(self):
        self.clock = 0.0

    def join(self) -> dict:
        self.clock += 0.4
        return {"start": self.clock - 0.4, "end": self.clock, "attempted": 1}

    def draw(self, n: int) -> np.ndarray:
        return np.zeros((n, 4), np.float32)

    def send(self, q: np.ndarray) -> dict:
        return {**self.join(), "attempted": q.shape[0]}


@pytest.mark.parametrize("mix", ["join-back-to-back", "closed-b256"])
def test_loop_runs_whole_operations_until_the_window_is_over(mix):
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    ops = harness.loop(traffic["loop"]).drive(traffic, _FakeJoin(), 1.0)
    # operations start at 0.0, 0.4, 0.8: the third starts inside the 1 s window and is counted whole
    assert [op["start"] for op in ops] == pytest.approx([0.0, 0.4, 0.8])
    assert {op["attempted"] for op in ops} == {traffic.get("batch", 1)}


RECIPE = {"dataset": "test", "function": "random_bitstring", "n_dims": 256, "n_samples": 1100,
          "n_queries": 100, "random_state": 1}


def test_blob_bits_follow_the_sources_recipe():
    from sklearn.datasets import make_blobs
    from sklearn.model_selection import train_test_split

    base, queries, centres = data.blob_bits(RECIPE)
    y, _ = make_blobs(n_samples=1100, n_features=256, centers=100, random_state=1)
    want_base, want_queries = train_test_split(y > 0, test_size=100, random_state=1)
    assert base.dtype == np.float32 and base.shape == (1000, 256) and centres.shape == (100, 256)
    assert np.array_equal(base, want_base) and np.array_equal(queries, want_queries)
    assert np.array_equal(base, data.blob_bits(RECIPE)[0])  # the same rows in every run


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 123456789])
def test_fresh_queries_are_deterministic_per_seed(seed):
    _, _, centres = data.blob_bits(RECIPE)
    q0 = data.fresh_bits(data.rng_for(seed, 4, 0), centres, 64)
    assert q0.dtype == np.float32 and q0.shape == (64, 256) and set(np.unique(q0)) <= {0.0, 1.0}
    assert np.array_equal(q0, data.fresh_bits(data.rng_for(seed, 4, 0), centres, 64))
    assert not np.array_equal(q0, data.fresh_bits(data.rng_for(seed, 4, 1), centres, 64))
    assert not np.array_equal(q0, data.fresh_bits(data.rng_for(seed + 1, 4, 0), centres, 64))


def test_streams_of_one_seed_differ():
    assert not np.array_equal(data.rng_for(5, 1).integers(0, 1 << 30, 8),
                              data.rng_for(5, 2).integers(0, 1 << 30, 8))
    with pytest.raises(ValueError):
        data.rng_for(-1)


def test_threshold_gives_the_neighbours_asked_for():
    x, _, _ = data.blob_bits(RECIPE)
    k, mean = data.choose_threshold(x, data.rng_for(3, 2), 5.0, 128)
    assert mean >= 5.0
    k_less, less = data.choose_threshold(x, data.rng_for(3, 2), 5.0, 128)
    assert (k_less, less) == (k, mean)  # same stream, same K
    d2 = ((x[:, None, :] - x[None, :64, :]) ** 2).sum(-1)
    assert np.array_equal(d2, (x[:, None, :] != x[None, :64, :]).sum(-1))  # squared L2 of bits is Hamming


@pytest.mark.parametrize("rows, message", [
    (np.full((2, 4), 0.5, np.float32), "integers"),
    (np.full((2, 1024), 255.0, np.float32), "overflow"),
])
def test_reference_refuses_rows_it_cannot_hold_exactly(rows, message):
    with pytest.raises(ValueError, match=message):
        reference.check_integer_rows(rows)


def test_map_phase_work_for_one_shape():
    ops, nbytes = work.map_phase(1000, 128, 8, 64)
    # read: rows 128,000 + anchors 1,024 + four box tables 2,048 words;
    # written: coordinates 8,000 + cells 1,000 + membership 2 × 1,000 words.
    assert nbytes == (128_000 + 1_024 + 2_048 + 8_000 + 1_000 + 2_000) * 4
    # 2·m per row-anchor distance, and 2 comparisons × 2 box kinds per row, box, anchor.
    assert ops == 2 * 1000 * 8 * 128 + 4 * 1000 * 64 * 8
    peak = harness.peaks()["TPU v5 lite"]
    t, bound = work.least_time_s(ops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    for kind, row in harness.peaks().items():
        assert row["source"] and row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0


def _reduction():
    from jax.profiler import ProfileData

    return trace.reduce_planes(ProfileData.from_text_proto(TRACE.read_text()).planes)


def test_trace_reduction_busy_idle_and_gaps():
    red = _reduction()
    # The window is 100 µs; the device ran ops over [10, 30) and [35, 45) µs
    # (two overlapping ops count once) and [60, 90) µs.
    assert red.window_s == pytest.approx(100e-6)
    assert red.busy_s == pytest.approx(60e-6)
    assert red.op_s["map_assign_blocked"] == pytest.approx(20e-6)
    # Idle: [45, 60) in a readback; [30, 35) in the join's untraced Python;
    # [0, 10) and [90, 100) outside the join.
    assert [(n, round(s * 1e6)) for n, s in red.gaps] == [
        ("bench.window", 20), ("np.asarray(jax.Array)", 15), ("bench.join", 5)]
    assert trace.breakdown(red)["device_ops"][0] == ["fusion", pytest.approx(30e-6)]


@pytest.mark.parametrize("event, name", [
    ("%pairdist_filtered_blocked.1 = s8[1024,4096]{1,0} custom-call(f32[1024,128]{1,0} %a)",
     "pairdist_filtered_blocked"),
    ("%copy-start.12 = (s32[4096]{0}, u32[]) copy-start(s32[4096]{0} %x)", "copy-start"),
    ("%while.4 = (pred[250000,128]{1,0}) while(...)", "while"),
    ("fusion", "fusion"),
])
def test_op_names_drop_the_instruction_text(event, name):
    assert trace.op_name(event) == name


def test_trace_reduction_attributes_ops_to_programs():
    red = _reduction()
    map_s = red.device_s(lambda op, program: program.startswith("jit_map_assign"))
    assert map_s == pytest.approx(20e-6)
    assert red.device_s(lambda op, program: op == "map_assign_blocked") == pytest.approx(20e-6)
    assert red.device_s(lambda op, program: "verify_tile" in program,
                        red.spans_named("bench.join")) == pytest.approx(14e-6)
    assert red.device_s(lambda op, program: True, [(1_040_000, 1_070_000)]) == pytest.approx(17e-6)  # 5 + 2 + 10 µs


def test_trace_without_window_is_refused():
    from jax.profiler import ProfileData

    text = TRACE.read_text().replace('"bench.window"', '"bench.other"')
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes(ProfileData.from_text_proto(text).planes)


def test_device_readers_on_the_small_trace():
    red = _reduction()
    join = harness.find_cell(harness.load_spec(), "hamming256-join")
    run = harness.Run(cell=join, seed=1, seconds=1.0, reduction=red,
                      peak=harness.peaks()["TPU v5 lite"], records={"rows": 1000, "dims": 256})
    got = harness.read_metrics(run, join.per_layer)
    assert got["idle_share.join"]["value"] == pytest.approx(0.4)
    assert got["verify_kernel_s.join"]["value"] == pytest.approx(10e-6)  # the kernel, not its copy
    c = join.config
    _, nbytes = work.map_phase(1000, 256, c["join"]["n_dims"], c["join"]["p"])
    assert got["map_roofline.join"]["value"] == pytest.approx(100 * nbytes / 819e9 / 20e-6)
    assert "reduce_phase_s.join" not in got  # no join records in this run
