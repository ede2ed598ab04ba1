"""The readers of the program's own spans (``bench/program_spans.py`` and
the per-layer metrics that use it), on a small text trace whose values are
worked out by hand in its header."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, program_spans, trace  # noqa: E402

TRACE = Path(__file__).with_name("small_program_trace.pbtxt")


def _planes(text: str | None = None):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text or TRACE.read_text()).planes


@pytest.fixture
def runs(monkeypatch):
    """A run of each cell whose trace is the small program trace."""
    def runs_of(text: str | None = None):
        monkeypatch.setattr(program_spans, "read_dir", lambda _: program_spans.from_planes(_planes(text)))
        red = trace.reduce_planes(_planes(text))
        spec = harness.load_spec()
        out = {}
        for name in ("hamming256-join", "hamming256-serve-b256"):
            cell = harness.find_cell(spec, name)
            run = harness.Run(cell=cell, seed=1, seconds=1.0, reduction=red,
                              peak=harness.peaks()["TPU v5 lite"], records={"rows": 1000, "dims": 256})
            out[name] = (run, harness.read_metrics(run, cell.per_layer))
        return out
    return runs_of


def test_spans_nest_with_their_counters():
    spans = program_spans.from_planes(_planes())
    assert spans.window == (1_000_000, 1_200_000)
    names = [s.name for s in spans.spans]
    assert names.count("verify.tile") == 3 and names.count("serve.query_batch") == 2
    parent = {s.name: spans.spans[s.parent].name if s.parent >= 0 else None for s in spans.spans}
    assert parent["spjoin.join"] is None and parent["serve.query_batch"] is None
    assert parent["spjoin.map"] == "spjoin.join" and parent["verify.finalize"] == "spjoin.reduce"
    assert parent["verify.cell"] == "spjoin.reduce" and parent["verify.tile"] == "verify.cell"
    assert parent["verify.readback"] == "verify.tile" and parent["serve.unpack"] == "serve.query_batch"
    assert spans.spans[0].counts == {"request": 1, "rows": 1000}
    assert spans.total("verify.tile", "n_cand") == (500.0, 3)
    assert spans.total("serve.readback", "mask_elems", [(1_110_000, 1_150_000)]) == (4000.0, 1)
    # the runtime's own events on the spans' line are kept, not taken for spans
    assert ("np.asarray(jax.Array)", 1_054_000, 1_062_000) in spans.events


@pytest.mark.parametrize("metric, value", [
    ("map_s.join", 10e-6),
    ("prepass_s.join", 12e-6),  # 5 + 4 + 3 µs
    ("tile_readback_s.join", 16e-6),  # 10 + 6
    ("tile_emit_s.join", 8e-6),  # 5 + 3
    ("sample_retrace_s.join", 9e-6),  # [8, 12) and [13, 18); the compile inside counts once
    ("prepass_survival.join", 0.2),  # (400 + 0 + 100) / (1000 + 500 + 1000)
])
def test_join_readers_on_the_small_program_trace(runs, metric, value):
    _, got = runs()["hamming256-join"]
    assert got[metric]["value"] == pytest.approx(value)


@pytest.mark.parametrize("metric, value", [
    ("route_ms.serve", 5e-3),  # (7 + 3) / 2 µs a batch
    ("mask_readback_ms.serve", 15e-3),  # (16 + 14) / 2
    ("unpack_ms.serve", 7.5e-3),  # (8 + 7) / 2
    ("mask_yield.serve", 0.0075),  # (40 + 20) / (4000 + 4000)
])
def test_serve_readers_on_the_small_program_trace(runs, metric, value):
    _, got = runs()["hamming256-serve-b256"]
    assert got[metric]["value"] == pytest.approx(value)


def test_the_device_readers_still_read_the_program_trace(runs):
    (_, join), (_, serve) = runs().values()
    assert join["verify_kernel_s.join"]["value"] == pytest.approx(11e-6)  # 7 + 4 µs in the join
    assert serve["serve_stage_ms.serve"]["value"] == pytest.approx(10e-3)  # (10 + 10) / 2 µs
    assert join["idle_share.join"]["value"] == pytest.approx(1 - 35e-6 / 200e-6)  # 4 + 7 + 4 + 10 + 10 µs busy


def test_idle_gaps_are_labelled_by_program_spans():
    gaps = dict(trace.reduce_planes(_planes()).gaps)
    # [34, 53) µs: its middle lies in verify.w_tiles; [60, 82): in the second tile's pre-pass
    assert gaps["verify.w_tiles"] == pytest.approx(19e-6)
    assert gaps["verify.prepass"] == pytest.approx(22e-6)
    assert "bench.join" not in gaps


def test_readers_read_nothing_without_program_spans(runs):
    text = TRACE.read_text()
    for prefix in program_spans.PREFIXES:
        text = text.replace(f'name: "{prefix}', 'name: "other.')
    for _, got in runs(text).values():
        assert not {m for m in got if m in NEW}


def test_readers_read_nothing_from_another_runs_trace(runs, monkeypatch):
    got = runs()
    other = program_spans.from_planes(_planes())
    other.window = (0.0, 1.0)
    monkeypatch.setattr(program_spans, "read_dir", lambda _: other)
    program_spans._read.update(reduction=None)
    for run, _ in got.values():
        assert not {m for m in harness.read_metrics(run, run.cell.per_layer) if m in NEW}


NEW = {"map_s.join", "prepass_s.join", "tile_readback_s.join", "tile_emit_s.join",
       "sample_retrace_s.join", "prepass_survival.join", "route_ms.serve",
       "mask_readback_ms.serve", "unpack_ms.serve", "mask_yield.serve"}


def test_every_new_metric_is_a_program_span_metric_of_one_cell():
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"] if m["name"] in NEW}
    assert set(entries) == NEW
    for m in entries.values():
        assert m["source"] == "program_span" and len(m["workloads"]) == 1
