"""Run one cell of the benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (the cell's data, the program's own set-up, warm-up of the cell's
shapes) is timed as ``setup_s``; then the cell's traffic runs for
``--seconds``, driven by the loop its mix names. With ``--trace 1`` the window runs under the profiler and the
per-layer metrics are read from its trace; with ``--trace 0`` the
end-to-end metrics are printed. After the window the program's state is
freed and what the window produced is compared with the plain reference.
The last lines on standard error give each number compared and its limit;
the last line on standard output is the result, as JSON.

Exits 2, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, or a device kind that ``bench/peaks.json`` does not hold.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, harness  # noqa: E402

CACHE = ROOT / ".bench_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def look_for_chip(jax, chips: int, peaks: dict) -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {d.platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    if d.device_kind not in peaks:
        raise NoChip(f"device kind {d.device_kind!r} is not in bench/peaks.json")
    return peaks[d.device_kind]


def use_compile_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program, however fast it compiles."""
    path = str(CACHE / "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, chip: bool = True,
             root: Path = ROOT, config_overrides: dict | None = None,
             break_path=None) -> tuple[dict, list[str]]:
    """One run of one cell: returns the result and the lines that compare
    each number with its limit. ``chip=False`` skips the look for a chip
    (tests on the CPU); ``config_overrides`` replaces configuration keys and
    ``break_path(system)`` alters the system under test, for tests only."""
    import jax
    from jax import monitoring

    cell = harness.find_cell(harness.load_spec(root), workload, root)
    if config_overrides:
        cell.config = {**cell.config, **config_overrides}
    peaks = harness.peaks(root)
    peak = look_for_chip(jax, cell.chips, peaks) if chip else next(iter(peaks.values()))
    if chip:
        use_compile_cache(jax)
    sys.path.insert(0, str(root / "src"))  # the program under test

    run = harness.Run(cell=cell, seed=seed, seconds=seconds, peak=peak)
    drive = harness.loop(cell.traffic["loop"], root).drive

    def on_duration(event, _secs, **_kw):
        if event == COMPILE_EVENT:
            run.executables += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        with jax.profiler.TraceAnnotation("bench.setup"):
            system = harness.system(cell.config["system"], root).System(run)
            system.start()
            if break_path is not None:
                break_path(system)
            system.warm(cell.traffic)
        run.setup_s = time.perf_counter() - T_START
        trace_dir = CACHE / "trace" / workload
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir), profiler_options=_profile_options(jax))
        before = run.executables
        with jax.profiler.TraceAnnotation("bench.window"):
            ops = drive(cell.traffic, system, seconds)
        in_window = run.executables - before
        if trace:
            jax.profiler.stop_trace()
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    run.records["ops"] = ops
    run.window_s = ops[-1]["end"] - ops[0]["start"]
    run.records["attempted"] = sum(op["attempted"] for op in ops)
    stats = jax.devices()[0].memory_stats() or {}
    device = {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    if trace:
        from bench import trace as trace_lib

        run.reduction = trace_lib.read_dir(str(trace_dir))
        device["busy_s"] = run.reduction.busy_s
        device["window_s"] = run.reduction.window_s

    system.release()
    gc.collect()
    with jax.profiler.TraceAnnotation("bench.reference"):
        numbers, failed = system.check()
    metrics = harness.read_metrics(run, cell.per_layer if trace else cell.end_to_end, root)
    checks = {k: {"value": v, "limit": compare.LIMITS[k]} for k, v in numbers.items()}
    result = {
        "correct": all(v <= compare.LIMITS[k] for k, v in numbers.items()),
        "attempted": int(run.records["attempted"]),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        from bench import trace as trace_lib

        result["breakdown"] = trace_lib.breakdown(run.reduction)
    result["checks"] = checks
    notes = [f"compiles_in_window={in_window}"] + [
        f"{k}={v}" for k, v in run.records.items() if k != "ops" and not isinstance(v, dict)
    ]
    lines = notes + [f"check {k}: {c['value']} (limit {c['limit']})" for k, c in checks.items()]
    return result, lines


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer would slow the host loop it measures
    opts.host_tracer_level = 2
    return opts


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, lines = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
