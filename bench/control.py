"""The control of the comparison: the plain reference computed in bfloat16
(``reference.partners(control=True)``) answers in the program's place, on
the cell's own data and sample, and has to come out not correct.

    python bench/control.py --workload <name> --seeds <n> [<n> ...]

Prints, for each seed, each number compared with its limit, and one JSON
line last. Not part of a benchmark run. It touches neither the program nor
JAX: the reference runs on the host.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, harness  # noqa: E402


def control_numbers(workload: str, seed: int, *, root: Path = ROOT,
                    config_overrides: dict | None = None) -> dict[str, int]:
    cell = harness.find_cell(harness.load_spec(root), workload, root)
    if config_overrides:
        cell.config = {**cell.config, **config_overrides}
    run = harness.Run(cell=cell, seed=seed, seconds=0.0)
    numbers, _ = harness.system(cell.config["system"], root).System(run).check(control=True)
    return numbers


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    out = {}
    for seed in args.seeds:
        nums = control_numbers(args.workload, seed)
        out[str(seed)] = nums
        fails = [k for k, v in nums.items() if v > compare.LIMITS[k]]
        print(f"seed {seed}: " + ", ".join(f"{k}={v} (limit {compare.LIMITS[k]})" for k, v in nums.items())
              + f" -> {'fails ' + ','.join(fails) if fails else 'PASSES'}", flush=True)
    print(json.dumps({"workload": args.workload, "control": out}))
    return 0 if all(any(v > compare.LIMITS[k] for k, v in n.items()) for n in out.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
