"""Layer 2: the jaxpr trace auditor.

The AST layer proves things about *source text*; this layer proves things
about what XLA will actually be asked to run. Every jitted public entry
point is traced with abstract shapes (``jax.make_jaxpr`` — no compilation,
no execution) and the resulting jaxpr is walked recursively (through
``pjit`` / ``shard_map`` / ``scan`` / ``cond`` inner jaxprs) to assert:

  (a) **no f64**: zero ``convert_element_type`` equations with a float64
      target anywhere in the trace — the kernel paths are f32 end to end.
  (b) **collective budget**: each distributed stage contains EXACTLY its
      contracted communication. One logical shuffle per stage = one
      ``all_to_all`` per dispatch buffer: the verify stage moves
      (payload, ids, own-cell) per side -> 6 primitives; serving moves the
      W side only -> 3; the stats/counts stages gather 3/4 packets. Any
      other collective primitive anywhere is a violation.
  (c) **static shapes**: every output aval has concrete integer dims — the
      capacity-bucket contract (no data-dependent output shapes survive a
      trace; a function that *can't* be traced abstractly, e.g. boolean
      masking `x[x > 0]`, is rejected with the trace error).
  (d) **recompile budget**: the verify engine's bucket quantizer
      (``verify.bucket_size``) bounds the distinct tile shapes — and hence
      XLA compilations — per entry point. The family size is computed
      exactly over every possible tile size and checked against a budget;
      a handful of family members are traced live to pin the out-shape =
      (cap_v, cap_w) law.

Results are emitted as ``runs/contracts.json`` and diffed against
``tools/spjoin_lint/contracts_baseline.json`` in CI, so a new collective,
an f64 cast, or a bucket-family blowup fails the build before any test runs.
"""
from __future__ import annotations

import collections
import json
import os
import pathlib

# Budgets for assertion (d). bucket_size is quarter-pow2: <= 4 shapes per
# octave + the floor sizes, so the family grows with log(cap), not cap.
RECOMPILE_BUDGET = {"v_buckets": 16, "w_buckets": 24}

# Contracted collective counts per entry point; entries not listed contract
# to ZERO collectives. Exactness matters both ways: fewer means the stage
# stopped communicating (broken), more means a second shuffle snuck in.
EXPECTED_COLLECTIVES = {
    "stage_stats": {"all_gather": 3},  # packet, confidence, count
    "stage_counts": {"all_gather": 4},  # v_cnt, w_cnt, mbb lo, mbb hi
    "stage_verify": {"all_to_all": 6},  # (payload, ids, own) x (V, W)
    "stage_verify_cross": {"all_to_all": 6},  # same buffers, R and S sides
    "stage_serve": {"all_to_all": 3},  # W side only: V buffers are pinned
}


# ---------------------------------------------------------------------------
# Jaxpr walking (duck-typed: works across jax versions without private deps)
# ---------------------------------------------------------------------------


def _inner_jaxprs(value):
    """Yield any jaxpr-like objects inside an eqn param value."""
    if hasattr(value, "eqns"):  # Jaxpr
        yield value
    elif hasattr(value, "jaxpr") and hasattr(getattr(value, "jaxpr"), "eqns"):
        yield value.jaxpr  # ClosedJaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _inner_jaxprs(v)


def walk_eqns(jaxpr):
    """Every equation in ``jaxpr`` and any jaxpr nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for inner in _inner_jaxprs(v):
                yield from walk_eqns(inner)


def collect_primitives(closed_jaxpr) -> collections.Counter:
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return collections.Counter(e.primitive.name for e in walk_eqns(jaxpr))


def count_f64_casts(closed_jaxpr) -> int:
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    n = 0
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name == "convert_element_type":
            if str(eqn.params.get("new_dtype", "")) in ("float64", "f64"):
                n += 1
    return n


def collective_counts(closed_jaxpr) -> dict:
    from spjoin_lint import config

    prims = collect_primitives(closed_jaxpr)
    return {k: v for k, v in prims.items() if k in config.COLLECTIVE_PRIMS}


# ---------------------------------------------------------------------------
# Entry tracing
# ---------------------------------------------------------------------------


def trace_entry(name: str, fn, args, *, static_argnames=()) -> dict:
    """Trace ``fn(*args)`` abstractly and report its contract surface.

    Never raises: a function that cannot be traced with abstract shapes
    (data-dependent output shape, host sync on a tracer) is *rejected* —
    the failure lands in ``entry["errors"]`` and fails the audit.
    """
    import jax

    entry = {
        "name": name,
        "collectives": {},
        "f64_casts": 0,
        "out_shapes": [],
        "out_dtypes": [],
        "errors": [],
    }
    try:
        jaxpr = jax.make_jaxpr(fn, static_argnums=())(*args) if not static_argnames \
            else jax.make_jaxpr(fn, static_argnames=static_argnames)(*args)
    except TypeError:
        # static handling differences across jax versions: fall back to a
        # closure with statics already bound.
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception as e:  # noqa: BLE001 - any trace failure is the finding
            entry["errors"].append(f"untraceable with abstract shapes: {type(e).__name__}: {e}")
            return entry
    except Exception as e:  # noqa: BLE001 - any trace failure is the finding
        entry["errors"].append(f"untraceable with abstract shapes: {type(e).__name__}: {e}")
        return entry

    entry["collectives"] = collective_counts(jaxpr)
    entry["f64_casts"] = count_f64_casts(jaxpr)
    for aval in jaxpr.out_avals:
        shape = getattr(aval, "shape", None)
        if shape is None or not all(isinstance(d, int) for d in shape):
            entry["errors"].append(f"non-static output shape: {aval}")
        else:
            entry["out_shapes"].append(list(shape))
            entry["out_dtypes"].append(str(getattr(aval, "dtype", "?")))
    return entry


def bucket_family(bucket_fn, cap: int, floor: int = 8) -> list[int]:
    """Exact set of bucket capacities ``bucket_fn`` can emit for 1..cap."""
    return sorted({int(bucket_fn(n, cap, floor)) for n in range(1, cap + 1)})


def audit_bucket_family(bucket_fn, cap_v: int, cap_w: int, budget=None) -> dict:
    """Assertion (d): the quantized tile family — the compile-cache keyspace
    — stays within budget. Returns the report dict (errors inside)."""
    budget = dict(RECOMPILE_BUDGET if budget is None else budget)
    fam_v = bucket_family(bucket_fn, cap_v)
    fam_w = bucket_family(bucket_fn, cap_w)
    rep = {
        "cap_v": cap_v,
        "cap_w": cap_w,
        "v_buckets": len(fam_v),
        "w_buckets": len(fam_w),
        "max_traces": len(fam_v) * len(fam_w),
        "budget": budget,
        "errors": [],
    }
    if len(fam_v) > budget["v_buckets"]:
        rep["errors"].append(
            f"V bucket family has {len(fam_v)} shapes for cap {cap_v} "
            f"(budget {budget['v_buckets']}) — every extra shape is an XLA "
            f"recompile"
        )
    if len(fam_w) > budget["w_buckets"]:
        rep["errors"].append(
            f"W bucket family has {len(fam_w)} shapes for cap {cap_w} "
            f"(budget {budget['w_buckets']})"
        )
    return rep


# ---------------------------------------------------------------------------
# The repo's entry points
# ---------------------------------------------------------------------------


def _synthetic_plan(p: int = 4, n: int = 4, m: int = 4, delta: float = 1.0):
    """A tiny JoinPlan with the right shapes; trace structure does not
    depend on the box values."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distributed as dist

    edges = np.linspace(-2.0, 2.0, p + 1, dtype=np.float32)
    big = np.float32(1e9)
    klo = np.full((p, n), -big, np.float32)
    khi = np.full((p, n), big, np.float32)
    klo[:, 0] = edges[:-1]
    khi[:, 0] = edges[1:]
    return dist.JoinPlan(
        anchors=jnp.zeros((n, m), jnp.float32),
        metric="l1",
        kernel_lo=jnp.asarray(klo),
        kernel_hi=jnp.asarray(khi),
        whole_lo=jnp.asarray(klo - delta),
        whole_hi=jnp.asarray(khi + delta),
        delta=delta,
        p=p,
    )


def repo_entries() -> list[dict]:
    """Trace every jitted public entry point with tiny abstract shapes."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import distributed as dist
    from repro.core import placement as placement_lib
    from repro.core import verify as verify_lib
    from repro.kernels import ops as kops

    f32 = jnp.float32
    entries: list[dict] = []

    # ---- kernel dispatch ops (numpy backend: the CI-stable trace) --------
    x = jnp.zeros((8, 4), f32)
    y = jnp.zeros((6, 4), f32)
    entries.append(trace_entry(
        "ops.pairdist",
        functools.partial(kops.pairdist, metric="l2", backend="numpy"), (x, y),
    ))
    entries.append(trace_entry(
        "ops.pairdist_mask",
        functools.partial(kops.pairdist_mask, delta=1.0, metric="l2", backend="numpy"),
        (x, y),
    ))
    entries.append(trace_entry(
        "ops.pairdist_mask_filtered",
        functools.partial(
            kops.pairdist_mask_filtered, delta=1.0, metric="l2",
            delta_bound=1.01, backend="numpy",
        ),
        (x, y, jnp.zeros((8, 4), f32), jnp.zeros((6, 4), f32)),
    ))
    boxes = tuple(jnp.zeros((4, 4), f32) for _ in range(4))
    entries.append(trace_entry(
        "ops.map_assign",
        functools.partial(kops.map_assign, metric="l1", backend="numpy"),
        (x, jnp.zeros((4, 4), f32)) + boxes,
    ))
    entries.append(trace_entry(
        "ops.assign_membership",
        functools.partial(kops.assign_membership, backend="numpy"),
        (jnp.zeros((8, 4), f32),) + boxes,
    ))

    # ---- the verify engine's tile kernel over the bucket family ----------
    def tile(cv, cw):
        def f(xv, xw, vids, wids, wcells):
            return verify_lib.verify_tile(
                xv, xw, vids, wids, wcells, 0,
                delta=1.0, metric="l1", backend="numpy", prune="none",
            )
        args = (
            jnp.zeros((cv, 4), f32), jnp.zeros((cw, 4), f32),
            jnp.zeros((cv,), jnp.int32), jnp.zeros((cw,), jnp.int32),
            jnp.zeros((cw,), jnp.int32),
        )
        return trace_entry(f"verify.verify_tile[{cv}x{cw}]", f, args)

    fam_v = bucket_family(verify_lib.bucket_size, 1024)
    fam_w = bucket_family(verify_lib.bucket_size, 4096)
    # Trace a spread of family members live to pin out_shape == (cap_v, cap_w).
    for cv, cw in [(fam_v[0], fam_w[0]), (fam_v[len(fam_v) // 2], fam_w[len(fam_w) // 2]),
                   (fam_v[-1], fam_w[-1])]:
        e = tile(cv, cw)
        if not e["errors"] and e["out_shapes"] != [[cv, cw]]:
            e["errors"].append(
                f"verify_tile({cv},{cw}) output shape {e['out_shapes']} is "
                f"not the bucket capacity [[{cv}, {cw}]]"
            )
        entries.append(e)

    # ---- the fused reduce tile: verify + on-device pair compaction -------
    # Contract: the compacted pair buffer's out-shape IS the capacity
    # bucket (static — assertion (c) rejects anything data-dependent),
    # zero f64 casts, zero collectives (compacted pairs ride the existing
    # exchange; the kernel itself never communicates).
    entries.append(trace_entry(
        "ops.verify_compact",
        functools.partial(
            kops.verify_compact, delta=1.0, metric="l1", capacity=16,
            cross=True, backend="numpy",
        ),
        (x, y, jnp.zeros((8,), jnp.int32), jnp.zeros((6,), jnp.int32),
         jnp.zeros((6,), jnp.int32), jnp.zeros((), jnp.int32)),
    ))
    e = entries[-1]
    if not e["errors"] and e["out_shapes"] != [[16, 2], [], []]:
        e["errors"].append(
            f"ops.verify_compact out shapes {e['out_shapes']} are not the "
            f"capacity-bucket contract [[16, 2], [], []] "
            f"(pairs buffer, count, n_cand)"
        )

    def ctile(cv, cw, cap):
        def f(xv, xw, vids, wids, wcells):
            return verify_lib.verify_tile_compact(
                xv, xw, vids, wids, wcells, 0,
                delta=1.0, metric="l1", backend="numpy", capacity=cap,
            )
        args = (
            jnp.zeros((cv, 4), f32), jnp.zeros((cw, 4), f32),
            jnp.zeros((cv,), jnp.int32), jnp.zeros((cw,), jnp.int32),
            jnp.zeros((cw,), jnp.int32),
        )
        return trace_entry(f"verify.verify_tile_compact[{cv}x{cw}x{cap}]", f, args)

    # Pair capacities ride the same quarter-pow2 ladder as the tile sides;
    # the engine tile's out-shape is (capacity + 1, 2) — buffer plus the
    # in-band [count, n_cand] row.
    for cv, cw, cap in [(fam_v[0], fam_w[0], 16), (fam_v[-1], fam_w[-1], 256)]:
        e = ctile(cv, cw, cap)
        if not e["errors"] and e["out_shapes"] != [[cap + 1, 2]]:
            e["errors"].append(
                f"verify_tile_compact({cv},{cw},{cap}) output shape "
                f"{e['out_shapes']} is not the capacity bucket "
                f"[[{cap + 1}, 2]]"
            )
        entries.append(e)

    # ---- the distributed stages (1-device mesh; jaxpr structure is what
    # we pin — the collective eqns are present regardless of mesh size) ----
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))
    plan = _synthetic_plan()
    N, m, p = 8, 4, 4
    data = jnp.zeros((N, m), f32)
    valid = jnp.ones((N,), f32)
    ids = jnp.arange(N, dtype=jnp.int32)

    stats_fn = dist.make_stage_stats(mesh, "data", t_cells=4, backend="numpy")
    entries.append(trace_entry("stage_stats", stats_fn, (data, valid)))

    counts_fn = dist.make_stage_counts(mesh, "data", plan, backend="numpy")
    entries.append(trace_entry("stage_counts", counts_fn, (data, valid)))

    vcfg = dist.VerifyConfig(
        cap_v=8, cap_w=8, backend="numpy", prune="pivot", delta_bound=1.01
    )
    verify_fn = dist.make_stage_verify(mesh, "data", plan, vcfg)
    entries.append(trace_entry("stage_verify", verify_fn, (data, valid, ids)))

    verify_x = dist.make_stage_verify(mesh, "data", plan, vcfg, cross=True)
    entries.append(trace_entry(
        "stage_verify_cross", verify_x, (data, valid, ids, data, valid, ids)
    ))

    pl = placement_lib.plan_placement(np.zeros(p, np.float64), 1, strategy="contiguous")
    serve_fn = dist.make_stage_serve(
        mesh, "data", plan, pl, cap_w=8, pair_cap=16, backend="numpy",
        prune="pivot", delta_bound=1.01,
    )
    fv = jnp.zeros((pl.n_slots, 8, m + plan.anchors.shape[0]), f32)
    fvi = jnp.zeros((pl.n_slots, 8), jnp.int32)
    entries.append(trace_entry("stage_serve", serve_fn, (fv, fvi, data, valid, ids)))

    # ---- the incremental cross path (ISSUE-8): ΔR×R_old in
    # ``DistIndex.insert_batch`` rides the SAME serve stage — the delta is
    # the W batch, the resident V buffers stay pinned. Traced with a
    # delta-sized batch so the contract (3 all_to_all, W side only, zero
    # V-side bytes per insert) is pinned for the streaming entry point too;
    # the [suffix] lookup maps it onto stage_serve's contracted counts.
    d_rows = jnp.zeros((4, m), f32)
    entries.append(trace_entry(
        "stage_serve[incremental]", serve_fn,
        (fv, fvi, d_rows, jnp.ones((4,), f32), jnp.arange(4, dtype=jnp.int32)),
    ))

    return entries


# ---------------------------------------------------------------------------
# Contract assembly, assertion, baseline diff
# ---------------------------------------------------------------------------


def build_contracts() -> dict:
    import jax

    from repro.core import verify as verify_lib

    entries = repo_entries()
    recompile = audit_bucket_family(verify_lib.bucket_size, 1024, 4096)
    violations: list[str] = []

    for e in entries:
        for err in e["errors"]:
            violations.append(f"{e['name']}: {err}")
        if e["f64_casts"]:
            violations.append(
                f"{e['name']}: {e['f64_casts']} convert_element_type -> "
                f"float64 equation(s) in the trace"
            )
        base = e["name"].split("[")[0]
        expected = EXPECTED_COLLECTIVES.get(e["name"], EXPECTED_COLLECTIVES.get(base, {}))
        if e["collectives"] != expected:
            violations.append(
                f"{e['name']}: collective contract violated — traced "
                f"{e['collectives'] or '{}'}, contracted {expected or '{}'}"
            )
    violations.extend(f"bucket-family: {err}" for err in recompile["errors"])

    return {
        "version": 1,
        "jax": jax.__version__,
        "entries": {e["name"]: {k: v for k, v in e.items() if k != "name"} for e in entries},
        "recompile": recompile,
        "violations": violations,
    }


def diff_against_baseline(contracts: dict, baseline_path: str) -> list[str]:
    """CI regression gate: collectives and bucket counts may not GROW past
    the committed baseline (improvements are fine and prompt a re-baseline)."""
    if not os.path.exists(baseline_path):
        return [
            f"no baseline at {baseline_path} — run `python -m spjoin_lint "
            f"--audit --write-baseline` and commit it"
        ]
    with open(baseline_path) as f:
        base = json.load(f)
    problems: list[str] = []
    for name, entry in contracts["entries"].items():
        b = base.get("entries", {}).get(name)
        if b is None:
            problems.append(
                f"{name}: new entry point not in baseline — re-baseline "
                f"deliberately with --write-baseline"
            )
            continue
        for prim, n in entry["collectives"].items():
            if n > b["collectives"].get(prim, 0):
                problems.append(
                    f"{name}: {prim} count grew {b['collectives'].get(prim, 0)} "
                    f"-> {n} vs baseline"
                )
        if entry["f64_casts"] > b.get("f64_casts", 0):
            problems.append(f"{name}: f64 casts grew vs baseline")
    rec, brec = contracts["recompile"], base.get("recompile", {})
    for k in ("v_buckets", "w_buckets"):
        if rec[k] > brec.get(k, rec[k]):
            problems.append(
                f"recompile regression: {k} grew {brec.get(k)} -> {rec[k]} — "
                f"the bucket quantizer got finer; every extra shape is an "
                f"XLA compile"
            )
    return problems


def run_audit(
    out_path: str = "runs/contracts.json",
    baseline_path: str = "tools/spjoin_lint/contracts_baseline.json",
    write_baseline: bool = False,
) -> tuple[dict, list[str]]:
    """Build contracts, write the artifact, and return (contracts, problems)."""
    contracts = build_contracts()
    problems = list(contracts["violations"])
    pathlib.Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(contracts, f, indent=2, sort_keys=True)
    if write_baseline:
        # Baseline stores the diffable surface only (no jax-version-specific
        # noise beyond what we pin).
        with open(baseline_path, "w") as f:
            json.dump(contracts, f, indent=2, sort_keys=True)
    else:
        problems.extend(diff_against_baseline(contracts, baseline_path))
    return contracts, problems
