"""Tests for the spjoin-lint contract checker (tools/spjoin_lint).

Two layers, tested separately:

* AST rules — each rule gets a good/bad pair: the bad snippet (or the
  known-violating fixture module under ``tests/lint_fixtures/``) must fire
  the rule, the good one must stay silent.
* jaxpr auditor — each assertion family (f64 cast, collective budget,
  dynamic shapes, recompile budget) is driven with a function built to
  violate it and must be rejected.

The fixture tree mirrors ``repro/...`` paths because several rules are
scoped by path suffix (triad only in ``repro/kernels/ops.py``, stream tiers
only in the configured files).
"""
import json
import pathlib
import textwrap

import pytest

from spjoin_lint import astlint, cli, config, jaxpr_audit, waivers

FIXTURES = pathlib.Path(__file__).parent / "lint_fixtures"


def lint_snippet(tmp_path, relname: str, code: str):
    """Write ``code`` at tmp_path/<relname> and lint that one file."""
    path = tmp_path / relname
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return astlint.lint_file(path)


def rules_fired(violations):
    return {v.rule for v in violations}


# ---------------------------------------------------------------------------
# AST rules: good/bad pairs
# ---------------------------------------------------------------------------


class TestHostSync:
    def test_bad_traced_sync(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax, numpy as np

            @jax.jit
            def f(x):
                return np.asarray(x).sum()
        """)
        assert rules_fired(vs) == {"host-sync"}

    def test_bad_item_and_float(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x[0].item() + float(jnp.max(x))
        """)
        assert len([v for v in vs if v.rule == "host-sync"]) == 2

    def test_good_static_args_not_flagged(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax
            import jax.numpy as jnp

            def f(x, delta):
                return x * float(delta)

            g = jax.jit(f, static_argnames=("delta",))
        """)
        assert vs == []

    def test_good_host_code_not_flagged(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import numpy as np

            def planner(xs):
                return float(np.asarray(xs).sum())
        """)
        assert vs == []

    def test_jit_assignment_seeds_traced_scope(self, tmp_path):
        # The seed is `g = jax.jit(f)` — f has no decorator.
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax, numpy as np

            def f(x):
                return np.asarray(x)

            g = jax.jit(f)
        """)
        assert rules_fired(vs) == {"host-sync"}

    def test_propagation_reaches_callee(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax, numpy as np

            def helper(x):
                return np.asarray(x)

            @jax.jit
            def f(x):
                return helper(x)
        """)
        assert rules_fired(vs) == {"host-sync"}


class TestStreamTier:
    def test_fixture_flags_in_loop_only(self):
        vs = astlint.lint_file(FIXTURES / "repro/core/verify.py")
        sync = [v for v in vs if v.rule == "host-sync"]
        # Two in-loop syncs; pre-loop np.asarray and cold_helper are silent.
        assert len(sync) == 2
        assert all("verify_pairs" in v.message for v in sync)
        assert {v.line for v in sync} == {17, 21}


class TestDispatchTriad:
    def test_fixture_missing_legs(self):
        vs = astlint.lint_file(FIXTURES / "repro/kernels/ops.py")
        triad = [v for v in vs if v.rule == "dispatch-triad"]
        by_fn = {}
        for v in triad:
            name = v.message.split("`")[1]
            by_fn.setdefault(name, []).append(v)
        assert set(by_fn) == {"missing_pallas", "missing_everything"}
        assert len(by_fn["missing_pallas"]) == 1  # only the pallas leg
        assert len(by_fn["missing_everything"]) == 3  # all three legs
        # complete_op and delegating_op (transitively) are silent.

    def test_good_triad_not_flagged(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/kernels/ops.py", """
            from repro.kernels import pairdist as _pd
            from repro.kernels import ref

            def resolve_backend(b="auto"):
                return b

            def op(x, y, *, backend="auto"):
                backend = resolve_backend(backend)
                if backend == "pallas":
                    return _pd.kernel(x, y)
                return ref.oracle(x, y)
        """)
        assert [v for v in vs if v.rule == "dispatch-triad"] == []


class TestF64Cast:
    def test_fixture_module_wide_in_kernels(self):
        vs = astlint.lint_file(FIXTURES / "repro/kernels/ops.py")
        f64 = [v for v in vs if v.rule == "f64-cast"]
        assert len(f64) == 3  # np.float64, .astype(float), dtype=float

    def test_core_only_traced_scopes(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax
            import numpy as np

            def host_planner(x):
                return np.zeros(4, np.float64) + x

            @jax.jit
            def f(x):
                return x.astype("float64")
        """)
        f64 = [v for v in vs if v.rule == "f64-cast"]
        assert len(f64) == 1  # only the jitted astype; the planner is free


class TestDynControl:
    def test_bad_if_over_tracer(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                if jnp.sum(x) > 0:
                    return x
                return -x
        """)
        assert "dyn-control" in rules_fired(vs)

    def test_good_static_if(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax
            import jax.numpy as jnp

            def f(x, metric):
                if metric == "l2":
                    return jnp.square(x)
                return jnp.abs(x)

            g = jax.jit(f, static_argnames=("metric",))
        """)
        assert vs == []

    def test_good_host_utility_call(self, tmp_path):
        # jax.default_backend() returns a Python string, not a tracer.
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax

            @jax.jit
            def f(x):
                return x * (2 if jax.default_backend() == "tpu" else 1)
        """)
        assert vs == []


class TestCollectiveSite:
    def test_bad_unblessed_all_to_all(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax

            def shuffle(x):
                return jax.lax.all_to_all(x, "data", 0, 0)
        """)
        assert rules_fired(vs) == {"collective-site"}

    def test_good_blessed_factory(self, tmp_path):
        # Same call, but in the blessed (file, function) site.
        vs = lint_snippet(tmp_path, "repro/core/distributed.py", """
            import jax

            def _make_exchange(axis):
                def exchange(x):
                    return jax.lax.all_to_all(x, axis, 0, 0)
                return exchange
        """)
        assert [v for v in vs if v.rule == "collective-site"] == []


class TestPallasConfined:
    def test_bad_core_imports(self):
        vs = astlint.lint_file(FIXTURES / "repro/core/bad_hotpath.py")
        confined = [v for v in vs if v.rule == "pallas-confined"]
        assert len(confined) == 2  # raw kernel module + pallas itself

    def test_good_ops_import(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            from repro.kernels import ops, ref

            def f(x, y):
                return ops.pairdist(x, y, metric="l2", backend="auto")
        """)
        assert vs == []


class TestWaivers:
    def test_waiver_suppresses(self, tmp_path):
        vs = lint_snippet(tmp_path, "repro/core/mod.py", """
            import jax, numpy as np

            @jax.jit
            def f(x):
                # spjoin-lint: allow[host-sync] -- fixture: deliberately waived
                return np.asarray(x).sum()
        """)
        assert vs == []

    def test_waiver_hygiene_from_fixture(self):
        vs = astlint.lint_file(FIXTURES / "repro/core/bad_hotpath.py")
        hygiene = [v for v in vs if v.rule == "waiver-hygiene"]
        msgs = " | ".join(v.message for v in hygiene)
        assert "unknown rule" in msgs
        assert "justification" in msgs
        assert "unused waiver" in msgs

    def test_ratchet(self, tmp_path, monkeypatch):
        monkeypatch.setattr(config, "MAX_WAIVERS", 1)
        code = """
            import jax, numpy as np

            @jax.jit
            def f(x):
                a = np.asarray(x)  # spjoin-lint: allow[host-sync] -- fixture waiver one
                b = np.asarray(x)  # spjoin-lint: allow[host-sync] -- fixture waiver two
                return a + b
        """
        path = tmp_path / "repro/core/mod.py"
        path.parent.mkdir(parents=True)
        path.write_text(textwrap.dedent(code))
        vs, n = astlint.lint_paths([str(tmp_path)])
        assert n == 2
        assert any(
            v.rule == "waiver-hygiene" and "ratchet" in v.message for v in vs
        )

    def test_parse_binds_standalone_comment(self):
        src = "x = 1\n# spjoin-lint: allow[host-sync] -- next line\n\ny = 2\n"
        ws = waivers.parse_waivers(src, "f.py")
        assert len(ws) == 1 and ws[0].target_line == 4


class TestFixtureInventory:
    def test_bad_fixture_fires_six_rules(self):
        """The headline acceptance check: >= 6 distinct AST rules
        demonstrably fire across the known-violating fixture tree."""
        fired = set()
        for f in sorted(FIXTURES.rglob("*.py")):
            if f.name != "clean_mod.py":
                fired |= rules_fired(astlint.lint_file(f))
        assert {
            "host-sync", "dispatch-triad", "f64-cast", "dyn-control",
            "collective-site", "pallas-confined", "waiver-hygiene",
        } <= fired

    def test_clean_fixture_is_silent(self):
        assert astlint.lint_file(FIXTURES / "repro/core/clean_mod.py") == []

    def test_real_tree_is_clean(self):
        root = pathlib.Path(__file__).parent.parent / "src"
        vs, n_waivers = astlint.lint_paths([str(root)])
        assert vs == []
        assert n_waivers <= config.MAX_WAIVERS


# ---------------------------------------------------------------------------
# jaxpr auditor
# ---------------------------------------------------------------------------


class TestJaxprAudit:
    def test_rejects_f64_cast(self):
        import jax
        import jax.numpy as jnp

        def promoting(x):
            return x.astype(jnp.float64)

        with jax.enable_x64(True):
            entry = jaxpr_audit.trace_entry(
                "bad_f64", promoting, (jnp.zeros((4,), jnp.float32),)
            )
        assert entry["f64_casts"] >= 1

    def test_collective_budget_counts_all_sites(self):
        # A function with TWO all_to_all calls must trace as 2, exceeding a
        # 1-per-stage contract.
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("data",))

        def noisy(x):
            a = jax.lax.all_to_all(x[None], "data", 0, 0)
            b = jax.lax.all_to_all(x[None], "data", 0, 0)
            return a + b

        fn = jax.shard_map(
            noisy, mesh=mesh, in_specs=jax.sharding.PartitionSpec("data"),
            out_specs=jax.sharding.PartitionSpec("data"),
        )
        entry = jaxpr_audit.trace_entry(
            "two_shuffles", fn, (jnp.zeros((1, 4), jnp.float32),)
        )
        assert entry["collectives"] == {"all_to_all": 2}
        assert entry["collectives"] != {"all_to_all": 1}

    def test_rejects_dynamic_output_shape(self):
        import jax.numpy as jnp

        def dynamic(x):
            return x[x > 0]  # boolean masking: data-dependent shape

        entry = jaxpr_audit.trace_entry(
            "dyn", dynamic, (jnp.zeros((8,), jnp.float32),)
        )
        assert entry["errors"]
        assert "untraceable" in entry["errors"][0]

    def test_recompile_budget_flags_identity_bucketing(self):
        # An identity "bucketing" (no quantization) has cap distinct shapes
        # and must blow any sane budget; the real quarter-pow2 one must not.
        from repro.core.verify import bucket_size

        bad = jaxpr_audit.audit_bucket_family(
            lambda n, cap, floor=8: max(n, floor), 1024, 4096
        )
        assert bad["errors"]
        good = jaxpr_audit.audit_bucket_family(bucket_size, 1024, 4096)
        assert good["errors"] == []
        assert good["v_buckets"] <= jaxpr_audit.RECOMPILE_BUDGET["v_buckets"]

    def test_walk_recurses_into_pjit(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def inner(x):
            return x.astype(jnp.float64)

        def outer(x):
            return inner(x) + 1

        with jax.enable_x64(True):
            entry = jaxpr_audit.trace_entry(
                "nested", outer, (jnp.zeros((4,), jnp.float32),)
            )
        assert entry["f64_casts"] >= 1  # found inside the pjit sub-jaxpr


@pytest.mark.slow
class TestEndToEnd:
    def test_full_audit_matches_baseline(self, tmp_path):
        contracts, problems = jaxpr_audit.run_audit(
            out_path=str(tmp_path / "contracts.json"),
            baseline_path=str(
                pathlib.Path(__file__).parent.parent
                / "tools/spjoin_lint/contracts_baseline.json"
            ),
        )
        assert problems == []
        assert (tmp_path / "contracts.json").exists()
        written = json.loads((tmp_path / "contracts.json").read_text())
        assert written["entries"].keys() == contracts["entries"].keys()

    def test_cli_end_to_end(self, capsys):
        root = pathlib.Path(__file__).parent.parent
        rc = cli.main([str(root / "src")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "0 violation(s)" in out

    def test_cli_fails_on_fixture(self, capsys):
        rc = cli.main([str(FIXTURES)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "[dispatch-triad]" in out and "[host-sync]" in out
