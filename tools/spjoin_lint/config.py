"""Rule configuration for the SP-Join contract linter.

Everything repo-specific lives here: which modules are in scope, which
functions are hot (and in which tier), where collectives are blessed, and
the waiver ratchet. The rule implementations in ``rules.py`` are generic;
this file is the policy.

Two-tier hot-scope model (docs/INVARIANTS.md):

  "traced"  the function body runs under ``jax.jit`` / ``shard_map`` /
            ``vmap`` / ``scan`` — a host sync here is a trace error or a
            silent recompile trigger, so ALL host-sync constructs are
            flagged, plus ``int()``/``float()``/``bool()`` on anything that
            is not a static argument.
  "stream"  a host-side streaming driver (the verify engine's tile loop,
            the serving query path). Syncs are its job — but one sync *per
            tile* is the difference between streaming and stalling, so
            sync constructs are flagged only inside ``for``/``while``
            bodies, where they must carry a waiver with a justification.

Traced scopes are mostly DETECTED structurally (functions passed to
``jax.jit`` / ``jax.shard_map`` / ``jax.vmap`` / ``jax.lax.scan`` /
``pl.pallas_call``, plus everything they call in the same module); the
lists below only add what structure cannot see (closures returned by a
factory and invoked through a variable) and the stream tier, which is a
design decision, not a syntactic fact.
"""
from __future__ import annotations

# Rule identifiers (the names used in `# spjoin-lint: allow[...]` waivers).
RULES = (
    "host-sync",  # no host/device sync in hot scopes
    "dispatch-triad",  # ops.py public fns need ref oracle + pallas + dispatch
    "f64-cast",  # no float64 / weak-f64 promotion in kernel paths
    "dyn-control",  # no data-dependent Python control flow under trace
    "collective-site",  # collectives only at blessed sites
    "pallas-confined",  # core/ must not import raw kernel modules
    "waiver-hygiene",  # waivers are justified, known, used, and bounded
)

# Files the linter runs over, as posix-path suffixes.
LINT_ROOTS = ("repro/core/", "repro/kernels/")

# ---------------------------------------------------------------------------
# Hot scopes
# ---------------------------------------------------------------------------

# Host streaming drivers: sync-in-loop is flagged, sync-outside-loop is fine.
# Qualnames are dotted nesting without <locals> ("Class.method", "outer.inner").
STREAM_SCOPES: dict[str, frozenset[str]] = {
    "repro/core/verify.py": frozenset(
        {"verify_cell_lists", "verify_pairs", "prune_band",
         "_flush_window_batch"}
    ),
    "repro/core/index.py": frozenset(
        {
            "MetricIndex.route",
            "MetricIndex.query_batch",
            "MetricIndex.query",
            "MetricIndex.insert_batch",
        }
    ),
    "repro/core/distributed.py": frozenset(
        {"DistIndex.query_batch", "DistIndex.insert_batch"}
    ),
}

# Traced scopes the structural detector cannot see: closures RETURNED by a
# factory and called through a local variable (the dispatch/shuffle closures
# are bound with `v_dispatch = _make_v_dispatch(...)` and invoked as
# `v_dispatch(...)` — no FunctionDef of that name is reachable by name
# resolution from the call site).
EXTRA_TRACED: dict[str, frozenset[str]] = {
    "repro/core/distributed.py": frozenset(
        {
            "_make_v_dispatch.v_dispatch",
            "_make_w_dispatch.w_dispatch",
            "_make_exchange.exchange",
            "_make_exchange.flat",
        }
    ),
}

# Scopes exempt from hot-scope rules entirely. reference_verify is the SEED
# baseline kept verbatim as the benchmark/parity oracle — its dense eager
# loop is the thing the engine exists to replace, not a hot path.
EXEMPT_SCOPES: dict[str, frozenset[str]] = {
    "repro/core/verify.py": frozenset({"reference_verify"}),
}

# ---------------------------------------------------------------------------
# Rule scoping
# ---------------------------------------------------------------------------

# dispatch-triad applies to these modules' PUBLIC functions that take a
# keyword-only `backend` argument.
TRIAD_MODULES = ("repro/kernels/ops.py",)

# f64-cast applies module-wide in kernels/ (everything there feeds a kernel
# path) and inside traced scopes elsewhere. Host-side planners (placement,
# cost_model) legitimately use float64 numpy.
F64_MODULE_WIDE = ("repro/kernels/",)

# pallas-confined: core/ may import only these names from repro.kernels —
# the dispatch layer and the jnp oracle. Raw kernel modules and pallas
# itself are off limits outside kernels/ (layering: core -> ops -> pallas).
BLESSED_KERNEL_IMPORTS = frozenset({"ops", "ref"})
RAW_KERNEL_MODULES = frozenset({"pairdist", "mapassign", "histogram", "compact"})

# collective-site: communication primitives and where each is blessed.
# Sites are (file suffix, top-level qualname) — closures inside the listed
# function are covered. Anything not listed here has NO blessed site.
COLLECTIVE_PRIMS = frozenset(
    {
        "all_to_all",
        "all_gather",
        "psum",
        "psum_scatter",
        "pmean",
        "pmax",
        "pmin",
        "ppermute",
        "pshuffle",
        "pswapaxes",
        "all_to_all_p",
    }
)
BLESSED_COLLECTIVE_SITES: dict[str, frozenset[tuple[str, str]]] = {
    # THE shuffle: one all_to_all per dispatch buffer, built in exactly one
    # factory shared by stage_verify and stage_serve.
    "all_to_all": frozenset({("repro/core/distributed.py", "_make_exchange")}),
    # Parameter-packet / counting gathers of the sampling + planning passes.
    "all_gather": frozenset(
        {
            ("repro/core/distributed.py", "make_stage_stats"),
            ("repro/core/distributed.py", "make_stage_counts"),
        }
    ),
}

# Host-sync construct lists shared by both tiers.
SYNC_NP_FUNCS = frozenset({"asarray", "array"})  # np.asarray / np.array
SYNC_METHODS = frozenset({"item", "block_until_ready"})
SYNC_JAX_FUNCS = frozenset({"device_get"})

# ---------------------------------------------------------------------------
# Waiver ratchet
# ---------------------------------------------------------------------------

# Maximum number of `# spjoin-lint: allow[...]` waivers across the linted
# tree. This is a RATCHET: it equals the number of waivers shipped today, so
# adding a waiver without removing one fails the build and forces the
# conversation. Lower it when waivers are removed; never raise it casually.
MAX_WAIVERS = 5

# Minimum justification length (characters after `--`) for a waiver.
MIN_JUSTIFICATION = 10
