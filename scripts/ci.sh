#!/usr/bin/env bash
# Tier-1 CI on the CPU: the choke-point invariants (no raw mesh APIs outside
# src/repro/substrate/, ...), the test suite, and the launcher smokes at
# smoke scale (--smoke).  The chip's own proof is chip_smoke.py.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

echo "ci: forbidden-API grep (raw mesh-context API outside substrate)"
# bare names too, so `from jax import set_mesh` can't sneak past; shard_map
# is matched only as a jax import/attribute since `from ..substrate import
# shard_map` is the sanctioned spelling
violations=$(grep -rnE "set_mesh|use_mesh|AxisType|get_abstract_mesh|jax\.shard_map|from jax import .*shard_map|jax\.experimental.*shard_map" \
    src/ --include='*.py' | grep -v "^src/repro/substrate/" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- raw mesh API outside src/repro/substrate/:"
    echo "$violations"
    exit 1
fi
echo "ci: choke-point invariant holds"

# Scoped sharding profiles (ISSUE 2): LOGICAL_RULES is the baseline table
# inside models/common.py only -- every other module resolves rules through
# the active ShardingProfile (sharding_profile context manager / explicit
# profile= arg), so concurrent engines can't race on a global dict.
echo "ci: forbidden-API grep (LOGICAL_RULES outside models/common.py)"
violations=$(grep -rn "LOGICAL_RULES" src/ tests/ --include='*.py' \
    | grep -v "^src/repro/models/common.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- LOGICAL_RULES accessed outside src/repro/models/common.py:"
    echo "$violations"
    exit 1
fi
echo "ci: profile choke-point invariant holds"

# Profile registry (ISSUE 5): CLI --profile choices derive from the PROFILES
# registry via models/common.py profile_names().  No launcher (or anything
# else in src/) may re-list the profile names in a hardcoded choices list --
# the lists drift the moment a profile is added.
echo "ci: forbidden-API grep (hardcoded profile-name choices lists)"
violations=$(grep -rnE 'choices=\[[^]]*"(baseline|opt1|serve|moe_ep)"' \
    src/ --include='*.py' | grep -v "^src/repro/models/common.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- hardcoded profile-name list (use models.common.profile_names()):"
    echo "$violations"
    exit 1
fi
echo "ci: profile-registry invariant holds"

# Level tables (ISSUE 3): the padded dense tables and the CSR level segments
# are built only by core/taskgraph.py (padded_level_tables /
# csr_level_segments).  No other module may reconstruct them by iterating
# TaskGraph.levels() -- everything downstream consumes the taskgraph builders,
# so the bucketing policy and tie-break ordering have a single owner.
echo "ci: forbidden-API grep (level-table construction outside core/taskgraph.py)"
violations=$(grep -rnE "\.levels\(\)|def padded_level_tables|def csr_level_segments" \
    src/ benchmarks/ --include='*.py' | grep -v "^src/repro/core/taskgraph.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- level tables constructed outside src/repro/core/taskgraph.py:"
    echo "$violations"
    exit 1
fi
echo "ci: level-table choke-point invariant holds"

# Bucketing policy (ISSUE 4): the jit-shape buckets (_geo_bucket), the
# fusion + hybrid-layout thresholds (CSR_FUSE_WASTE / CSR_DENSE_SKEW) and
# the CSR_TRACES counters are owned by core/ceft_jax.py alone, matching the
# level-table gate above -- everything else consumes csr_device_inputs /
# fuse_levels outputs, so changing the bucket policy (and hence what
# recompiles) has a single owner.
echo "ci: forbidden-API grep (CSR bucket policy outside core/ceft_jax.py)"
violations=$(grep -rnE "CSR_TRACES|CSR_FUSE|CSR_DENSE|_bucket\(|def _geo_bucket" \
    src/ benchmarks/ --include='*.py' | grep -v "^src/repro/core/ceft_jax.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- CSR bucket policy accessed outside src/repro/core/ceft_jax.py:"
    echo "$violations"
    exit 1
fi
echo "ci: bucket-policy choke-point invariant holds"

# Plan-cache ownership (ISSUE 6): the graph store, the device-state store
# and the old ceft_jax one-slot caches (_GRAPH_STATE / _REQUEST_GRAPH) are
# owned by sched/plancache.py alone.  Nothing else in src/ or benchmarks/
# may hold segment-table or built-graph caching state -- the invalidation
# invariant (a cost delta may only skip work, never change the schedule)
# is only auditable while the cached state has a single owner.
echo "ci: forbidden-API grep (plan/graph caching state outside sched/plancache.py)"
violations=$(grep -rnE "_GRAPH_STATE|_REQUEST_GRAPH|_GRAPH_STORE|_DEVICE_STATE" \
    src/ benchmarks/ --include='*.py' | grep -v "^src/repro/sched/plancache.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- plan/graph caching state outside src/repro/sched/plancache.py:"
    echo "$violations"
    exit 1
fi
echo "ci: plan-cache ownership invariant holds"

# Placement-plane ownership (ISSUE 7): worker lifecycle state -- the
# _WorkerState machine, the subprocess transport/bootstrap, and the pool
# member list -- is private to serve/pool.py.  The Router (and everything
# else) sees only the public pool API (launch/drain/mark_lost/generate/
# machine), so "where computation lives" keeps a single owner and the
# failure-as-degradation invariant stays auditable.
echo "ci: forbidden-API grep (worker lifecycle state outside serve/pool.py)"
violations=$(grep -rnE "_WorkerState|_worker_main|_SubprocWorker|_InprocWorker|_PoolMember|pool\._members" \
    src/ benchmarks/ --include='*.py' | grep -v "^src/repro/serve/pool.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- worker lifecycle state accessed outside src/repro/serve/pool.py:"
    echo "$violations"
    exit 1
fi
echo "ci: placement-plane ownership invariant holds"

# Fault-injection containment (ISSUE 8): the chaos harness attaches through
# the pool's public handle-wrapper seam, and that seam (plus the injector
# machinery) must stay private to serve/faults.py -- production modules may
# not install handle middleware or reach fault hooks directly.  The launcher
# is the one sanctioned consumer (install_chaos behind --chaos-seed).
echo "ci: forbidden-API grep (fault-injection hooks outside serve/faults.py)"
violations=$(grep -rnE "add_handle_wrapper|_handle_wrappers|_FaultyHandle" \
    src/ benchmarks/ --include='*.py' \
    | grep -v "^src/repro/serve/pool.py:" \
    | grep -v "^src/repro/serve/faults.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- fault-injection hook used outside src/repro/serve/faults.py:"
    echo "$violations"
    exit 1
fi
violations=$(grep -rnE "FaultInjector|FaultPlan|install_chaos" \
    src/ benchmarks/ --include='*.py' \
    | grep -v "^src/repro/serve/faults.py:" \
    | grep -v "^src/repro/launch/serve.py:" || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- fault machinery referenced outside faults.py/launch/serve.py:"
    echo "$violations"
    exit 1
fi
echo "ci: fault-injection containment invariant holds"

# Planner registry (ISSUE 10): serve/ and sched/ select planners by NAME
# through core/planners.py -- importing the scheduler functions themselves
# (ceft_cpop/cpop/heft/heft_down/ceft_heft_up/ceft_heft_down/bruteforce or
# raw list_schedule) would bypass the registry and fork the planner surface.
# Importing the planners module, CeftResult/Plan types, and the machinery
# modules (ceft_jax, machine, taskgraph) stays sanctioned.
echo "ci: forbidden-API grep (scheduler functions imported outside the planner registry)"
violations=$(grep -rnE "from \.\.core\.(cpop|heft|bruteforce) import|from \.\.core import [^#]*\b(ceft_cpop|cpop|heft|heft_down|ceft_heft_up|ceft_heft_down|bruteforce_cpl|list_schedule)\b" \
    src/repro/serve/ src/repro/sched/ --include='*.py' || true)
if [ -n "$violations" ]; then
    echo "ci: FAIL -- scheduler imported directly in serve/ or sched/ (use core.planners by name):"
    echo "$violations"
    exit 1
fi
echo "ci: planner-registry invariant holds"

# Docs completeness (ISSUE 9): docs/architecture.md's module map must name
# every module under src/repro/serve/ and src/repro/sched/ -- a new module
# lands with its line in the map or CI fails -- and every relative markdown
# link in docs/*.md and README.md must resolve to a real file, so the docs
# cannot silently rot as the tree moves.
echo "ci: docs check (module map complete, relative links resolve)"
python - <<'PY'
import pathlib
import re
import sys

root = pathlib.Path(".")
errors = []

arch = (root / "docs" / "architecture.md").read_text()
for pkg in ("serve", "sched"):
    for mod in sorted((root / "src" / "repro" / pkg).glob("*.py")):
        if mod.name == "__init__.py":
            continue
        if f"{pkg}/{mod.name}" not in arch:
            errors.append(f"docs/architecture.md: module map is missing "
                          f"{pkg}/{mod.name}")

link = re.compile(r"\[[^\]]*\]\(([^)#\s]+)(#[^)]*)?\)")
for md in [root / "README.md", *sorted((root / "docs").glob("*.md"))]:
    for target, _frag in link.findall(md.read_text()):
        if "://" in target:
            continue
        if not (md.parent / target).exists():
            errors.append(f"{md}: broken relative link -> {target}")

if errors:
    print("ci: FAIL -- docs check:")
    for e in errors:
        print(f"  {e}")
    sys.exit(1)
print("ci: docs are complete and links resolve")
PY

echo "ci: tier-1 tests"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q

# Router smoke (ISSUE 5): the CEFT-routed multi-tenant front-end end-to-end
# on real smoke engines (--smoke) -- two tenants, a two-profile pool, tiny
# decode.
echo "ci: router smoke (repro.launch.serve --router)"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.launch.serve \
    --router --tenants 2 --pool serve,baseline --requests 2 \
    --prompt-len 8 --max-new 2 --smoke > /dev/null
echo "ci: router smoke ok"

# Planner-registry smoke (ISSUE 10): the same front-end end-to-end with a
# NON-CEFT planner selected by name and the moldable fork-join axis on --
# the registry seam must serve real requests, not just pass unit tests.
echo "ci: non-CEFT planner smoke (--planner heft --max-split 2)"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.launch.serve \
    --router --tenants 2 --pool serve,baseline --requests 2 \
    --prompt-len 8 --max-new 2 --planner heft --max-split 2 --smoke \
    | grep "planner=heft" > /dev/null
echo "ci: non-CEFT planner smoke ok"

# Chaos smoke (ISSUE 8): the same front-end under the seeded fault injector
# (kills + hangs + delayed/duplicated replies scheduled by the seed) with
# the deadline watchdog armed.  The launcher exits nonzero unless every
# admitted request completed exactly once and hedge work stayed bounded by
# the overdue critical-path count -- the chaos soak's acceptance, as a smoke.
echo "ci: chaos smoke (repro.launch.serve --router --chaos-seed)"
# seed 13 @ rate 0.35 schedules a kill, two hangs and a held-duplicate reply
# across the first calls -- verified deterministic by FaultPlan.seeded
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.launch.serve \
    --router --tenants 2 --pool serve,baseline --pool-size 4 --requests 3 \
    --prompt-len 8 --max-new 2 --deadline-factor 3 --chaos-seed 13 \
    --chaos-rate 0.35 --smoke \
    | grep "chaos: every admitted request completed exactly once"
echo "ci: chaos smoke ok"

# Perf trajectory + regression gate (ISSUE 3 + 4): refresh the
# machine-readable CEFT baseline on every CI pass, then diff the fresh rows
# against the *committed* baseline -- a >2x slowdown of any jax_csr row fails
# CI (tolerant of smoke-scale noise via the absolute-ms floor; rows absent
# from the baseline are skipped).  The committed baseline is assumed to come
# from comparable hardware (each passing CI run rewrites it, so committing
# the refreshed file keeps the baseline anchored to the CI machine); on a
# much slower box, regenerate the baseline once before trusting the gate.
# The shrunk scale keeps this a smoke-sized run; jax_csr rows are checked
# against jax_padded (bit-identical) and the float64 numpy path inside the
# bench.
echo "ci: CEFT perf baseline (BENCH_ceft.json, shrunk scale)"
baseline=$(mktemp)
trap 'rm -f "$baseline"' EXIT
if ! git show HEAD:BENCH_ceft.json > "$baseline" 2>/dev/null; then
    cp BENCH_ceft.json "$baseline"   # no git history: gate against last run
fi
# the tournament suite rides in the same pass: its in-bench asserts (the
# loud NONZERO misidentification rate, the oracle dominance check, and the
# moldable router's mapping-change check) make it a correctness gate too
REPRO_BENCH_SCALE=0.05 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --only ceft_throughput serve_router tournament \
    --json BENCH_ceft.json > /dev/null
echo "ci: wrote BENCH_ceft.json"
echo "ci: perf-regression gate (fresh jax_csr rows vs committed baseline)"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.check_regression "$baseline" BENCH_ceft.json \
    --impl jax_csr --threshold 2.0
