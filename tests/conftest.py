"""Test fixtures (reference strategy: python/ray/tests/conftest.py —
`ray_start_regular`-style local clusters; SURVEY.md §4).

Collective / mesh tests run against a virtual 8-device CPU mesh, the
reference's pattern of CPU-only collective suites mirroring the GPU ones
(util/collective/tests/single_node_cpu_tests vs distributed_gpu_tests).
"""

import os
import sys

# Tests run against the CPU backend with 8 virtual devices (SURVEY.md §4:
# the CPU mirror of the device suites). XLA_FLAGS must be set before the
# first backend init.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# XLA:CPU builds fast, not well: its older emitters, LLVM at level 1 and
# without its expensive passes. What these tests hold is the program's
# arithmetic against a reference, on programs of a few rows that are built
# once and run once, so the building is the time (867 programs in one
# model's first gradient, 40 ms each; tests/test_lfm2_moe.py takes 29% less
# of the CPU so), and the whole of tier-1 has a time limit. Not level 0,
# which is faster still: the newer emitters then sum some bfloat16
# products in bfloat16 (a dp=4 step's loss 0.3% off its unsharded twin,
# test_models_ops.py) and the older ones round two forms of one sum apart
# (test_gated_delta.py, test_loss.py). The chip's compiler, where a test
# compiles for it, takes no notice. tests/cell_rehearsal.py hands the same
# three to the subprocesses it starts.
FAST_BUILD_FLAGS = ("--xla_cpu_use_fusion_emitters=false",
                    "--xla_backend_optimization_level=1",
                    "--xla_llvm_disable_expensive_passes=true")
for _flag in FAST_BUILD_FLAGS:
    if _flag.split("=")[0].lstrip("-") not in _flags:
        _flags += " " + _flag
os.environ["XLA_FLAGS"] = _flags
os.environ["JAX_PLATFORMS"] = "cpu"
# Worker subprocesses spawned by ray_tpu set their own env; the driver-side
# jax (this process) is pinned to cpu here, also for a jax that something
# imported before the variable above was set:
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 runs")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection chaos runs (long; also marked "
        "slow so tier-1's `-m 'not slow'` filter excludes them)")
    config.addinivalue_line(
        "markers",
        "perf_smoke: fast, deterministic performance guards (syscall/"
        "write-count based, never wall-clock) — run in tier-1 and "
        "selectable standalone via `-m perf_smoke`")
    config.addinivalue_line(
        "markers",
        "lint: project-invariant static-analysis suite "
        "(ray_tpu/devtools/lint) run against the live tree in tier-1; "
        "selectable standalone via `-m lint`")


# Suites that run under the dynamic lock-order tracker
# (_private/lockdep.py): the transport-framing tier exercises the
# writer/executor/gate locks directly, and the chaos tier drives the
# whole control plane through failure paths — both must come out with
# ZERO potential-ABBA cycles. Assertion per test so a report is
# attributable to the test that produced it.
_LOCKDEP_SUITES = {"test_transport_framing", "test_fault_injection",
                   "test_direct_calls", "test_cross_plane_ordering",
                   "test_serve_direct", "test_put_path", "test_shuffle"}


@pytest.fixture(autouse=True)
def _lockdep_guard(request, tmp_path_factory):
    name = getattr(request.module, "__name__", "")
    if name.rpartition(".")[2] not in _LOCKDEP_SUITES:
        yield
        return
    from ray_tpu._private import lockdep
    lockdep.reset()
    prev = lockdep.enabled
    # Spill dir: cycles recorded in SPAWNED daemons/workers (which
    # inherit RAY_TPU_LOCKDEP=1) are process-local and die with them —
    # every process appends cycles here at record time, so the
    # assertion below covers the whole process tree, not just the head.
    dump_dir = str(tmp_path_factory.mktemp("lockdep"))
    prev_dir = os.environ.get("RAY_TPU_LOCKDEP_DIR")
    os.environ["RAY_TPU_LOCKDEP_DIR"] = dump_dir
    lockdep.configure(True)
    try:
        yield
        cycles = list(lockdep.cycle_reports())
        seen = {(tuple(c["cycle"]), c.get("pid")) for c in cycles}
        for rep in lockdep.collect_dumped_cycles(dump_dir):
            key = (tuple(rep["cycle"]), rep.get("pid"))
            if key not in seen:
                seen.add(key)
                cycles.append(rep)
        if cycles:
            child = [c for c in cycles if c.get("pid") != os.getpid()]
            pytest.fail(
                f"lockdep: {len(cycles)} potential ABBA deadlock(s) "
                f"recorded during this test ({len(child)} in child "
                f"processes):\n" + lockdep.format_reports()
                + "".join(f"\n[child pid {c.get('pid')}] cycle "
                          f"{' -> '.join(c['cycle'])}" for c in child))
    finally:
        lockdep.configure(prev)
        if prev_dir is None:
            os.environ.pop("RAY_TPU_LOCKDEP_DIR", None)
        else:
            os.environ["RAY_TPU_LOCKDEP_DIR"] = prev_dir


# Suites that run under the refcount-conservation shadow ledger
# (_private/refdebug.py): the direct-call and cross-plane tiers
# exercise the buffered-accounting surface (parks, barriers, borrows,
# escapes) and the chaos tier kills processes mid-accounting — every
# test must replay to a clean conservation report. Per-test journal
# dir so a violation is attributable to the test that produced it
# (these suites all build per-test clusters).
_REFDEBUG_SUITES = {"test_direct_calls", "test_cross_plane_ordering",
                    "test_fault_injection", "test_drain",
                    "test_serve_direct", "test_transfer",
                    "test_put_path", "test_shuffle"}


@pytest.fixture(autouse=True)
def _refdebug_guard(request, tmp_path_factory):
    name = getattr(request.module, "__name__", "")
    if name.rpartition(".")[2] not in _REFDEBUG_SUITES:
        yield
        return
    from ray_tpu._private import refdebug
    refdebug.reset()
    prev = refdebug.enabled
    # Journal dir: every process of the run (head, daemons, workers —
    # which inherit RAY_TPU_REFDEBUG=1) appends its refcount events
    # here at record time, SIGKILL-safe; the checker replays the merged
    # journals on teardown.
    dump_dir = str(tmp_path_factory.mktemp("refdebug"))
    prev_dir = os.environ.get("RAY_TPU_REFDEBUG_DIR")
    os.environ["RAY_TPU_REFDEBUG_DIR"] = dump_dir
    refdebug.configure(True)
    try:
        yield
        refdebug.reset()  # close our journal handle before replaying
        violations = refdebug.check_journals(dump_dir)
        if violations:
            pytest.fail(
                f"refdebug: {len(violations)} refcount-conservation "
                f"violation(s) recorded during this test:\n"
                + refdebug.format_report(violations))
    finally:
        refdebug.configure(prev)
        if prev_dir is None:
            os.environ.pop("RAY_TPU_REFDEBUG_DIR", None)
        else:
            os.environ["RAY_TPU_REFDEBUG_DIR"] = prev_dir


# Suites that run under the wire-protocol conformance tap
# (_private/wiretap.py): the protocol-heavy tiers replay every frame
# crossing a recv mux through the session DFAs of
# devtools/lint/protocol_model.py — the dynamic half of the
# protocol-order/payload-schema static passes. Per-test journal dir so
# a nonconforming sequence is attributable to the test that produced
# it (every process of the run appends violations at record time,
# SIGKILL-safe).
_WIRETAP_SUITES = {"test_direct_calls", "test_cross_plane_ordering",
                   "test_serve_direct", "test_transfer", "test_shuffle"}


@pytest.fixture(autouse=True)
def _wiretap_guard(request, tmp_path_factory):
    name = getattr(request.module, "__name__", "")
    if name.rpartition(".")[2] not in _WIRETAP_SUITES:
        yield
        return
    from ray_tpu._private import wiretap
    wiretap.reset()
    prev = wiretap.enabled
    dump_dir = str(tmp_path_factory.mktemp("wiretap"))
    prev_dir = os.environ.get("RAY_TPU_WIRETAP_DIR")
    os.environ["RAY_TPU_WIRETAP_DIR"] = dump_dir
    wiretap.configure(True)
    try:
        yield
        wiretap.reset()  # close our journal handle before replaying
        violations = wiretap.collect_violations(dump_dir)
        if violations:
            pytest.fail(
                f"wiretap: {len(violations)} wire-protocol "
                f"violation(s) recorded during this test:\n"
                + wiretap.format_report(violations))
    finally:
        wiretap.configure(prev)
        if prev_dir is None:
            os.environ.pop("RAY_TPU_WIRETAP_DIR", None)
        else:
            os.environ["RAY_TPU_WIRETAP_DIR"] = prev_dir


# Suites that run under the Eraser-style lockset race detector
# (_private/racedebug.py): the direct-call, cross-plane, shuffle and
# chaos tiers drive the hot concurrent classes (scheduler queue,
# writer queues, reply tables, actor queues) from many threads at
# once — every tracked field must keep a non-empty candidate lockset
# for the whole test. Per-test spill dir so a race is attributable to
# the test that produced it (spawned daemons/workers inherit
# RAY_TPU_RACEDEBUG=1 and append reports at record time, SIGKILL-safe).
_RACEDEBUG_SUITES = {"test_direct_calls", "test_cross_plane_ordering",
                     "test_shuffle", "test_fault_injection"}


@pytest.fixture(autouse=True)
def _racedebug_guard(request, tmp_path_factory):
    name = getattr(request.module, "__name__", "")
    if name.rpartition(".")[2] not in _RACEDEBUG_SUITES:
        yield
        return
    from ray_tpu._private import racedebug
    racedebug.reset()
    prev = racedebug.enabled
    dump_dir = str(tmp_path_factory.mktemp("racedebug"))
    prev_dir = os.environ.get("RAY_TPU_RACEDEBUG_DIR")
    os.environ["RAY_TPU_RACEDEBUG_DIR"] = dump_dir
    racedebug.configure(True)
    try:
        yield
        races = racedebug.race_reports()
        seen = {(r["owner"], r["field"], r.get("pid")) for r in races}
        for rep in racedebug.collect_dumped_races(dump_dir):
            key = (rep["owner"], rep["field"], rep.get("pid"))
            if key not in seen:
                seen.add(key)
                races.append(rep)
        if races:
            child = [r for r in races if r.get("pid") != os.getpid()]
            pytest.fail(
                f"racedebug: {len(races)} potential data race(s) "
                f"recorded during this test ({len(child)} in child "
                f"processes):\n" + racedebug.format_reports()
                + "".join(f"\n[child pid {r.get('pid')}] "
                          f"{r['owner']}.{r['field']}" for r in child))
    finally:
        # configure(prev) restores the racedebug flag only; lockdep —
        # which racedebug.configure(True) switched on as its lockset
        # source — is left alone (the lockdep guard owns that flag).
        racedebug.configure(prev)
        if prev_dir is None:
            os.environ.pop("RAY_TPU_RACEDEBUG_DIR", None)
        else:
            os.environ["RAY_TPU_RACEDEBUG_DIR"] = prev_dir


@pytest.fixture(scope="module")
def ray_start_shared():
    """Module-shared cluster (reference: ray_start_regular_shared)."""
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular():
    """Fresh cluster per test (reference: ray_start_regular)."""
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    """Test calls init() itself (reference: conftest.py:449
    shutdown_only). Shuts down BEFORE as well: a module-scoped session
    left running by an earlier test file must not leak into a test that
    needs its own init() (e.g. a custom object_store_memory)."""
    ray_tpu.shutdown()
    yield
    ray_tpu.shutdown()
