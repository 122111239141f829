"""What every `tests/test_*_cell_rehearsal.py` does the same way, once: a
train cell end to end at tiny size on the CPU through the benchmark's own
command lines (`chipbench/run.py --rehearsal`, `limit_readings.py`,
`step_counters.py`, `scope_profile.py`), on BENCHMARK.json as it is with
the cell's configuration and traffic mix swapped for tiny stand-ins.

chipbench's own rehearsal (chipbench/tests, not part of tier-1) looks every
configuration up in rehearsal/data/tiny.json and asserts `reduced == []`;
both are files the benchmark already has, which a PR that adds a cell may
not edit (PERF.md section 7), so each new cell is rehearsed from a file
here. That file keeps what is the cell's own: its names, its tiny
stand-ins, which faults it plants and every assertion about the cell. The
numbers of a CPU run mean nothing and are written nowhere.

Not collected (no `test_` prefix); tests/test_cell_files.py holds the
files to it."""

import json
import os
import shutil
import subprocess
import sys
import tempfile

from conftest import FAST_BUILD_FLAGS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "chipbench/tests/rehearsal/data"


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def manifest(tmp_path_factory, cell, tiny_config, tiny_traffic) -> str:
    """The path of a BENCHMARK.json that lists `cell` alone, its
    configuration's file and its traffic mix the tiny ones under TINY."""
    m = load("BENCHMARK.json")
    workload = next(w for w in m["workloads"] if w["name"] == cell)
    config = next(c for c in m["configs"] if c["name"] == workload["config"])
    m["paths"] = [TINY]
    config["file"] = f"{TINY}/configs/{tiny_config}.json"
    workload["traffic"] = tiny_traffic
    m["workloads"], m["configs"] = [workload], [config]
    path = tmp_path_factory.mktemp(cell) / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    return str(path)


def subprocess_env(cache_dir=None) -> dict:
    """The environment of a rehearsal's subprocess: this one's less the
    two variables tests/conftest.py sets for its own process, with
    XLA:CPU building as fast as it does for the tests (conftest.py has
    why, and why no faster) on the four virtual devices run.py would ask
    for itself were `XLA_FLAGS` unset. With `cache_dir`, jax's persistent
    cache there with every program kept: for a process that builds the
    same programs again and again, in a directory nothing else reads."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["XLA_FLAGS"] = " ".join(
        ("--xla_force_host_platform_device_count=4", *FAST_BUILD_FLAGS))
    if cache_dir is not None:
        env.update(JAX_COMPILATION_CACHE_DIR=str(cache_dir),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    return env


# run.py ends by requiring that no /dev/shm/ray_tpu_session_* appeared
# during its run and stayed. That looks at the whole machine, and tier-1
# runs several test files, each with clusters of its own, at once: their
# sessions are not this run's leftovers. So the rehearsal runs run.py as
# __main__ with that one glob answering nothing, and everything else as it
# is (the chip run keeps the check: there run.py is alone on its machine).
RUN_PY = r"""
import glob, runpy, sys
_glob = glob.glob
glob.glob = lambda p, *a, **k: [] if str(p).startswith(
    "/dev/shm/ray_tpu_session_") else _glob(p, *a, **k)
sys.argv = ["chipbench/run.py"] + sys.argv[1:]
runpy.run_path("chipbench/run.py", run_name="__main__")
"""


def run_cell(manifest, cell, seed, trace, seconds=2.0):
    """`run.py --rehearsal` on the cell: (the detail line, the last line)
    of a run that was correct on the CPU, its loss inside its tolerance
    of the reference's and its metrics among those BENCHMARK.json lists
    the cell under on that side of `--trace`. The window is `seconds`
    long: it has to hold a report group of the tiny steps on a machine
    whose cores five other test workers share (drivers/train.py refuses a
    window that closed before one did)."""
    proc = subprocess.run(
        [sys.executable, "-c", RUN_PY, "--rehearsal", manifest,
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env=subprocess_env(),
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    detail, line = lines[-2], lines[-1]
    assert line["correct"] is True, (line, detail)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    check = detail["checks"]["loss_vs_reference"]
    assert abs(check["got"] - check["want"]) <= check["tolerance"]
    declared = {m["name"] for m in load("BENCHMARK.json")[
        "per_layer" if trace else "end_to_end"]
        if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= declared
    if trace:
        # The CPU has no Mosaic rows, so the kernel metrics are left out;
        # what the host clock gives is there.
        assert {"step_ms_p50", "time_to_first_step_s"} <= set(
            line["metrics"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    return detail, line


def _tool(command, manifest, cell, *args, cache_dir=None):
    return subprocess.run(
        [sys.executable, *command, "--rehearsal", manifest, "--workload",
         cell, *args],
        capture_output=True, text=True, timeout=900,
        env=subprocess_env(cache_dir), cwd=ROOT)


# chipbench/limit_readings.py with some of the family's faults to plant:
# the pass reads each fault's loss and kernel errors in a program of its
# own, a rehearsal keeps one of each kind and the others are a cheaper
# in-process test's, which the cell's file names.
KEPT_FAULTS_PY = r"""
import importlib, json, runpy, sys
sys.path.insert(0, ".")
family = importlib.import_module(sys.argv[1])
family.STRUCTURAL_FAULTS = {
    name: family.STRUCTURAL_FAULTS[name] for name in json.loads(sys.argv[2])}
sys.argv = ["chipbench/limit_readings.py"] + sys.argv[3:]
runpy.run_path("chipbench/limit_readings.py", run_name="__main__")
"""


def limit_readings(manifest, cell, seeds, family, kept=None):
    """chipbench/limit_readings.py on the cell, with `kept` only those of
    the family's `STRUCTURAL_FAULTS` planted: (the line of each seed, the
    last line's ranges). The process builds most of its programs several
    times over (the program's layers under every fault that leaves them
    alone), so it gets a compile cache of its own, beside the manifest
    and gone when it ends."""
    command = ["chipbench/limit_readings.py"] if kept is None else [
        "-c", KEPT_FAULTS_PY, family.__name__, json.dumps(list(kept))]
    with tempfile.TemporaryDirectory(
            dir=os.path.dirname(manifest), prefix="jax_cache_") as cache:
        proc = _tool(command, manifest, cell, "--seeds", str(seeds),
                     cache_dir=cache)
    assert proc.returncode == 0, proc.stderr[-3000:]
    *rows, ranges = (json.loads(x) for x in proc.stdout.strip().splitlines())
    faults = (*family.STRUCTURAL_FAULTS, *getattr(
        family, "PRECISION_FAULTS", ())) if kept is None else kept
    assert set(ranges["off_reference"]) == {"program", "all_bfloat16",
                                            *faults}
    assert set(ranges["kernel_errors_worst"]) == set(ranges["off_reference"])
    assert ranges["kernel_limit"] == family.KERNEL_LIMIT
    return rows, ranges


def step_counters(manifest, cell, seeds, steps) -> dict:
    """chipbench/step_counters.py on the cell: its last line."""
    proc = _tool(["chipbench/step_counters.py"], manifest, cell,
                 "--seeds", str(seeds), "--steps", str(steps))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def scope_profile(manifest, cell, seed, steps):
    """chipbench/scope_profile.py on the cell: the ended process (without
    a chip it refuses, which is what its caller holds)."""
    return _tool(["chipbench/scope_profile.py"], manifest, cell,
                 "--seed", str(seed), "--steps", str(steps))


def lookup_in_tree_without(tmp_path, cell, module_files, import_line=None):
    """The cell looked up by run.py in a copy of the tree from before the
    family's program: `ray_tpu/` less `module_files` (and less the line of
    models/__init__.py that starts `import_line` and all after it), with
    this benchmark laid over it. The ended process, which failed at once
    in run.py's own process, before a cluster or a chip is touched."""
    tree = tmp_path / "tree"
    shutil.copytree(os.path.join(ROOT, "chipbench"), tree / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    shutil.copytree(os.path.join(ROOT, "ray_tpu"), tree / "ray_tpu",
                    ignore=shutil.ignore_patterns(
                        "__pycache__", *module_files, "*.so"))
    if import_line:
        init = tree / "ray_tpu" / "models" / "__init__.py"
        init.write_text(init.read_text().split(import_line)[0])
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tree,
        capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode not in (0, 124, 137), proc.stderr[-2000:]
    return proc


READ_PY = r"""
import importlib, json, sys
sys.path.insert(0, sys.argv[1])
from chipbench import harness
readers, record, family = json.loads(sys.argv[2])
if family:
    counters = record["counters"]
    counters["train_flops_per_token"] = importlib.import_module(
        "chipbench.families." + family).train_flops_per_token(
            record["config"], counters["seq"])
out = {n: harness.reader(n).read(record) for n in readers}
assert "jax" not in sys.modules, "a reader imported jax"
print(json.dumps(out))
"""


def read_without_jax(readers, record, family=None) -> dict:
    """{reader: what it reads from the hand-made `record`} in a process
    that has imported no jax by the end; with `family` (a module's name
    under chipbench/families) the record's `train_flops_per_token` is
    that family's count at the record's own `seq`, made there."""
    proc = subprocess.run(
        [sys.executable, "-c", READ_PY, ROOT,
         json.dumps([list(readers), record, family])],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout)
