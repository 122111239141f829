"""A cell's two test files keep what is the cell's own and take the rest
from tests/cell_rehearsal.py and tests/compile_v5e.py: the next cell's
files are copied from the newest, and what a copy carries along is repaired
in one of them and not in the others (ROADMAP.md C10)."""

import ast
import glob
import os
import subprocess
import types

import cell_rehearsal
import conftest

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSALS = sorted(glob.glob(os.path.join(HERE, "test_*_cell_rehearsal.py")))
COMPILES = sorted(glob.glob(os.path.join(HERE, "test_compile_v5e_*.py")))


def _parsed(path):
    with open(path) as f:
        return ast.parse(f.read())


def _imports(tree, module):
    """The names a file's top level takes from `module` by `from ... import`
    ("" for `import module`)."""
    names = set()
    for node in tree.body:
        if isinstance(node, ast.ImportFrom) and node.module == module:
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.Import):
            names.update("" for alias in node.names if alias.name == module)
    return names


def _top_level(tree):
    """({function's name: its definition}, the names assigned) at a file's
    top level."""
    return ({node.name: node for node in tree.body
             if isinstance(node, ast.FunctionDef)},
            {target.id for node in tree.body if isinstance(node, ast.Assign)
             for target in node.targets if isinstance(target, ast.Name)})


def test_every_rehearsal_file_takes_the_runs_from_the_one_harness():
    assert len(REHEARSALS) >= 8
    for path in REHEARSALS:
        tree, name = _parsed(path), os.path.basename(path)
        assert _imports(tree, "cell_rehearsal"), name
        assert not _imports(tree, "subprocess"), name
        defined, assigned = _top_level(tree)
        assert not {"RUN_PY", "LIMITS_PY", "ROOT"} & assigned, name
        assert not {"_env", "_load"} & set(defined), name
        # the manifest's fixture is the harness's call and nothing else
        body = [node for node in defined["manifest_path"].body
                if not isinstance(node, ast.Expr)]      # less a docstring
        assert len(body) == 1 and isinstance(body[0], ast.Return), name
        assert ast.unparse(body[0].value.func) in (
            "rehearsal.manifest", "cell_rehearsal.manifest", "manifest"), name


def test_every_compile_file_takes_the_topology_from_the_one_harness():
    assert len(COMPILES) >= 10
    for path in COMPILES:
        tree, name = _parsed(path), os.path.basename(path)
        assert "topo" in _imports(tree, "compile_v5e"), name
        defined, assigned = _top_level(tree)
        assert not {"topo", "_total", "_load"} & set(defined), name
        assert "HBM_BYTES" not in assigned, name
        with open(path) as f:
            assert "get_topology_desc" not in f.read(), name


def test_a_rehearsals_subprocess_builds_as_the_tests_do_on_four_devices():
    env = cell_rehearsal.subprocess_env()
    flags = env["XLA_FLAGS"].split()
    assert set(conftest.FAST_BUILD_FLAGS) <= set(flags)
    assert len(conftest.FAST_BUILD_FLAGS) == 3
    assert "--xla_force_host_platform_device_count=4" in flags
    assert len(flags) == 4
    assert "JAX_PLATFORMS" not in env
    # this process's own flags are the same three, from the same tuple
    assert set(conftest.FAST_BUILD_FLAGS) <= set(
        os.environ["XLA_FLAGS"].split())
    assert not [k for k in env if k.startswith(("JAX_COMPILATION_CACHE",
                                                "JAX_PERSISTENT_CACHE"))
                and k not in os.environ]


def test_limit_readings_alone_gets_a_cache_that_ends_with_it(
        tmp_path_factory, monkeypatch):
    """`subprocess_env(cache_dir=d)` sets jax's three cache variables, and
    the one caller that passes a directory makes it under pytest's
    temporary root, for that process alone, and removes it."""
    env = cell_rehearsal.subprocess_env(cache_dir="/somewhere")
    assert {k: env[k] for k in env if k not in cell_rehearsal.subprocess_env()
            } == {"JAX_COMPILATION_CACHE_DIR": "/somewhere",
                  "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                  "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    seen = []

    def ended(command, env, **kwargs):
        cache = env.get("JAX_COMPILATION_CACHE_DIR")
        seen.append((command, cache, cache and os.path.isdir(cache)
                     and not os.listdir(cache)))
        return subprocess.CompletedProcess(
            command, 0, stderr="", stdout='{"seed": 1}\n{"off_reference": '
            '{"program": 0, "all_bfloat16": 0}, "kernel_limit": 1, '
            '"kernel_errors_worst": {"program": 0, "all_bfloat16": 0}}\n')

    monkeypatch.setattr(cell_rehearsal.subprocess, "run", ended)
    manifest = cell_rehearsal.manifest(
        tmp_path_factory, "olmoe-train-1chip", "olmoe-tiny",
        "tiny-train-olmoe")
    family = types.SimpleNamespace(STRUCTURAL_FAULTS={}, KERNEL_LIMIT=1)
    cell_rehearsal.limit_readings(manifest, "olmoe-train-1chip", 1, family)
    cell_rehearsal.step_counters(manifest, "olmoe-train-1chip", 1, 1)
    cell_rehearsal.scope_profile(manifest, "olmoe-train-1chip", 1, 1)
    (_, cache, fresh), *others = seen
    assert fresh and not os.path.exists(cache)
    assert os.path.commonpath(
        [cache, str(tmp_path_factory.getbasetemp())]) == str(
            tmp_path_factory.getbasetemp())
    assert [cache for _, cache, _ in others] == [None, None]
