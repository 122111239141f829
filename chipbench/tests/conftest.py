"""The benchmark's tests are run from the repo root:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def rehearsal_manifest() -> dict:
    """BENCHMARK.json and candidates.json as they are, with each
    configuration and traffic mix swapped for its tiny stand-in
    (rehearsal/data/tiny.json): cells and metrics keep their names."""
    from chipbench import harness

    m = harness.merged_manifest()
    tiny = harness.load_json(os.path.join(
        harness.HERE, "tests", "rehearsal", "data", "tiny.json"))
    m["paths"] = tiny["paths"]
    for c in m["configs"]:
        c["file"] = f"{tiny['paths'][0]}/configs/{tiny['configs'][c['name']]}.json"
    for w in m["workloads"]:
        w["traffic"] = tiny["traffic"][w["traffic"]]
    return m


@pytest.fixture(scope="session")
def rehearsal_path(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("rehearsal") / "BENCHMARK.json"
    path.write_text(json.dumps(rehearsal_manifest()))
    return str(path)
