"""What every cell shares: finding a cell's files by name, the table of
peaks, device facts, the compile counter and the last printed line.

A cell is one entry of ``workloads`` in BENCHMARK.json, or of
candidates.json beside this file: cells that are built and rehearsed but
not yet measured well enough on the chip to be held to a bound (each says
why under `not_yet`; the driver never asks for one). Its pieces are found
by name and nothing here knows any of them:

    configs/<config>.json      sizes, source, `family`
    traffic/<traffic>.json     `driver` and every parameter of the mix
    families/<family>.py       build the config object, ops/bytes, reference
    drivers/<driver>.py        run(cell) -> record
    layer_metrics/<metric>.py  read(record) -> number or None
"""

from __future__ import annotations

import importlib
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchFailure(Exception):
    """The benchmark cannot produce a result it would stand behind."""


def require(cond, message: str):
    if not cond:
        raise BenchFailure(message)


def say(**fields):
    """A line of detail on stdout, before the last line."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """The module ``chipbench.<kind>.<name>`` ('-' in a name is '_' in the
    file: a module cannot be called gpt2-small)."""
    require(NAME_RE.match(name), f"bad {kind} name {name!r}")
    return importlib.import_module(
        f"chipbench.{kind}.{name.replace('-', '_').replace('.', '_')}")


def reader(metric: str):
    """The reader of a per-layer metric: layer_metrics/<metric>.py, or,
    for a metric named <prefix>_<reader>, layer_metrics/<reader>.py. The
    contract gives a metric one `moves`, so a reading that moves another
    end-to-end metric in another kind of cell (the device's idle share)
    is declared once per kind, under a prefixed name, and read by one
    file."""
    name = metric
    while True:
        try:
            return plugin("layer_metrics", name)
        except ModuleNotFoundError as e:
            if not (e.name or "").startswith("chipbench.layer_metrics.") \
                    or "_" not in name:
                raise
            name = name.split("_", 1)[1]


def merged_manifest() -> dict:
    """BENCHMARK.json with the entries of candidates.json appended, list
    by list."""
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    extra = load_json(os.path.join(HERE, "candidates.json"))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[group] = manifest[group] + extra[group]
    return manifest


class Cell:
    """One workload of a manifest with its configuration and traffic
    loaded. Data files are looked up from the repo's root: a
    configuration by its `file`, a mix under the manifest's first path."""

    def __init__(self, manifest: dict, workload: str):
        self.manifest = manifest
        cells = {w["name"]: w for w in manifest["workloads"]}
        require(workload in cells,
                f"no workload {workload!r}; have {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfgs = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(
            os.path.join(REPO, cfgs[self.entry["config"]]["file"]))
        self.traffic = load_json(os.path.join(
            REPO, manifest["paths"][0], "traffic",
            self.entry["traffic"] + ".json"))
        self.family = plugin("families", self.config["family"])
        self.driver = plugin("drivers", self.traffic["driver"])

    def metrics(self, group: str) -> list:
        """The manifest's metrics of `group` that this cell reports."""
        return [m for m in self.manifest[group]
                if self.name in m.get("workloads", [self.name])]


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["by_device_kind"]
    require(device_kind in table,
            f"no published peak for device_kind {device_kind!r}; the "
            f"table has {sorted(table)}. Add the kind with its source; "
            f"there is no default.")
    return table[device_kind]


def out_dir(cell_name: str) -> str:
    """Scratch for one run (traces, worker stats, logs), inside the
    checkout and listed in .gitignore."""
    d = os.path.join(REPO, "chipbench_out", cell_name)
    os.makedirs(d, exist_ok=True)
    return d


# ---------------------------------------------------------------------------
# Inside the process that owns the chip(s)
# ---------------------------------------------------------------------------
def device_facts() -> dict:
    """The device as this process's JAX reports it (copied from
    chip_smoke.py's _device_facts, cut to what the result line needs)."""
    import jax

    devs = jax.local_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid(),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir}


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest local chip, from the runtime's own
    statistics: the peak of live buffers plus the peak it reserved for
    running programs' temporaries. On the chip tool's v5e the two are
    disjoint (limit - in use - reserved = largest free block, PR 22) and
    `peak_bytes_in_use` alone leaves the temporaries out: 0.92 GB for a
    train step that reserves 10.5 GB. 0 where the backend reports
    nothing (the CPU rehearsal)."""
    import jax

    def held(d):
        st = d.memory_stats() or {}
        return st.get("peak_bytes_in_use", 0) \
            + st.get("peak_bytes_reserved", 0)
    return int(max(held(d) for d in jax.local_devices()))


class CompileCounter:
    """Counts the programs this process builds, through jax.monitoring:
    every miss of jit's in-memory cache, whether XLA then compiles or the
    persistent cache answers (`cache_hits` says how many it answered).
    `mark()` starts the window; `since_mark` must stay 0 inside it."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon

        self.total = 0
        self.cache_hits = 0
        self._mark = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == self.EVENT:
            self.total += 1

    def _on_event(self, event, **kw):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def mark(self):
        self._mark = self.total

    @property
    def since_mark(self) -> int:
        return self.total - self._mark


def mosaic_kernel_names(lowered_text: str) -> set:
    """Names of the Mosaic (Pallas TPU) kernels a lowered program calls
    (copied from chip_smoke.py)."""
    return set(re.findall(
        r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"', lowered_text))


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in [0, 100], of a non-empty list."""
    xs = sorted(values)
    require(xs, "percentile of nothing")
    k = max(0, min(len(xs) - 1, int(-(-q * len(xs) // 100)) - 1))
    return float(xs[k])


def parent_backend_initialised() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def wait_chips_released(timeout_s: float = 90.0) -> float:
    """Wait until no process holds a chip of this machine, and return how
    long that took. A chip is a VFIO group file `/dev/vfio/<n>`; opening
    it answers EBUSY while something holds it, and the machine lets go of
    a dead worker's chips seconds AFTER the worker has ended (PR 22: on
    the four-chip host every second back-to-back run died on
    `open(/dev/vfio/1): Device or resource busy`, with no process of the
    first left). The probe opens and closes the file and touches nothing
    else; a machine without such files, or one that refuses the probe for
    another reason, is not waited for."""
    import errno
    import glob

    t0 = time.monotonic()
    for path in sorted(glob.glob("/dev/vfio/[0-9]*")):
        while time.monotonic() - t0 < timeout_s:
            try:
                os.close(os.open(path, os.O_RDWR))
            except OSError as e:
                if e.errno == errno.EBUSY:
                    time.sleep(0.25)
                    continue
            break
    return time.monotonic() - t0
