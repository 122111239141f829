#!/usr/bin/env python3
"""Where a train cell's step spends its device time, by the program's own
names: the family's train step on the cell's own feed (a ring of seeded
batches as drivers/train.py draws them, the mesh the mix names), warmed
up, then --steps steps under `ray_tpu.util.profiling.capture`, the trace
read by `profiling.read_device_events` and reduced by `profiling.by_scope`
with the compiled step's text (so a row with no `tf_op` finds its
`op_name`, and fusions that hold two scopes' work are flagged). A tool
beside step_counters.py and limit_readings.py: PERF.md section 5's tables
come from it, and what the capture and the reduction cost is in `cost`.
A device's time is read from a device: where jax runs on anything but a
TPU, or the trace holds no device plane, the tool prints why, writes
nothing and exits 3 (--rehearsal, the tiny sizes on the CPU that the
repo's tests run it at, ends there too, having built and run the step).

    python3 chipbench/scope_profile.py --workload granite4h-train-1chip --seed 7 --steps 3

Prints one JSON line (the table without its rows' detail is the last
line) and writes the whole result to
chiprun_out/scope_profile_<workload>.json. --events FILE also writes the
first traced step's device events, gzipped JSON [[name, tf_op, start_ns,
duration_ns], ...]: what a recorded fixture of the repo's tests is cut
from.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class NoDevicePlane(RuntimeError):
    """The steps ran and their trace holds no TPU's plane."""


def profile(cell, seed: int, steps: int, events_out: str = None) -> dict:
    import jax
    import numpy as np

    from ray_tpu.util import profiling

    # The names read are this tree's: a program read back from the
    # persistent cache (whose key leaves locations out) would carry the
    # names of whichever tree compiled it.
    jax.config.update("jax_enable_compilation_cache", False)
    family, t = cell.family, cell.traffic
    cfg = family.build(cell.config, remat=bool(t["remat"]))
    dp = t["mesh_dp"]
    if dp:
        from jax.sharding import NamedSharding, PartitionSpec

        from ray_tpu.parallel import MeshConfig, make_mesh, tp_rules
        mesh = make_mesh(MeshConfig(dp=dp))
        _, init_state, step, _ = family.train_program(
            cfg, mesh=mesh, rules=tp_rules())
        rows = NamedSharding(mesh, PartitionSpec("dp"))
        place = lambda b: jax.device_put(b, rows)   # noqa: E731
    else:
        _, init_state, step, _ = family.train_program(cfg)
        place = jax.device_put
    rng = np.random.default_rng([seed, 1])      # as drivers/train.py draws
    ids = rng.integers(0, cell.config["vocab_size"],
                       (t["ring_batches"], t["global_batch"], t["seq"]),
                       dtype=np.int32)
    ring = [place((b, np.roll(b, -1, 1))) for b in ids]
    state = init_state(jax.random.PRNGKey(seed))
    compiled = step.lower(state, ring[0]).compile()
    text = compiled.as_text()
    for i in range(2):                          # warm-up
        state, metrics = compiled(state, ring[i % len(ring)])
    jax.block_until_ready(state)
    logdir = os.path.join(REPO, "chipbench_out", "scope_profile", cell.name)
    started = time.perf_counter()
    with profiling.capture(logdir) as cap:
        for i in range(steps):
            state, metrics = compiled(state, ring[i % len(ring)])
        jax.block_until_ready(state)
    traced_s = time.perf_counter() - started - cap.stop_s
    t0 = time.perf_counter()
    trace = profiling.read_device_events(cap.xplane)
    t1 = time.perf_counter()
    device = jax.local_devices()[0]
    if device.platform != "tpu" or trace["plane"] is None:
        raise NoDevicePlane(
            f"{cell.name}: {steps} step(s) ran on {device.platform} (loss "
            f"{float(metrics['loss']):.4f}) and {cap.xplane} holds "
            f"{len(trace['planes'])} device plane(s): no device time to "
            f"read, nothing written")
    events, runs = profiling.step_events(trace["events"], trace["modules"])
    table = profiling.by_scope(events, runs, text)
    t2 = time.perf_counter()
    if events_out and trace["modules"]:
        first = min(start for _, start, _ in trace["modules"])
        length = next(d for _, s, d in trace["modules"] if s == first)
        with gzip.open(events_out, "wt") as f:
            json.dump([e for e in events
                       if first <= e[2] < first + length], f)
    return {
        "cell": cell.name, "seed": seed, "steps_asked": steps,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.local_devices())},
        "plane": trace["plane"], "planes": trace["planes"],
        "loss": float(metrics["loss"]),
        "host_step_ms": 1e3 * traced_s / steps,
        "cost": {"stop_s": cap.stop_s, "read_device_events_s": t1 - t0,
                 "by_scope_s": t2 - t1,
                 "xplane_bytes": os.path.getsize(cap.xplane),
                 "device_events": len(trace["events"]),
                 "compiled_text_bytes": len(text)},
        "mixed_fusions": len(profiling.mixed_fusions(text)),
        **table}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--events", metavar="FILE",
                    help="write the first traced step's events here")
    ap.add_argument("--rehearsal", metavar="MANIFEST",
                    help="as run.py's: tiny sizes on the CPU, where the "
                    "run ends in the refusal; tests only")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    os.chdir(REPO)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        os.environ.setdefault("RAY_TPU_PALLAS_INTERPRET", "1")

    from chipbench import harness
    from ray_tpu.util import profiling

    cell = harness.Cell(harness.load_json(args.rehearsal) if args.rehearsal
                        else harness.merged_manifest(), args.workload)
    try:
        out = profile(cell, args.seed, args.steps, args.events)
    except NoDevicePlane as refused:
        sys.stderr.write(f"scope_profile: {refused}\n")
        return 3
    sys.stderr.write(profiling.format_by_scope(out) + "\n")
    folder = os.path.join(REPO, "chiprun_out")
    os.makedirs(folder, exist_ok=True)
    with open(os.path.join(folder, f"scope_profile_{cell.name}.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("scopes", "unscoped")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
