#!/usr/bin/env python3
"""chipbench/run.py — run one cell of BENCHMARK.json on the chip(s) of
this machine and print its result as the last line of stdout.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every run is a new process that starts its own cluster, warms the cell's
shapes (set-up), measures for --seconds, checks the outputs against the
plain float32 reference, tears the cluster down, waits until every process
it started has ended, and prints one JSON line last. This parent process never initialises a JAX backend: every device
fact comes from the worker that owns the chip. Without the chips the cell
asks for it exits non-zero and prints no result; there is no CPU mode
(the benchmark's tests rehearse the control flow with --rehearsal, whose
last line says "platform": "cpu" and whose numbers are written nowhere).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
DEADLINE_S = 1150.0   # a first run compiles and may take 1200 s


def _watchdog(seconds: float):
    """Every wait has a deadline: past it, stop every process this run
    started (they share its process group), itself included."""
    def fire():
        sys.stderr.write(f"chipbench: deadline of {seconds:.0f}s passed; "
                         f"killing the process group\n")
        sys.stderr.flush()
        os.killpg(os.getpgrp(), signal.SIGKILL)
    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()


def _started_and_alive() -> list:
    """Pids of the live processes this run started: they share its
    process group (zombies hold nothing and do not count)."""
    me, group, out = os.getpid(), os.getpgrp(), []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == me:
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue      # ended while we looked
        if int(fields[2]) == group and fields[0] != "Z":
            out.append(int(entry))
    return out


def _wait_until_all_ended(timeout_s: float = 120.0) -> float:
    """Wait for every process this run started to end; kill what outlasts
    the deadline. (The chips themselves come free later still:
    harness.wait_chips_released.)"""
    t0 = time.monotonic()
    while (left := _started_and_alive()) \
            and time.monotonic() - t0 < timeout_s:
        time.sleep(0.1)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    while left and _started_and_alive():
        time.sleep(0.1)
    return time.monotonic() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", metavar="MANIFEST",
                    help="the benchmark's tests only: take the cells from "
                         "this manifest of tiny sizes and run on the CPU")
    args = ap.parse_args(argv)
    platform = "cpu" if args.rehearsal else "tpu"

    if not os.path.isdir(os.path.join(REPO, "ray_tpu")):
        sys.stderr.write(f"{REPO} holds chipbench but not the ray_tpu "
                         f"package it measures\n")
        return 1
    sys.path.insert(0, REPO)
    # Workers import chipbench.* by name: they start in this directory.
    os.chdir(REPO)
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p])
    if os.getpgrp() != os.getpid():
        os.setpgrp()

    from chipbench import harness
    from ray_tpu._private.resources import (DEFAULT_COMPILE_CACHE_DIR,
                                            TPUAcceleratorManager)

    cell = harness.Cell(harness.load_json(args.rehearsal) if args.rehearsal
                        else harness.merged_manifest(), args.workload)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE_DIR
    cold = not glob.glob(os.path.join(cache_dir, "*"))
    _watchdog(DEADLINE_S)
    if platform == "tpu":
        detected = TPUAcceleratorManager.get_current_node_num_accelerators()
        if detected < cell.chips:
            sys.stderr.write(
                f"chipbench: cell {cell.name} needs {cell.chips} TPU "
                f"chip(s), this machine has {detected} (/dev/accel*, "
                f"/dev/vfio/*). There is no CPU mode.\n")
            return 1
    else:
        # The rehearsal: CPU workers, enough virtual devices for a mesh.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        os.environ.setdefault("RAY_TPU_PALLAS_INTERPRET", "1")

    import ray_tpu
    from ray_tpu import serve

    # A run that was killed leaves its chips held for some seconds more;
    # waiting them out is not this run's set-up.
    chips_wait_s = harness.wait_chips_released() if platform == "tpu" \
        else 0.0
    shm_before = set(glob.glob("/dev/shm/ray_tpu_session_*"))
    try:
        record = cell.driver.run(cell, seed=args.seed, seconds=args.seconds,
                                 trace=bool(args.trace), platform=platform)
        t_measured = time.time()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        waited_s = _wait_until_all_ended()
        if platform == "tpu":
            waited_s += harness.wait_chips_released()
    teardown_s = time.time() - t_measured
    leftover = set(glob.glob("/dev/shm/ray_tpu_session_*")) - shm_before
    harness.require(not leftover, f"leftover object-store sessions: "
                                  f"{sorted(leftover)}")
    harness.require(not harness.parent_backend_initialised(),
                    "the parent process initialised a JAX backend")
    device = record["device"]
    harness.require(device["platform"] == platform,
                    f"the cell ran on {device['platform']!r}")
    if platform == "tpu":
        harness.peaks_for(device["kind"])

    setup_s = record["window_start_unix"] - T_PROCESS_START - chips_wait_s
    record.update(cell=cell.entry, config=cell.config, traffic=cell.traffic,
                  seconds=args.seconds)
    metrics = {}
    if args.trace:
        for m in cell.metrics("per_layer"):
            value = harness.reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = {**record["end_to_end"], "setup_s": setup_s}
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    harness.say(cell=cell.name, seed=args.seed, trace=args.trace,
                not_yet_a_cell=cell.entry.get("not_yet"), setup_s=setup_s, cold_compile_cache=cold,
                wall_s=time.time() - T_PROCESS_START, teardown_s=teardown_s,
                waited_for_processes_s=waited_s,
                waited_for_chips_at_start_s=chips_wait_s,
                checks=record.get("checks"),
                counters={k: v for k, v in record["counters"].items()
                          if not isinstance(v, (list, dict))},
                end_to_end=record["end_to_end"])
    line = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]),
            "metrics": metrics, "device": device}
    trace = record.get("trace") or {}
    if args.trace and trace:
        line["breakdown"] = {"device_ops": trace.get("device_ops", []),
                             "idle_gaps": trace.get("idle_gaps", [])}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
