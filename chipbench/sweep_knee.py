#!/usr/bin/env python3
"""Find the knee of a serve cell once, on the chip: one replica, the
cell's mix offered at each of --rates in turn. The knee is the highest
rate at which the backlog does not grow over the window and at least 90%
of the probes that answer do so within a second; the cell's traffic file then fixes
rate_per_s at about 0.8 of it, and PERF.md keeps the table.

    python3 chipbench/sweep_knee.py --workload gpt2s-serve-chat --rates 8,12,16 --seconds 20
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", metavar="MANIFEST",
                    help="as run.py's: tiny sizes on the CPU, tests only")
    args = ap.parse_args(argv)
    platform = "cpu" if args.rehearsal else "tpu"
    sys.path.insert(0, REPO)
    os.chdir(REPO)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import ray_tpu
    from chipbench import harness
    from chipbench.drivers import serve as driver
    from ray_tpu import serve

    cell = harness.Cell(harness.load_json(args.rehearsal) if args.rehearsal
                        else harness.merged_manifest(), args.workload)
    ray_tpu.init()
    try:
        up = driver.start_replica(cell, seed=args.seed, trace=False,
                                   platform=platform)
        print(json.dumps({"reference": up["reference"],
                          "replica_ready_s": up["replica_ready_s"]},
                         default=str), flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = {**cell.traffic, "rate_per_s": rate}
            got = driver.offer(up["url"], traffic, args.seed, args.seconds)
            rec = driver.score(got["results"], got["t_open_unix"],
                               got["t_stop_unix"], args.seconds, platform,
                               traffic["overdue"])
            c = rec["counters"]
            steps = c["engine_steps_in_window"] or 1
            print(json.dumps({
                "rate_per_s": rate, **rec["end_to_end"],
                "failed": rec["failed"], "attempted": rec["attempted"],
                "ttft_p50_ms": c["ttft_p50_ms"],
                "ttft_p90_ms": c["ttft_p90_ms"],
                "norm_latency_p90": c["norm_latency_p90"],
                "probes_within_1s_share": c["probes_within_1s_share"],
                "inflight_at_open": c["inflight_at_open"],
                "inflight_at_close": c["inflight_at_close"],
                "out_tokens_per_s": c["out_tokens_in_window"] / args.seconds,
                "engine_step_ms": 1e3 * c["engine_steps_span_s"] / steps,
                "batch_occupancy": c["out_tokens_in_window"] / steps,
                "generator_late_p99_ms": c["generator_late_p99_ms"],
            }), flush=True)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
