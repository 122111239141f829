#!/usr/bin/env python3
"""Take the readings a train cell's `reference_loss_tolerance` is set
from, once, on the chip, at the cell's own size: for each of --seeds, on
the sample drivers/train.py compares (its seeded weights, the first
`reference_sample_sequences` sequences of its first batch), the program's
loss, the family's plain float32 reference, the same reference with every
parameter and value in bfloat16, and, where the family has faults to
plant (`STRUCTURAL_FAULTS`, `PRECISION_FAULTS`, `planted`), the program's
loss under each. Where the family holds its kernels to a limit of its own
(`kernel_errors`, `KERNEL_LIMIT`) the same table is read for that. A limit
lies above the program's largest reading and under the lower precision's
and the planted faults' smallest; the mix's `why_tolerance` and PERF.md
keep the tables.

    python3 chipbench/limit_readings.py --workload granite4h-train-1chip --seeds 11,2147483900

Prints one JSON line a seed and a last line of ranges, and writes both to
chiprun_out/limit_readings_<workload>.json.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def readings(cell, seed: int) -> dict:
    """One seed's losses (program, reference, all_bfloat16, one a planted
    fault) and, where the family has them, its kernels' errors for the
    same. The family's `reference_loss` takes a dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    family, t = cell.family, cell.traffic
    cfg = family.build(cell.config, remat=bool(t["remat"]))
    init_params, _, _, loss = family.train_program(cfg)
    # the sample as drivers/train.py draws it
    rng = np.random.default_rng([seed, 1])
    first = rng.integers(0, cell.config["vocab_size"],
                         (t["ring_batches"], t["global_batch"], t["seq"]),
                         dtype=np.int32)[0, :t["reference_sample_sequences"]]
    sample = (first, np.roll(first, -1, 1))
    params = init_params(jax.random.PRNGKey(seed))

    def reference(dtype):
        with jax.default_matmul_precision("highest"):
            return float(jax.jit(lambda p, b: family.reference_loss(
                p, b[0], b[1], cfg, dtype))(params, sample))

    def program(fault=None):
        with family.planted(fault) if fault else contextlib.nullcontext():
            # a function of its own: jit keeps what it traced for `loss`
            return float(jax.jit(lambda p, b: loss(p, b))(params, sample))

    out = {"seed": seed, "program": program(), "reference": reference(None),
           "all_bfloat16": reference(jnp.bfloat16)}
    faults = (*getattr(family, "STRUCTURAL_FAULTS", ()),
              *getattr(family, "PRECISION_FAULTS", ()))
    for fault in faults:
        out[fault] = program(fault)
    if hasattr(family, "kernel_errors"):
        # the family's limit on its kernels: the same table again
        errors = {"program": family.kernel_errors(cfg, seed),
                  "all_bfloat16": family.kernel_errors(cfg, seed, low=True)}
        for fault in faults:
            with family.planted(fault):
                errors[fault] = family.kernel_errors(cfg, seed)
        out["kernel_errors"] = errors
    return out


def span(values) -> list:
    return [min(values), max(values)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearsal", metavar="MANIFEST",
                    help="as run.py's: tiny sizes on the CPU, tests only")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    os.chdir(REPO)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from chipbench import harness

    cell = harness.Cell(harness.load_json(args.rehearsal) if args.rehearsal
                        else harness.merged_manifest(), args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(cell, seed))
        print(json.dumps(rows[-1]), flush=True)
    ranges = {"seeds": len(rows),
              "tolerance": cell.traffic["reference_loss_tolerance"],
              "off_reference": {
                  k: span([abs(r[k] - r["reference"]) for r in rows])
                  for k in rows[0]
                  if k not in ("seed", "reference", "kernel_errors")}}
    if "kernel_errors" in rows[0]:
        ranges["kernel_limit"] = cell.family.KERNEL_LIMIT
        ranges["kernel_errors_worst"] = {
            k: span([max(r["kernel_errors"][k].values()) for r in rows])
            for k in rows[0]["kernel_errors"]}
    print(json.dumps(ranges), flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limit_readings_{cell.name}.json"), "w") as f:
        json.dump({"rows": rows, **ranges}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
