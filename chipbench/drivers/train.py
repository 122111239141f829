"""Driver "train": a JaxTrainer job whose loop is the benchmark's, run in
the TrainWorker that owns the chip(s). Reads traffic/<mix>.json:
global_batch, seq, mesh_dp, remat, ring_batches, report_every,
fetch_lag_groups, median_over_groups, warmup_steps, traced_steps and the
reference tolerances."""

from __future__ import annotations

import collections
import os
import time

from .. import harness, xplane
from ..harness import require


def step_times(marks: list, over: int = 1) -> list:
    """Seconds a step of every run of `over` consecutive report groups
    (sliding by one group). `marks` are (steps completed, host time at
    which their last step was seen complete), one for the window's opening
    and one per group."""
    return [(seen - seen0) / (steps - steps0) for
            (steps0, seen0), (steps, seen) in zip(marks, marks[over:])]


def median_step_s(marks: list, over: int = 1) -> float:
    """The step time the window's throughput is worked out from: the
    median of step_times(marks, over). A stall of the measuring host lands
    in `over` of those runs and leaves the median where it was, and a mark
    seen late by d is d / (over x group time) of a run, not d / group time
    of a group. With fewer groups than `over` it is the median over what
    there is."""
    require(len(marks) > 1, "the window closed before a report group did")
    runs = sorted(step_times(marks, max(1, min(over, len(marks) - 1))))
    return runs[len(runs) // 2]


def run(cell, *, seed: int, seconds: float, trace: bool, platform: str):
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    on_tpu = platform == "tpu"
    out = harness.out_dir(cell.name)
    ray_tpu.init()
    t_fit = time.time()
    result = JaxTrainer(
        loop_on_chip,
        train_loop_config={
            "config": cell.config, "traffic": cell.traffic,
            "chips": cell.chips, "seed": seed, "seconds": seconds,
            "trace": trace, "platform": platform, "out_dir": out},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=on_tpu,
            resources_per_worker={"TPU": cell.chips} if on_tpu else None),
        run_config=RunConfig(name=cell.name,
                             storage_path=os.path.join(out, "train")),
    ).fit()
    if result.error is not None:
        raise harness.BenchFailure(
            f"train job failed in the chip worker: {result.error!r}"
        ) from result.error
    require("record" in result.metrics,
            f"train loop ended without its record: {result.metrics}")
    rec = result.metrics["record"]
    rec["counters"]["time_to_first_step_s"] = \
        rec["counters"].pop("first_step_unix") - t_fit
    return rec


def loop_on_chip(c: dict):
    """train_loop_per_worker: everything that touches JAX."""
    import jax
    import numpy as np

    from ray_tpu import train

    compiles = harness.CompileCounter()
    facts = harness.device_facts()
    platform, t, chips = c["platform"], c["traffic"], c["chips"]
    require(facts["platform"] == platform,
            f"worker computes on {facts['platform']!r}, not {platform!r}")
    family = harness.plugin("families", c["config"]["family"])
    cfg = family.build(c["config"], remat=bool(t["remat"]))
    if platform == "tpu":
        require(facts["count"] == chips,
                f"cell needs {chips} chip(s), worker sees {facts['count']}")
        peaks = harness.peaks_for(facts["kind"])
    B, S, dp = t["global_batch"], t["seq"], t["mesh_dp"]
    rng = np.random.default_rng([c["seed"], 1])
    ring_np = rng.integers(0, c["config"]["vocab_size"],
                           (t["ring_batches"], B, S), dtype=np.int32)
    checks = {}

    # Outside the window: the program's loss against the plain float32
    # reference on a seeded sample of the first batch's sequences.
    key = jax.random.PRNGKey(c["seed"])
    k = t["reference_sample_sequences"]
    sample = (ring_np[0, :k], np.roll(ring_np[0, :k], -1, 1))
    init_params, init_state, step, loss = family.train_program(cfg)
    params_one = init_params(key)
    got = float(jax.jit(loss)(params_one, sample))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(
            lambda p, b: family.reference_loss(p, b[0], b[1], cfg)
        )(params_one, sample))
    checks["loss_vs_reference"] = {"got": got, "want": want,
                                   "tolerance": t["reference_loss_tolerance"]}
    ok = abs(got - want) <= t["reference_loss_tolerance"]

    if dp:
        from jax.sharding import NamedSharding, PartitionSpec

        from ray_tpu.parallel import MeshConfig, make_mesh, tp_rules
        # The whole first batch on ONE device, for the dp step's first
        # loss to equal.
        b0 = (ring_np[0], np.roll(ring_np[0], -1, 1))
        one_dev = float(jax.jit(loss)(params_one, b0))
        mesh = make_mesh(MeshConfig(dp=dp))
        _, init_state, step, _ = family.train_program(
            cfg, mesh=mesh, rules=tp_rules())
        rows = NamedSharding(mesh, PartitionSpec("dp"))
        place = lambda b: jax.device_put(b, rows)   # noqa: E731
    else:
        place = jax.device_put
    del params_one
    ring = [place((b, np.roll(b, -1, 1))) for b in ring_np]
    state = init_state(key)
    lowered = step.lower(state, ring[0]).as_text()
    kernels = sorted(harness.mosaic_kernel_names(lowered))

    # Warm-up: the one shape, compiled or read from the cache.
    state, m = step(state, ring[0])
    first_loss = float(m["loss"])
    first_step_unix = time.time()
    if dp:
        tol = t["one_device_loss_rel_tolerance"]
        checks["dp_first_loss"] = {"dp": first_loss, "one_device": one_dev,
                                   "rel_tolerance": tol}
        ok = ok and abs(first_loss - one_dev) <= tol * abs(one_dev)
    for i in range(1, t["warmup_steps"]):
        state, m = step(state, ring[i % len(ring)])
    jax.block_until_ready(state)
    train.report({"device": facts, "first_loss": first_loss})

    # The measured window. The loss of a report group is fetched
    # `fetch_lag_groups` groups after its last step was dispatched, so the
    # device always has that many groups queued and a host that is slow
    # to wake does not leave it idle; 0 fetches it at once, which drains
    # the queue at every report.
    every, lag = t["report_every"], t.get("fetch_lag_groups", 0)
    n, traced, losses, pending = 0, None, [first_loss], collections.deque()
    trace_dir = os.path.join(c["out_dir"], "trace")
    trace_from = every if c["trace"] else -1
    trace_to = trace_from + t["traced_steps"] if c["trace"] else -1

    def fetch_oldest():
        steps, loss_on_device = pending.popleft()
        losses.append(float(loss_on_device))
        marks.append((steps, time.perf_counter()))
        train.report({"step": steps, "loss": losses[-1]})

    compiles.mark()
    window_start_unix = time.time()
    t0 = time.perf_counter()
    marks = [(0, t0)]
    while time.perf_counter() - t0 < c["seconds"]:
        if n == trace_from:
            jax.block_until_ready(state)
            xplane.start(trace_dir)
        with xplane.span("dispatch"):
            state, m = step(state, ring[n % len(ring)])
        n += 1
        if n == trace_to:
            with xplane.span("trace_end_barrier"):
                jax.block_until_ready(state)
            traced = xplane.stop(trace_dir, platform)
        if n % every == 0:
            pending.append((n, m["loss"]))
            if len(pending) > lag:
                with xplane.span("report"):
                    fetch_oldest()
    while pending:
        fetch_oldest()
    jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    compiled_in_window = compiles.since_mark
    losses.append(float(m["loss"]))
    group_s = step_times(marks)
    step_s = median_step_s(marks, t.get("median_over_groups", 1))

    finite = bool(np.all(np.isfinite(losses)))
    checks.update(losses_finite=finite, first_loss=losses[0],
                  last_loss=losses[-1], mosaic_kernels=kernels,
                  compiled_in_window=compiled_in_window)
    ok = ok and finite and losses[-1] < losses[0] \
        and compiled_in_window == 0
    if platform == "tpu":
        checks["expected_kernels"] = sorted(family.MOSAIC_KERNELS)
        ok = ok and kernels == sorted(family.MOSAIC_KERNELS)
    # Tokens per second from the median step time, not steps / window:
    # the mean over the window moves with every stall of the measuring
    # host (its cores may be shared), the median does not. The mean stays
    # among the counters, so a program that stalls itself still shows.
    tokens_per_s = B * S / step_s
    device = {**{k: facts[k] for k in ("platform", "kind", "count")},
              "memory_peak_bytes": harness.memory_peak_bytes()}
    checks["group_step_ms"] = [round(1e3 * g, 2) for g in group_s]
    reduced = {}
    if traced is not None:
        reduced = xplane.reduce(traced)
        reduced["steps"] = t["traced_steps"]
        device.update(busy_s=reduced.get("busy_s", 0.0),
                      window_s=reduced.get("window_s", 0.0))
    record = {
        "correct": bool(ok), "checks": checks,
        "attempted": n, "failed": 0,
        "window_start_unix": window_start_unix,
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "device": device,
        "counters": {
            "steps": n, "window_s": window_s, "tokens_per_s": tokens_per_s,
            "window_mean_tokens_per_s": B * S * n / window_s,
            "group_step_s": group_s, "global_batch": B, "seq": S,
            "chips": chips, "first_step_unix": first_step_unix,
            "programs_built": compiles.total,
            "persistent_cache_hits": compiles.cache_hits,
            "train_flops_per_token": family.train_flops_per_token(cfg, S),
            "attention_kernel_flops":
                family.attention_kernel_flops(cfg, B // max(1, dp), S),
            "attention_kernel_bytes":
                family.attention_kernel_bytes(cfg, B // max(1, dp), S),
            "peaks": peaks if platform == "tpu" else None,
        },
        "trace": reduced,
    }
    train.report({"record": record})
