#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the system's main path once, through the entry points a user calls,
at the full width of GPT-2-small (12 L, d 768, 12x64 heads, vocab 50304):

1. train: ``JaxTrainer(..., ScalingConfig(num_workers=1, use_tpu=True))``
   takes seeded steps of ``make_train_step(cfg)`` at B=16/S=1024 inside the
   TrainWorker that owns the chip, reporting through ``train.report``;
2. serve: once the scheduler has the chip back, ``serve.run(build_llm_app(
   cfg, continuous_batching=True, max_batch=8, num_tpus=1))`` answers HTTP
   requests through the proxy from a replica that initialises its own
   weights on the chip.

Every device fact printed comes from the process that owns the chip. This
parent process never initialises a JAX backend (a chip belongs to one
process at a time) and checks that about itself before it exits. There is
no CPU mode: without a chip the script fails before it starts anything.

    python chip_smoke.py               # one chip: what the driver runs
    python chip_smoke.py --four-chip   # builder's run on a four-chip host

The last line of stdout is ``{"ok": true, "device": {...}}`` as the chip
worker's JAX reports it; any failed check raises and the exit code is not 0.
It writes only under ``chiprun_out/chip_smoke/`` and the compile cache.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import glob
import json
import os
import shutil
import signal
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Published bf16 peak FLOP/s per chip by device_kind (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s). A kind that is not here is an
# error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12}

KERNELS = {"_fwd_kernel", "_dq_kernel", "_dkv_kernel"}


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _require(cond, message: str):
    if not cond:
        raise SmokeFailure(message)


def _say(**fields):
    print(json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------------------
# Inside the process that owns the chip(s)
# ---------------------------------------------------------------------------
def _device_facts() -> dict:
    """Which device this process computes on, as its own JAX sees it."""
    from importlib import metadata

    import jax

    import ray_tpu

    dev = jax.devices()[0]
    return {
        "pid": os.getpid(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "local_device_count": jax.local_device_count(),
        "device_ids": [d.id for d in jax.local_devices()],
        "device_coords": [list(getattr(d, "coords", ()))
                          for d in jax.local_devices()],
        "tpu_ids": ray_tpu.get_tpu_ids(),
        "versions": {p: metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(("TPU_", "JAX_", "XLA_", "LIBTPU",
                                 "CLOUD_TPU", "RAY_TPU_PALLAS"))},
    }


def mosaic_kernel_names(lowered_text: str) -> set:
    """Names of the Mosaic (Pallas TPU) kernels a lowered program calls.
    Neither interpret mode nor mha_reference produces these."""
    import re
    return set(re.findall(
        r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"', lowered_text))


def _check_attention_against_reference() -> dict:
    """Forward and the three gradients of the kernels at B=2, H=12,
    S=1024, D=64 bf16 against mha_reference in float32 on the same values
    (tolerances of tests/test_models_ops.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import flash_attention, mha_reference

    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, g = (jax.random.normal(kk, (2, 12, 1024, 64), jnp.float32
                                    ).astype(jnp.bfloat16) for kk in ks)

    def run(fn, *xs):
        out, vjp = jax.vjp(lambda a, b, c: fn(a, b, c, True, None), *xs)
        return (out,) + vjp(g.astype(out.dtype))

    got = jax.jit(lambda *xs: run(flash_attention, *xs))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *xs: run(mha_reference, *xs))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    errs = {}
    for name, a, b, tol in zip(("out", "dq", "dk", "dv"), got, want,
                               (2e-2, 6e-3, 6e-3, 6e-3)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = 1.0 if name == "out" else max(1.0, float(np.abs(b).max()))
        np.testing.assert_allclose(a / scale, b / scale, atol=tol,
                                   rtol=tol, err_msg=f"flash {name}")
        errs[name] = float(np.abs(a - b).max() / scale)
    return errs


def _profile_two_steps(step, state, batch, trace_dir: str):
    """Trace two steps; say whether ProfileData finds a device plane."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(trace_dir):
        for _ in range(2):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    _require(len(files) == 1, f"expected one xplane file, got {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    planes = {p.name: sum(len(list(line.events)) for line in p.lines)
              for p in data.planes}
    info = {
        "trace_bytes": os.path.getsize(files[0]),
        "planes": planes,
        "device_plane_readable": any(
            n.startswith("/device:TPU") and c > 0
            for n, c in planes.items()),
    }
    shutil.rmtree(trace_dir, ignore_errors=True)
    return state, info


def _host_to_device_mb_s() -> list:
    """One 38 MB float32 image batch (64x3x224x224) through
    jax.device_put, twice (the first pays allocation)."""
    import jax
    import numpy as np

    x = np.random.default_rng(0).random((64, 3, 224, 224), np.float32)
    rates = []
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(x))
        rates.append(round(x.nbytes / 1e6 / (time.perf_counter() - t0), 1))
    return rates


def train_loop(config: dict):
    """train_loop_per_worker: runs in the TrainWorker, which owns the
    chip(s) the scheduler pinned for it."""
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import gpt_loss, make_train_step
    from ray_tpu.models.gpt import shard_batch

    facts = _device_facts()
    train.report({"device": facts})
    platform = config["platform"]
    _require(facts["platform"] == platform,
             f"chip worker computes on {facts['platform']!r}, "
             f"expected {platform!r}: {facts}")
    on_tpu = platform == "tpu"
    if on_tpu:
        _require(facts["local_device_count"] == len(facts["tpu_ids"]),
                 f"pinned to chips {facts['tpu_ids']} but JAX sees "
                 f"{facts['local_device_count']} local devices")

    cfg = config["cfg"]
    B, S, dp = config["batch"], config["seq"], config["mesh_dp"]
    rng = np.random.default_rng(config["seed"])
    toks = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    batch = (toks, np.roll(toks, -1, 1))   # ONE seeded batch, repeated
    summary = {}

    if dp:
        # One worker, several chips: the same step, data-parallel over a
        # mesh of this worker's devices.
        from ray_tpu.parallel import MeshConfig, make_mesh, tp_rules
        _require(facts["local_device_count"] == dp,
                 f"mesh dp={dp} needs {dp} local devices: {facts}")
        # First-step loss of the same seed on ONE device, for comparison.
        one_dev_loss = float(jax.jit(
            lambda p, b: gpt_loss(p, b, cfg))(
                make_train_step(cfg)[0](jax.random.PRNGKey(
                    config["seed"]))["params"], batch))
        mesh = make_mesh(MeshConfig(dp=dp))
        init_state, step = make_train_step(cfg, mesh=mesh,
                                           rules=tp_rules())
        batch = shard_batch(batch, mesh)
        shard_devs = sorted(s.device.id
                            for s in batch[0].addressable_shards)
        _require(len(set(shard_devs)) == dp,
                 f"batch shards sit on devices {shard_devs}, not {dp} "
                 f"distinct ones")
        summary.update(one_device_first_loss=one_dev_loss,
                       batch_shard_device_ids=shard_devs,
                       mesh=str(mesh.shape))
    else:
        init_state, step = make_train_step(cfg)
        batch = jax.device_put(batch)
    state = init_state(jax.random.PRNGKey(config["seed"]))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))

    # The kernels must be IN the program: three Mosaic custom calls.
    lowered = step.lower(state, batch).as_text()
    kernels = sorted(mosaic_kernel_names(lowered))
    if on_tpu:
        _require(set(kernels) == KERNELS,
                 f"lowered train step calls Mosaic kernels {kernels}, "
                 f"expected {sorted(KERNELS)}")

    t0 = time.perf_counter()
    state, metrics = step(state, batch)
    losses = [float(metrics["loss"])]
    first_step_s = time.perf_counter() - t0
    train.report({"step": 0, "loss": losses[0],
                  "first_step_s": first_step_s})
    if dp:
        _require(abs(losses[0] - one_dev_loss) <= 5e-3 * one_dev_loss,
                 f"first loss over {dp} chips {losses[0]} vs one chip "
                 f"{one_dev_loss} for the same seed")
    for i in range(1, config["steps"] + 1):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        train.report({"step": i, "loss": losses[-1]})
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0],
             f"loss did not fall on a repeated batch: {losses}")

    # Honest timing: a window closed by block_until_ready and one closed
    # by fetching a value must agree (an early-returning barrier once
    # reported 0.9 ms "steps").
    def window(sync) -> float:
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(config["steps"]):
            state, m = step(state, batch)
        sync(state, m)
        return (time.perf_counter() - t0) / config["steps"]

    step_s_barrier = window(lambda s, m: jax.block_until_ready(s))
    step_s_fetch = window(lambda s, m: float(m["loss"]))
    agree = abs(step_s_barrier - step_s_fetch) \
        <= 0.15 * max(step_s_barrier, step_s_fetch)
    tokens_per_s = B * S / step_s_barrier
    model_flops_per_s = 6.0 * n_params * tokens_per_s
    if on_tpu:
        _require(agree,
                 f"block_until_ready window {step_s_barrier * 1e3:.2f} "
                 f"ms/step vs value-fetch window "
                 f"{step_s_fetch * 1e3:.2f} ms/step")
        _require(facts["device_kind"] in PEAK_BF16_FLOPS,
                 f"no published peak for device_kind "
                 f"{facts['device_kind']!r}")
        peak = PEAK_BF16_FLOPS[facts["device_kind"]] \
            * facts["local_device_count"]
        _require(model_flops_per_s < peak,
                 f"6*N*tokens/s = {model_flops_per_s:.3e} exceeds the "
                 f"published peak {peak:.3e}: the timing is wrong")
        summary["attention_vs_reference_err"] = \
            _check_attention_against_reference()
        per_dev = [d.memory_stats()["bytes_in_use"]
                   for d in jax.local_devices()]
        _require(all(b > 0 for b in per_dev),
                 f"a device holds no bytes: {per_dev}")
        summary["bytes_in_use_per_device"] = per_dev

    state, profile = _profile_two_steps(
        step, state, batch, os.path.join(config["out_dir"], "trace"))
    summary.update(
        n_params=n_params, batch=B, seq=S, losses=losses,
        mosaic_kernels=kernels,
        mosaic_custom_calls=lowered.count("@tpu_custom_call"),
        first_step_s=round(first_step_s, 2),
        # Observations of one smoke run, not benchmark metrics.
        step_ms_block_until_ready=round(step_s_barrier * 1e3, 2),
        step_ms_value_fetch=round(step_s_fetch * 1e3, 2),
        tokens_per_s=round(tokens_per_s, 1),
        model_flops_per_s_6ND=model_flops_per_s,
        # Three facts ROADMAP A0/A1 wait on, for this device_kind.
        roadmap_facts={
            "device_kind": facts["device_kind"],
            "block_until_ready_agrees_with_value_fetch": agree,
            "profiler_device_plane_readable":
                profile["device_plane_readable"],
            "host_to_device_mb_s_38mb_float32": _host_to_device_mb_s(),
        },
        profile=profile,
    )
    train.report({"summary": summary})


# ---------------------------------------------------------------------------
# Phases (driver side; none of this touches JAX)
# ---------------------------------------------------------------------------
def run_train_phase(cfg, *, platform: str, batch: int, seq: int,
                    steps: int, out_dir: str, chips: int = 1,
                    seed: int = 0) -> dict:
    """GPT train steps through JaxTrainer on worker-owned chip(s).
    `platform` is what the worker must report ("tpu" from __main__);
    `chips` > 1 gives ONE worker that many chips and a dp mesh."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    on_tpu = platform == "tpu"
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "cfg": cfg, "platform": platform, "batch": batch, "seq": seq,
            "steps": steps, "seed": seed, "out_dir": out_dir,
            "mesh_dp": chips if chips > 1 else 0},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=on_tpu,
            resources_per_worker={"TPU": chips} if on_tpu else None),
        run_config=RunConfig(name=f"train_{chips}chip",
                             storage_path=os.path.join(out_dir, "train")))
    result = trainer.fit()
    if result.error is not None:
        raise SmokeFailure(
            f"train phase failed in the chip worker: {result.error!r}"
        ) from result.error
    _require("summary" in result.metrics,
             f"train loop ended without its summary: {result.metrics}")
    return {"device": result.metrics["device"],
            **result.metrics["summary"]}


def _post(url: str, body: dict, timeout: float) -> bytes:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read()
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"POST {body} answered HTTP {e.code}: "
                           f"{e.read()[:500]!r}") from e


def run_serve_phase(cfg, *, platform: str, num_replicas: int = 1,
                    max_batch: int = 8, requests: int = 8,
                    max_tokens: int = 32, deadline_s: float = 300.0
                    ) -> dict:
    """Serve + llm: continuous-batching replicas that initialise their own
    weights on their own chip, asked over HTTP through the proxy. Text is
    not judged (random weights, and ids >= 256 are dropped): completion,
    the engine's step count and the replica's device are."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    on_tpu = platform == "tpu"
    serve.run(build_llm_app(cfg=cfg, continuous_batching=True,
                            max_batch=max_batch, num_replicas=num_replicas,
                            num_tpus=1 if on_tpu else 0),
              name="smoke", route_prefix="/smoke")
    url = serve.proxy_address() + "/smoke"
    t0 = time.perf_counter()
    deadline = time.monotonic() + deadline_s
    # The first answers include process start, weight init on the chip
    # and the prefill + decode compiles; waves of small requests until
    # every replica has answered one.
    warm_pids, warmups = set(), 0
    with concurrent.futures.ThreadPoolExecutor(2 * num_replicas) as pool:
        while len(warm_pids) < num_replicas:
            _require(time.monotonic() < deadline,
                     f"only replicas {sorted(warm_pids)} answered within "
                     f"{deadline_s}s")
            wave = [pool.submit(_post, url, {"prompt": "warm up",
                                             "max_tokens": 2}, deadline_s)
                    for _ in range(2 * num_replicas)]
            for f in wave:
                reply = json.loads(f.result(timeout=deadline_s))
                warm_pids.add(reply["device"]["pid"])
            warmups += len(wave)
    first_answers_s = time.perf_counter() - t0

    def ask(i: int):
        body = {"prompt": f"request {i}: tell me something",
                "max_tokens": max_tokens, "stream": i == 0}
        raw = _post(url, body, timeout=deadline_s)
        return None if i == 0 else json.loads(raw)

    # All at once: the later ones join a batch that is already decoding.
    with concurrent.futures.ThreadPoolExecutor(requests) as pool:
        futures = [pool.submit(ask, i) for i in range(requests)]
        replies = [f.result(timeout=deadline_s) for f in futures]
    answered_s = time.perf_counter() - t0
    replies = [r for r in replies if r is not None]   # [0] streamed text
    for r in replies:
        _require("text" in r and "error" not in r, f"bad reply: {r}")
        _require(r["device"]["platform"] == platform,
                 f"replica computes on {r['device']['platform']!r}, "
                 f"expected {platform!r}: {r['device']}")
    by_pid = {r["device"]["pid"]: r["device"] for r in replies}
    _require(len(by_pid) == num_replicas,
             f"{len(by_pid)} of {num_replicas} replicas answered: "
             f"{sorted(by_pid)}")
    if on_tpu:
        chips = [tuple(d["tpu_ids"]) for d in by_pid.values()]
        _require(all(len(c) == 1 for c in chips)
                 and len(set(chips)) == num_replicas,
                 f"replicas do not hold distinct single chips: {chips}")
        _require(all(d["local_device_count"] == 1
                     for d in by_pid.values()),
                 f"a one-chip replica sees more devices: {by_pid}")
    steps = {pid: max(r["engine_steps"] for r in replies
                      if r["device"]["pid"] == pid) for pid in by_pid}
    sequential = warmups + requests * (max_tokens - 1)   # warm-ups: 1
    _require(all(s >= max_tokens - 1 for s in steps.values()),
             f"an engine took fewer decode steps than one request "
             f"needs: {steps}")
    _require(sum(steps.values()) < sequential,
             f"{sum(steps.values())} decode steps for {requests} "
             f"concurrent requests — no batching (sequential would "
             f"take {sequential})")
    serve.delete("smoke")
    return {"requests_answered": warmups + requests, "streamed": 1,
            "replicas": list(by_pid.values()), "engine_steps": steps,
            "sequential_steps_would_be": sequential,
            "first_answers_s": round(first_answers_s, 2),
            "all_answered_s": round(answered_s, 2)}


def _psum_loop(config: dict):
    """Workers that passed JaxBackendConfig.on_start share one runtime: a
    psum over all of its devices must be right in every worker."""
    import jax
    import numpy as np

    from ray_tpu import train

    n = jax.device_count()
    total = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "w"),
        mesh=jax.sharding.Mesh(np.array(jax.devices()), ("w",)),
        in_specs=jax.sharding.PartitionSpec("w"),
        out_specs=jax.sharding.PartitionSpec()))(np.arange(1.0, n + 1.0))
    train.report({"device_count": n,
                  "local_device_count": jax.local_device_count(),
                  "psum": float(total.addressable_data(0)[0]),
                  "expected": n * (n + 1) / 2})


def run_four_workers_phase(out_dir: str, *, platform: str) -> dict:
    """Four one-chip workers under JaxBackendConfig: either one runtime
    over all four with a correct psum, or a loud refusal in on_start —
    never four unsynchronised replicas."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        _psum_loop,
        scaling_config=ScalingConfig(num_workers=4,
                                     use_tpu=platform == "tpu"),
        run_config=RunConfig(name="four_workers",
                             storage_path=os.path.join(out_dir, "train"))
    ).fit()
    if result.error is None:
        m = result.metrics
        _require(m["device_count"] == 4 * m["local_device_count"]
                 and m["psum"] == m["expected"],
                 f"four workers ran without forming one runtime: {m}")
        return {"outcome": "one runtime", **m}
    _require("runtimes are isolated" in str(result.error),
             f"four-worker gang failed for another reason: "
             f"{result.error!r}")
    return {"outcome": "refused at on_start",
            "error": str(result.error)[-400:]}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def _parent_backend_initialised() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def _wait_chips_free(total: int, deadline_s: float = 60.0):
    """The dead worker's chips go back to the scheduler asynchronously."""
    import ray_tpu
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        free = ray_tpu.available_resources().get("TPU", 0.0)
        if free == total:
            return
        time.sleep(0.2)
    raise SmokeFailure(
        f"scheduler did not get its chips back within {deadline_s}s: "
        f"{free} of {total} free")


DEADLINE_S = 1100.0     # the whole run; the contract allows 1200


def _watchdog(seconds: float, phase: list):
    """Every wait has a deadline: when the whole run blows its budget, say
    where, then stop every process this script started (they share its
    process group) — itself included."""
    def fire():
        sys.stderr.write(
            f"chip_smoke: deadline of {seconds:.0f}s passed during phase "
            f"{phase[0]!r}; killing the process group\n")
        sys.stderr.flush()
        os.killpg(os.getpgrp(), signal.SIGKILL)
    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()
    return timer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="builder's run on a four-chip host: one worker "
                         "x 4 chips, four replicas x 1 chip, four workers "
                         "x 1 chip")
    args = ap.parse_args(argv)

    _require(os.path.isdir(os.path.join(REPO, "ray_tpu")),
             f"{REPO} holds chip_smoke.py but not the ray_tpu package")
    sys.path.insert(0, REPO)
    t_start = time.monotonic()
    if os.getpgrp() != os.getpid():   # a session leader already leads
        os.setpgrp()
    phase = ["preflight"]
    _watchdog(DEADLINE_S, phase)

    from ray_tpu import _native
    from ray_tpu._private.resources import (DEFAULT_COMPILE_CACHE_DIR,
                                            TPUAcceleratorManager)

    detected = TPUAcceleratorManager.get_current_node_num_accelerators()
    want = 4 if args.four_chip else 1
    _require(detected >= want,
             f"no TPU chip to run on: detected {detected} chip(s) "
             f"(/dev/accel*, /dev/vfio/*), need {want}; JAX_PLATFORMS="
             f"{os.environ.get('JAX_PLATFORMS')!r}. This script has no "
             f"CPU mode.")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE_DIR
    cache_entries_before = len(glob.glob(os.path.join(cache_dir, "*")))
    native_prebuilt = bool(glob.glob(os.path.join(
        os.path.dirname(_native.__file__), "libray_tpu*.so")))
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    shm_before = set(glob.glob("/dev/shm/ray_tpu_session_*"))

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import state
    from ray_tpu.models import GPTConfig

    cfg = dataclasses.replace(GPTConfig.gpt2_small(), remat=False)
    report = {"detected_chips": detected, "compile_cache_dir": cache_dir,
              "compile_cache_entries_before": cache_entries_before,
              "native_library_prebuilt": native_prebuilt}
    ray_tpu.init()
    try:
        node = state.current()
        report["store_backend"] = type(node.store).__name__
        report["native_dispatch"] = type(node.pool._mux).__name__
        report["store_dir"] = node.store_dir
        report["store_capacity"] = node.store.capacity
        _require(report["store_backend"] == "ArenaObjectStore"
                 and report["native_dispatch"] == "_NativeMux",
                 f"not on the native store/dispatcher: {report} "
                 f"(build error: {_native.build_error()})")
        _require(ray_tpu.cluster_resources().get("TPU") == detected,
                 f"scheduler holds {ray_tpu.cluster_resources()} but "
                 f"{detected} chips were detected")

        phase[0] = "train"
        chips = 4 if args.four_chip else 1
        report["train"] = train = run_train_phase(
            cfg, platform="tpu", batch=16, seq=1024, steps=5,
            out_dir=OUT_DIR, chips=chips)
        _say(phase="train", **train)
        dev = train["device"]
        if chips == detected:
            _require(dev["local_device_count"] == detected,
                     f"detected {detected} chip(s) but the worker that "
                     f"was given all of them sees "
                     f"{dev['local_device_count']}")
        report["compile_cache_entries_after_train"] = n_cache = len(
            glob.glob(os.path.join(cache_dir, "*")))
        _require(dev["compile_cache_dir"] == cache_dir and n_cache > 0,
                 f"compile cache {cache_dir} holds {n_cache} entries "
                 f"after the train phase; the worker used "
                 f"{dev['compile_cache_dir']!r}")

        # Hand-over: the trainer's worker was killed; another process
        # must be able to open the same chip.
        phase[0] = "hand-over"
        _wait_chips_free(detected)

        phase[0] = "serve"
        report["serve"] = served = run_serve_phase(
            cfg, platform="tpu", num_replicas=chips, requests=8 * chips)
        _say(phase="serve", **served)

        if args.four_chip:
            phase[0] = "four workers"
            serve.shutdown()
            _wait_chips_free(detected)
            report["four_workers"] = run_four_workers_phase(
                OUT_DIR, platform="tpu")
            _say(phase="four_workers", **report["four_workers"])
    finally:
        phase[0] = "shutdown"
        logs = os.path.join(state.current().session_dir, "logs")
        if os.path.isdir(logs):
            shutil.copytree(logs, os.path.join(OUT_DIR, "logs"),
                            dirs_exist_ok=True)
        serve.shutdown()
        ray_tpu.shutdown()

    leftover = set(glob.glob("/dev/shm/ray_tpu_session_*")) - shm_before
    _require(not leftover, f"leftover object-store sessions: {leftover}")
    _require(not _parent_backend_initialised(),
             "the parent process initialised a JAX backend")
    report["parent_backend_initialised"] = False
    report["wall_s"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    _say(phase="done", wall_s=report["wall_s"],
         first_step_s=train["first_step_s"],
         store_backend=report["store_backend"],
         native_dispatch=report["native_dispatch"],
         store_dir=report["store_dir"],
         store_capacity=report["store_capacity"],
         compile_cache_dir=cache_dir,
         compile_cache_entries=[cache_entries_before, n_cache],
         parent_backend_initialised=False,
         device_kind=dev["device_kind"], versions=dev["versions"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
