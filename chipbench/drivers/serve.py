"""Driver "serve": Serve + llm behind the HTTP proxy, asked by an
open-loop generator in a process of its own. Reads traffic/<mix>.json:
max_batch, the generator's parameters (loadgen.py), request_timeout_s,
overdue, traced_seconds and the reference tolerances.

Untraced, the replica is the program's own build_llm_app deployment.
Traced, it is TracedLLMServer below: the same engine behind the same
handler, plus the profiler hook the program's class lacks."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from .. import harness, loadgen, xplane
from ..harness import require

ROUTE = "/llm"


# ---------------------------------------------------------------------------
# inside processes that own the chip
# ---------------------------------------------------------------------------
class ReferenceCheck:
    """Prefill then decode through the program's make_continuous_fns, at
    the served cache shape, against the plain float32 reference's full
    forward on seeded prompts. Runs in an actor that is killed before the
    replica takes the chip; it also fills the compile cache."""

    def check(self, config: dict, traffic: dict, seed: int,
              platform: str) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import gpt_init
        from ray_tpu.models.generate import (_bucket_len, init_cache,
                                             make_continuous_fns)

        t0 = time.time()
        facts = harness.device_facts()
        require(facts["platform"] == platform,
                f"reference check ran on {facts['platform']!r}")
        family = harness.plugin("families", config["family"])
        cfg = family.build(config)
        max_len, slots = cfg.max_seq_len, traffic["max_batch"]
        steps = traffic["reference_decode_steps"]
        params = gpt_init(jax.random.PRNGKey(0), cfg)   # the engine's seed
        prefill, decode = make_continuous_fns(cfg, max_len, slots)
        cache = init_cache(cfg, slots, max_len)
        jax.block_until_ready((params, cache))
        t_loaded = time.time()
        rng = np.random.default_rng([seed, 2])
        n = traffic["reference_prompts"]
        spec = traffic["prompt_tokens"]
        lens = [int(x) for x in rng.integers(spec["min"],
                                             spec["median"] * 2, n)]
        at = [int(x) for x in rng.choice(slots, n, replace=False)]
        seqs, got = [], []
        tokens = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        for ln, slot in zip(lens, at):
            ids = [256] + [int(x) for x in rng.integers(0, 256, ln - 1)]
            bucket = _bucket_len(ln, max_len)
            last, cache = prefill(
                params, np.asarray([ids + [0] * (bucket - ln)], np.int32),
                cache, slot, ln)
            last = np.asarray(last)
            seqs.append(ids)
            got.append([last])
            tokens[slot], pos[slot] = int(last.argmax()), ln
        for _ in range(steps):
            logits, cache = decode(params, tokens, pos, cache)
            logits = np.asarray(logits)
            for j, slot in enumerate(at):
                seqs[j].append(int(tokens[slot]))
                got[j].append(logits[slot])
                tokens[slot] = int(logits[slot].argmax())
                pos[slot] += 1
        t_served = time.time()
        width = max_len       # one shape whatever the seed: one compile
        batch = np.asarray([s + [0] * (width - len(s)) for s in seqs],
                           np.int32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(
                lambda p, x: family.reference_logits(p, x, cfg.n_heads)
            )(params, batch))
        err = scale = 0.0
        for j, ln in enumerate(lens):
            for k, row in enumerate(got[j]):
                ref = want[j, ln - 1 + k]
                err = max(err, float(np.abs(row - ref).max()))
                scale = max(scale, float(np.abs(ref).max()))
        del cache
        return {"max_abs_logit_error": err, "reference_logit_scale": scale,
                "prompt_lengths": lens, "slots": at, "decode_steps": steps,
                "device": facts,
                "memory_peak_bytes": harness.memory_peak_bytes(),
                "seconds": time.time() - t0,
                "phases_s": {"weights_and_cache": t_loaded - t0,
                             "prefill_and_decode": t_served - t_loaded,
                             "reference": time.time() - t_served}}


def traced_llm_app(cfg, max_batch: int, num_tpus: float, trace_dir: str,
                   platform: str):
    """build_llm_app(continuous_batching=True) with a profiler hook:
    the same ContinuousBatchingEngine behind the same non-streaming
    handler; a body {"bench": ...} starts or stops a trace in the replica
    (only the process that holds the chip can trace it)."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=1,
                      ray_actor_options={"num_tpus": num_tpus}
                      if num_tpus else None,
                      max_ongoing_requests=max(16, 2 * max_batch))
    class TracedLLMServer:
        def __init__(self):
            from ray_tpu.llm.continuous import ContinuousBatchingEngine

            self.compiles = harness.CompileCounter()
            self.engine = ContinuousBatchingEngine(
                cfg=cfg, params=None, max_batch=max_batch)
            self._device = harness.device_facts()

        def __call__(self, request):
            body = request.get("body") or {}
            op = body.get("bench")
            if op == "trace_start":
                self.compiles.mark()
                xplane.start(trace_dir)
                return {"ok": True}
            if op == "trace_stop":
                traced = xplane.stop(trace_dir, platform)
                return {"trace": xplane.reduce(traced),
                        "memory_peak_bytes": harness.memory_peak_bytes(),
                        "compiled_since_mark": self.compiles.since_mark,
                        "device": self._device}
            text = self.engine.complete(
                str(body.get("prompt", "")),
                max(1, int(body.get("max_tokens", 32))), 0.0)
            return {"text": text, "device": self._device,
                    "engine_steps": self.engine.steps}

    return TracedLLMServer.bind()


# ---------------------------------------------------------------------------
# the parent: never touches JAX
# ---------------------------------------------------------------------------
def post(url: str, body: dict, timeout: float = 120.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def start_replica(cell, *, seed: int, trace: bool, platform: str) -> dict:
    """Reference check, then serve.run and warm-up of every shape the mix
    reaches. Returns url, checks and timings."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    t, on_tpu = cell.traffic, platform == "tpu"
    chips = 1.0 if on_tpu else 0.0
    cfg = cell.family.build(cell.config)
    # The engine stops a request at its budget and nowhere else on this
    # path (the handler passes no stop token), and cuts the budget only
    # where prompt + budget pass the context: a mix inside it gets
    # exactly max_tokens tokens a reply, which norm_latency divides by.
    # The reply itself carries no count.
    require(t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            <= cfg.max_seq_len, "the mix's longest request passes the "
            "context, so a reply's tokens would not be its max_tokens")
    checker = ray_tpu.remote(ReferenceCheck).options(
        num_tpus=chips).remote()
    t_check = time.time()
    ref = ray_tpu.get(checker.check.remote(cell.config, t, seed, platform))
    ref["with_process_start_s"] = time.time() - t_check
    ray_tpu.kill(checker)
    # The scheduler gets a killed actor's chip back asynchronously, and
    # the replica needs it.
    deadline = time.monotonic() + 60.0
    while on_tpu and ray_tpu.available_resources().get("TPU", 0.0) \
            < ray_tpu.cluster_resources().get("TPU"):
        require(time.monotonic() < deadline,
                "the scheduler did not get the reference check's chip back")
        time.sleep(0.1)

    t_run = time.time()
    if trace:
        app = traced_llm_app(cfg, t["max_batch"], chips, os.path.join(
            harness.out_dir(cell.name), "trace"), platform)
    else:
        app = build_llm_app(cfg=cfg, continuous_batching=True,
                            max_batch=t["max_batch"], num_tpus=chips)
    serve.run(app, name="chipbench", route_prefix=ROUTE)
    url = serve.proxy_address() + ROUTE
    deadline = time.monotonic() + 600.0
    while True:
        try:
            first = post(url, {"prompt": "ready?", "max_tokens": 2})
            if "text" in first:
                break
        except (urllib.error.URLError, OSError, ValueError) as e:
            require(time.monotonic() < deadline,
                    f"no answer from the replica within 600 s: {e!r}")
            time.sleep(0.2)
    ready_s = time.time() - t_run
    # One request per prefill bucket the mix reaches; the decode step is
    # one program whatever the occupancy.
    for bucket in loadgen.prompt_buckets(t, cfg.max_seq_len):
        n = min(bucket, t["prompt_tokens"]["max"])
        post(url, {"prompt": "w" * (n - 1), "max_tokens": 2})
    return {"url": url, "reference": ref, "replica_ready_s": ready_s,
            "warmed_s": time.time() - t_run, "first_reply": first}


def offer(url: str, traffic: dict, seed: int, seconds: float,
          traced_s: float = 0.0) -> dict:
    """Run the generator process against `url` and return its results
    with the window it was told. What has not answered a second after
    the window closes is cancelled. With traced_s, ask the replica for a
    trace of that length from a quarter of the way into the window (two
    seconds at most)."""
    t_open = time.time() + float(traffic.get("ramp_s", 0.0)) + 1.0
    job = {"url": url, "traffic": traffic, "seed": seed,
           "seconds": seconds, "t_open_unix": t_open,
           "t_stop_unix": t_open + seconds + 1.0}
    child = subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=harness.REPO)
    traced = {}

    def take_trace():
        time.sleep(max(0.0, t_open + min(2.0, seconds / 4) - time.time()))
        post(url, {"bench": "trace_start"})
        time.sleep(traced_s)
        traced.update(post(url, {"bench": "trace_stop"}, timeout=300.0))

    tracer = threading.Thread(target=take_trace, daemon=True) \
        if traced_s else None
    try:
        if tracer:
            tracer.start()
        out, _ = child.communicate(
            json.dumps(job).encode(),
            timeout=job["t_stop_unix"] - time.time() + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    require(child.returncode == 0,
            f"the load generator exited {child.returncode}")
    if tracer:
        tracer.join(timeout=300.0)
    return {"results": json.loads(out), "t_open_unix": t_open,
            "t_stop_unix": job["t_stop_unix"], "traced": traced}


def score(results: list, t_open: float, t_stop: float, seconds: float,
          platform: str, overdue: dict) -> dict:
    """End-to-end metrics, counters and the accounting checks from the
    generator's per-request results. A request counts in the window in
    which it ANSWERS, timed from when it was due: per-token latency does
    not depend on a request's length here (the decode step has one
    shape), and waiting for the window's last arrivals to finish would
    add up to 256 steps to every run. A request still unanswered at
    `t_stop`, when the generator cancels, is in flight if it is younger
    than `overdue` allows its length (first_token_s + max_tokens x
    ms_per_token) and has failed if it is older: a request the system
    dropped or hung is counted, not forgotten. What is merely queued
    shows as backlog growth."""
    t_close = t_open + seconds
    done_at = lambda r: r["done_unix"] if r["status"] else float("inf")  # noqa: E731
    ok = lambda r: r["status"] == 200 and r.get("text_ok") \
        and r.get("platform") == platform          # noqa: E731
    allowed_s = lambda r: overdue["first_token_s"] \
        + r["max_tokens"] * overdue["ms_per_token"] * 1e-3   # noqa: E731
    inside = sorted((r for r in results if t_open <= done_at(r) < t_close),
                    key=done_at)
    late = [r for r in results if not r["status"]
            and t_stop - r["due_unix"] > allowed_s(r)]
    good = [r for r in inside if ok(r)]
    lat_ms = lambda r: (r["done_unix"] - r["due_unix"]) * 1e3  # noqa: E731
    probes = [lat_ms(r) for r in good if r["probe"]]
    norm = [lat_ms(r) / r["max_tokens"] for r in good if not r["probe"]]
    require(norm, f"no request answered inside the window: {results[:3]}")
    steps = [r["engine_steps"] for r in good]
    sent_late = [(r["sent_unix"] - r["due_unix"]) * 1e3
                 for r in results if r["status"]]
    inflight = lambda at: sum(1 for r in results  # noqa: E731
                              if r["sent_unix"] <= at < done_at(r))
    failed = [r for r in inside if not ok(r)] + late
    # Accounting: a reply of n tokens needed n-1 decode steps, so the
    # engine's counter is at least that when it answers; and the window's
    # replies cannot have needed more steps than a sequential engine.
    counted = all(r["engine_steps"] >= r["max_tokens"] - 1 for r in good)
    span = max(steps) - min(steps)
    sequential = sum(r["max_tokens"] - 1 for r in results)
    pct = lambda xs, q: harness.percentile(xs, q) if xs else None  # noqa: E731
    return {
        "end_to_end": {"norm_latency_p50": harness.percentile(norm, 50)},
        "attempted": len(inside) + len(late), "failed": len(failed),
        "correct": not failed and counted and 0 < span <= sequential,
        "checks": {"failed_examples": failed[:3],
                   "overdue_unanswered": len(late),
                   "engine_steps_cover_each_reply": counted,
                   "engine_steps_in_window": span,
                   "sequential_steps_would_be": sequential},
        "counters": {
            "probes": len(probes), "requests": len(norm),
            "ttft_p50_ms": pct(probes, 50), "ttft_p90_ms": pct(probes, 90),
            "norm_latency_p90": harness.percentile(norm, 90),
            "probes_within_1s_share": sum(p <= 1e3 for p in probes)
            / max(1, len(probes)),
            "generator_late_p99_ms": harness.percentile(sent_late, 99),
            "out_tokens_in_window": sum(r["max_tokens"] for r in good),
            "engine_steps_in_window": span,
            "engine_steps_span_s": good[-1]["done_unix"]
            - good[0]["done_unix"],
            "inflight_at_open": inflight(t_open),
            "inflight_at_close": inflight(t_close),
            "offered_rate_per_s": sum(
                1 for r in results
                if t_open <= r["due_unix"] < t_close) / seconds,
        },
    }


def run(cell, *, seed: int, seconds: float, trace: bool, platform: str):
    import ray_tpu

    ray_tpu.init()
    t = cell.traffic
    up = start_replica(cell, seed=seed, trace=trace, platform=platform)
    ref = up["reference"]
    got = offer(up["url"], t, seed, seconds,
                traced_s=t["traced_seconds"] if trace else 0.0)
    rec = score(got["results"], got["t_open_unix"], got["t_stop_unix"],
                seconds, platform, t["overdue"])
    ref_ok = ref["max_abs_logit_error"] <= t["reference_logit_tolerance"]
    rec["checks"]["reference"] = {k: v for k, v in ref.items()
                                  if k != "device"}
    rec["correct"] = bool(rec["correct"] and ref_ok)
    rec["window_start_unix"] = got["t_open_unix"]
    rec["counters"].update(replica_ready_s=up["replica_ready_s"],
                           warmed_s=up["warmed_s"],
                           reference_check_s=ref["with_process_start_s"])
    traced = got["traced"]
    if trace:
        require(traced.get("trace"),
                f"the replica's trace holds no device operation: {traced}")
        facts, peak = traced["device"], traced["memory_peak_bytes"]
        rec["checks"]["compiled_in_traced_window"] = \
            traced["compiled_since_mark"]
        rec["correct"] = rec["correct"] \
            and traced["compiled_since_mark"] == 0
        rec["trace"] = traced["trace"]
    else:
        # The program's replica has no hook for its memory: the peak is
        # the reference check's, which held the same weights and cache
        # and ran the same prefill and decode programs on this chip.
        facts, peak = ref["device"], ref["memory_peak_bytes"]
        rec["trace"] = {}
    rec["device"] = {"platform": facts["platform"], "kind": facts["kind"],
                     "count": facts["count"], "memory_peak_bytes": peak}
    if rec["trace"]:
        rec["device"].update(busy_s=rec["trace"]["busy_s"],
                             window_s=rec["trace"]["window_s"])
    return rec
