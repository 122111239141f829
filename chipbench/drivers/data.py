"""Driver "data": batch inference through Dataset.map_batches — CPU
preprocess tasks feeding one actor that owns the chip — with the consumer
counting labels. Reads traffic/<mix>.json: batch_size, num_blocks,
predictor_concurrency, warmup_batches, traced_batches and the reference
tolerance; the executor keeps the program's default limits. The
pipeline's shape is examples/data_resnet_inference.py's; the timing is
not."""

from __future__ import annotations

import json
import os
import time

from .. import harness, xplane
from ..harness import require

# torchvision's ImageNet normalisation, as the upstream transform has it
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def preprocess(batch, seed: int, side: int):
    """CPU stage, the user's own: one seeded uint8 image a row (decoding
    is absent) through ToTensor + Normalize as the upstream pipeline has
    them, left as float32 HWC."""
    import numpy as np

    ids = batch["id"]
    rng = np.random.default_rng([seed, int(ids[0])])
    image = rng.integers(0, 256, (len(ids), side, side, 3), dtype=np.uint8
                         ).astype(np.float32)
    image *= np.float32(1.0 / 255.0)
    image -= np.asarray(MEAN, np.float32)
    image /= np.asarray(STD, np.float32)
    return {"id": ids, "image": image}


class Predictor:
    """The map_batches actor that owns the chip. One JSON line of timings
    per call goes to calls.jsonl in the run's out_dir; the traced run
    also leaves trace.json there."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 platform: str, trace: bool, out: str):
        import jax
        import numpy as np

        from ray_tpu.models import make_predictor, resnet_forward, \
            resnet_init

        t0 = time.time()
        self.compiles = harness.CompileCounter()
        self.facts = harness.device_facts()
        require(self.facts["platform"] == platform,
                f"predictor computes on {self.facts['platform']!r}")
        family = harness.plugin("families", config["family"])
        cfg = family.build(config)
        # The program's own default weights (key 0), as a user of
        # make_predictor(cfg) gets them: it closes over its parameters, so
        # they are constants of the compiled program and weights from
        # --seed would compile anew in every run. --seed drives the images.
        # One jitted call: leaf by leaf, each of some 500 small programs
        # compiles anew in every run (the cache keeps only long compiles).
        params = jax.jit(lambda key: resnet_init(key, cfg))(
            jax.random.PRNGKey(0))
        self.predict = make_predictor(cfg, params=params)
        side, n = config["image_size"], traffic["reference_images"]
        # Outside the window: the program's logits against the plain
        # float32 reference on seeded images.
        x = preprocess({"id": np.arange(n)}, seed, side)["image"]
        got = np.asarray(jax.jit(
            lambda im: resnet_forward(params, im, cfg))(x))
        want = np.asarray(jax.jit(lambda im: family.reference_logits(
            params, im, cfg.bottleneck))(x))
        scale = float(np.abs(want).max())
        self.reference = {
            "max_abs_logit_error": float(np.abs(got - want).max()),
            "reference_logit_scale": scale,
            "relative_tolerance": traffic["reference_logit_tolerance"]}
        # Warm the one shape.
        warm = np.zeros((traffic["batch_size"], side, side, 3), np.float32)
        np.asarray(self.predict(jax.device_put(warm)))
        self.platform, self.out = platform, out
        self.calls = 0
        # One contiguous trace: from before call `trace_from` to after
        # call `trace_to - 1`, two calls into the window.
        self.trace_from = traffic["warmup_batches"] + 2 if trace else -1
        self.trace_to = self.trace_from + traffic["traced_batches"]
        self.trace_dir = os.path.join(out, "trace")
        self.log = open(os.path.join(out, "calls.jsonl"), "a")
        self._write({"facts": self.facts, "reference": self.reference,
                     "constructor_s": time.time() - t0,
                     "flops_per_image":
                         family.forward_flops_per_image(cfg, side)})

    def _write(self, obj: dict):
        self.log.write(json.dumps(obj) + "\n")
        self.log.flush()

    def __call__(self, batch):
        import jax
        import numpy as np

        if self.calls == self.trace_from:
            xplane.start(self.trace_dir)
        t0 = time.perf_counter()
        start_unix = time.time()
        with xplane.span("h2d"):
            x = jax.block_until_ready(jax.device_put(batch["image"]))
        t1 = time.perf_counter()
        with xplane.span("predict"):
            y = jax.block_until_ready(self.predict(x))
        t2 = time.perf_counter()
        with xplane.span("fetch"):
            labels = np.asarray(y)
        t3 = time.perf_counter()
        self.calls += 1
        if self.calls == self.trace_to:
            reduced = xplane.reduce(xplane.stop(self.trace_dir,
                                                self.platform))
            reduced["batches"] = self.trace_to - self.trace_from
            with open(os.path.join(self.out, "trace.json"), "w") as f:
                json.dump(reduced, f)
        self._write({"start_unix": start_unix, "n": len(labels),
                     "h2d_ms": (t1 - t0) * 1e3,
                     "compute_ms": (t2 - t1) * 1e3,
                     "fetch_ms": (t3 - t2) * 1e3,
                     "trace_stop_ms": (time.perf_counter() - t3) * 1e3,
                     "programs_built": self.compiles.total,
                     "memory_peak_bytes": harness.memory_peak_bytes()})
        return {"id": batch["id"], "label": labels}


def run(cell, *, seed: int, seconds: float, trace: bool, platform: str):
    import ray_tpu
    import ray_tpu.data as rd

    t, on_tpu = cell.traffic, platform == "tpu"
    out = harness.out_dir(cell.name)
    for name in ("calls.jsonl", "trace.json"):
        if os.path.exists(os.path.join(out, name)):
            os.remove(os.path.join(out, name))
    ray_tpu.init()
    size = t["batch_size"]
    ds = rd.range(t["num_blocks"] * size,
                  override_num_blocks=t["num_blocks"])
    ds = ds.map_batches(preprocess, batch_size=None,
                        fn_kwargs={"seed": seed,
                                   "side": cell.config["image_size"]})
    ds = ds.map_batches(
        Predictor, batch_size=size,
        concurrency=t["predictor_concurrency"],
        num_tpus=1 if on_tpu else None,
        fn_constructor_kwargs={"config": cell.config, "traffic": t,
                               "seed": seed, "platform": platform,
                               "trace": trace, "out": out})
    # The consumer: the window opens when the last warm-up batch arrives
    # (the bundles the executor took in flight while the Predictor loaded
    # have passed by then) and closes with the last batch inside
    # `seconds`: from one arrival to another, so a whole number of
    # batches over the time they took.
    t_open = t_last = None
    images, batches_ok = 0, True
    stream = ds.iter_batches(batch_size=None)
    try:
        for i, batch in enumerate(stream):
            now = time.time()
            if i == t["warmup_batches"] - 1:
                t_open = t_last = now
            elif t_open is not None:
                if now - t_open > seconds:
                    break
                images += len(batch["label"])
                batches_ok = batches_ok and len(batch["label"]) == size
                t_last = now
    finally:
        stream.close()
    require(t_open is not None and images > 0,
            "the stream ended before the window opened")

    with open(os.path.join(out, "calls.jsonl")) as f:
        lines = [json.loads(x) for x in f]
    head, calls = lines[0], lines[1:]
    inside = [c for c in calls if t_open <= c["start_unix"] <= t_last]
    require(inside, "no predictor call inside the window")
    compiled = inside[-1]["programs_built"] - inside[0]["programs_built"]
    ref = head["reference"]
    ref_ok = ref["max_abs_logit_error"] \
        <= ref["relative_tolerance"] * ref["reference_logit_scale"]
    facts = head["facts"]
    med = lambda k: harness.percentile(  # noqa: E731
        [c[k] for c in inside], 50)
    reduced = {}
    if trace:
        path = os.path.join(out, "trace.json")
        require(os.path.exists(path),
                "the window closed before the predictor's trace did")
        reduced = harness.load_json(path)
    device = {"platform": facts["platform"], "kind": facts["kind"],
              "count": facts["count"],
              "memory_peak_bytes": max(c["memory_peak_bytes"]
                                       for c in calls)}
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    return {
        "correct": bool(ref_ok and batches_ok and compiled == 0),
        "checks": {"reference": ref, "every_batch_full": batches_ok,
                   "compiled_in_window": compiled},
        "attempted": images // size, "failed": 0,
        "window_start_unix": t_open,
        "end_to_end": {"images_per_s": images / (t_last - t_open)},
        "device": device,
        "counters": {
            "images": images, "window_s": t_last - t_open,
            "predictor_calls": len(inside),
            "predictor_busy_s": sum(c["h2d_ms"] + c["compute_ms"]
                                    + c["fetch_ms"] for c in inside) / 1e3,
            "h2d_ms_p50": med("h2d_ms"), "compute_ms_p50": med("compute_ms"),
            "fetch_ms_p50": med("fetch_ms"),
            "batch_flops": head["flops_per_image"] * size,
            "constructor_s": head["constructor_s"],
            "peaks": harness.peaks_for(facts["kind"]) if on_tpu else None,
        },
        "trace": reduced,
    }
