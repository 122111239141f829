"""GPT-2-small fine-tune (reference config #4: Ray Train HF
TransformersTrainer GPT-2 fine-tune, release/ml_user_tests/ — the
BASELINE.md north-star tokens/sec workload).

Native GPT-2 124M-equivalent (models.GPTConfig.gpt2_small: bf16 matmuls,
flash-attention Pallas kernel, remat) trained on synthetic token streams
through JaxTrainer. Run:

    python examples/train_gpt2_finetune.py [--steps 20] [--smoke]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def train_loop(config):
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import GPTConfig, make_train_step

    cfg = GPTConfig.tiny() if config["smoke"] else GPTConfig.gpt2_small()
    init_state, step = make_train_step(cfg)
    state = init_state(jax.random.PRNGKey(train.get_world_rank()))
    rng = np.random.default_rng(train.get_world_rank())
    B, S = config["batch_size"], config["seq_len"]
    if config["smoke"]:
        S = min(S, cfg.max_seq_len)

    # compile step excluded from timing
    toks = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    state, _ = step(state, (toks, np.roll(toks, -1, 1)))
    jax.block_until_ready(state["params"])

    t0 = time.perf_counter()
    tokens_done = 0
    for i in range(config["steps"]):
        toks = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
        state, metrics = step(state, (toks, np.roll(toks, -1, 1)))
        tokens_done += B * S
    jax.block_until_ready(state["params"])
    dt = time.perf_counter() - t0
    # The worker names the device that did the work: without --use-tpu it
    # is pinned to the CPU, and a rate must never pass for a chip's.
    train.report({
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "loss": float(metrics["loss"]),
        "tokens_per_s": tokens_done / dt,
        "step_ms": dt / config["steps"] * 1e3,
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--use-tpu", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.steps, args.batch_size, args.seq_len = 3, 2, 64

    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig

    ray_tpu.init(ignore_reinit_error=True)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"steps": args.steps,
                           "batch_size": args.batch_size,
                           "seq_len": args.seq_len,
                           "smoke": args.smoke},
        scaling_config=ScalingConfig(num_workers=args.workers,
                                     use_tpu=args.use_tpu))
    result = trainer.fit()
    if result.error is not None:
        print(json.dumps({"workload": "train_gpt2_finetune",
                          "error": str(result.error)}))
        raise SystemExit(1)
    m = result.metrics
    print(json.dumps({"workload": "train_gpt2_finetune",
                      "platform": m["platform"],
                      "device_kind": m["device_kind"],
                      **{k: round(float(m[k]), 3)
                         for k in ("loss", "tokens_per_s", "step_ms")}}))


if __name__ == "__main__":
    main()
