"""Data-parallel MNIST training (reference config #1: TorchTrainer MNIST,
python/ray/train/examples/pytorch/ run with 2 CPU workers).

JaxTrainer runs `train_loop_per_worker` on N workers; each worker builds
the same MLP, shards the (synthetic, zero-egress) MNIST-shaped dataset via
streaming_split, and reports loss/accuracy per epoch. Run:

    python examples/train_mnist.py [--workers 2] [--epochs 2] [--smoke]
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
    import jax.numpy as jnp
    import optax

    from ray_tpu import train

    rng = jax.random.PRNGKey(train.get_world_rank())

    def init_params(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": jax.random.normal(k1, (784, 128)) * 0.05,
            "b1": jnp.zeros(128),
            "w2": jax.random.normal(k2, (128, 10)) * 0.05,
            "b2": jnp.zeros(10),
        }

    def loss_fn(params, x, y):
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        logits = h @ params["w2"] + params["b2"]
        onehot = jax.nn.one_hot(y, 10)
        loss = -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits) * onehot, axis=-1))
        acc = jnp.mean(jnp.argmax(logits, -1) == y)
        return loss, acc

    tx = optax.adam(config["lr"])
    params = init_params(rng)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x, y)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    shard = train.get_dataset_shard("train")
    for epoch in range(config["epochs"]):
        n, loss_sum, acc_sum, batches = 0, 0.0, 0.0, 0
        t0 = time.perf_counter()
        for batch in shard.iter_batches(batch_size=config["batch_size"]):
            x = jnp.asarray(batch["image"]).reshape(-1, 784)
            y = jnp.asarray(batch["label"])
            params, opt_state, loss, acc = step(params, opt_state, x, y)
            n += len(y)
            loss_sum += float(loss)
            acc_sum += float(acc)
            batches += 1
        train.report({
            "epoch": epoch, "loss": loss_sum / max(batches, 1),
            "accuracy": acc_sum / max(batches, 1),
            "samples_per_s": n / (time.perf_counter() - t0),
        })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.rows, args.epochs = 1024, 1

    import numpy as np

    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu.train import JaxTrainer, ScalingConfig

    ray_tpu.init(ignore_reinit_error=True)
    # A gang larger than the cluster can never schedule (each worker
    # reserves one CPU) — size to what's there, like the reference's
    # ScalingConfig guidance.
    workers = min(args.workers,
                  max(1, int(ray_tpu.cluster_resources().get("CPU", 1))))
    rng = np.random.default_rng(0)
    ds = rd.from_items([
        {"image": rng.normal(size=(28, 28)).astype(np.float32),
         "label": int(rng.integers(0, 10))}
        for _ in range(args.rows)])

    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"lr": 1e-3, "epochs": args.epochs,
                           "batch_size": args.batch_size},
        scaling_config=ScalingConfig(num_workers=workers),
        datasets={"train": ds})
    result = trainer.fit()
    if result.error is not None:
        print(json.dumps({"workload": "train_mnist",
                          "error": str(result.error)}))
        raise SystemExit(1)
    print(json.dumps({"workload": "train_mnist", "workers": workers,
                      **{k: round(float(result.metrics[k]), 4)
                         for k in ("loss", "accuracy", "samples_per_s")
                         if k in result.metrics}}))


if __name__ == "__main__":
    main()
