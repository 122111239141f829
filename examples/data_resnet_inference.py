"""ResNet-50 batch inference through Data actor pools (reference config
#3: Ray Data `map_batches` ResNet-50 over ImageNet — the
`map_batches(..., num_gpus=1)` GPU path, actor_pool_map_operator.py:34).

Synthetic ImageNet-shaped images (zero egress); each pool actor holds a
jitted ResNet-50 (`num_tpus=1` pins a chip per actor on TPU hosts). Run:

    python examples/data_resnet_inference.py [--images 256] [--smoke]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ResNetPredictor:
    def __init__(self, tiny: bool):
        from ray_tpu.models import ResNetConfig, make_predictor

        cfg = ResNetConfig.tiny() if tiny else ResNetConfig.resnet50()
        self.predict = make_predictor(cfg)

    def __call__(self, batch):
        import numpy as np

        batch["label"] = np.asarray(self.predict(batch["image"]))
        return batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=256)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--concurrency", type=int, default=2)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-tpus", type=float, default=0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.images, args.image_size = 64, 64

    import numpy as np

    import ray_tpu
    import ray_tpu.data as rd

    ray_tpu.init(ignore_reinit_error=True)
    rng = np.random.default_rng(0)
    side = args.image_size
    ds = rd.from_items([
        {"image": rng.normal(size=(side, side, 3)).astype(np.float32)}
        for _ in range(args.images)])

    kwargs = dict(batch_size=args.batch_size,
                  concurrency=args.concurrency,
                  fn_constructor_args=(args.smoke,))
    if args.num_tpus:
        kwargs["num_tpus"] = args.num_tpus
    t0 = time.perf_counter()
    out = ds.map_batches(ResNetPredictor, **kwargs)
    n = sum(1 for _ in out.iter_rows())
    dt = time.perf_counter() - t0
    print(json.dumps({
        "workload": "data_resnet_inference", "images": n,
        "images_per_s": round(n / dt, 2),
        "batch_size": args.batch_size,
        "concurrency": args.concurrency,
    }))


if __name__ == "__main__":
    main()
