"""Continuous-batching LLM serving demo.

Starts a Serve app whose replica hosts ONE shared
ContinuousBatchingEngine: concurrent requests decode together in a
slot-reuse KV batch, and a late request joins the RUNNING decode
instead of queueing behind it (vLLM-style continuous batching,
re-expressed for XLA's compile-once model — static shapes, slot reuse,
no recompiles as requests come and go).

Smoke (CPU): python examples/llm_serve_continuous.py --smoke
TPU:         python examples/llm_serve_continuous.py  (pins a chip per
             replica via num_tpus=1)
"""
import argparse
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model on CPU")
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=32)
    args = ap.parse_args()

    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import ray_tpu
    ray_tpu.init(ignore_reinit_error=True)

    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.models import GPTConfig

    if args.smoke:
        import jax

        from ray_tpu.models import gpt_init
        cfg = GPTConfig(vocab_size=272, d_model=64, n_heads=4,
                        n_layers=2, d_ff=128, max_seq_len=256)
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        app = build_llm_app(cfg=cfg, params=params,
                            continuous_batching=True,
                            max_batch=args.streams)
    else:
        # The replica owns the chip and initialises the (random) weights
        # there; this driver process never creates a device array.
        app = build_llm_app(cfg=GPTConfig.gpt2_small(),
                            continuous_batching=True,
                            max_batch=args.streams, num_tpus=1)

    serve.start()
    serve.run(app, name="llm", route_prefix="/llm")
    addr = serve.proxy_address()
    print(f"serving at {addr}/llm (continuous batching, "
          f"{args.streams} slots)")

    prompts = [f"request {i}: tell me something" for i in
               range(args.streams)]
    outs = [None] * len(prompts)

    def hit(i):
        body = json.dumps({"prompt": prompts[i],
                           "max_tokens": args.max_tokens}).encode()
        r = urllib.request.urlopen(f"{addr}/llm", data=body,
                                   timeout=600)
        outs[i] = json.loads(r.read())["text"]

    t0 = time.perf_counter()
    threads = [threading.Thread(target=hit, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    n_tok = sum(len(o or "") for o in outs)
    print(f"{len(prompts)} concurrent streams x {args.max_tokens} "
          f"tokens in {dt:.2f}s (~{n_tok / dt:.0f} chars/s aggregate)")
    for p, o in zip(prompts[:2], outs[:2]):
        print(f"  {p!r} -> {o[:40]!r}...")
    serve.shutdown()


if __name__ == "__main__":
    main()
