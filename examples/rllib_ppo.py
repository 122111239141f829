"""RLlib PPO (reference config #5: rllib/tuned_examples/ppo/ — the
multi-learner PPO suite; here: mesh-DP JAX learner + env-runner actors).

Run:

    python examples/rllib_ppo.py [--iters 5] [--smoke]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="CartPole-v1")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--runners", type=int, default=2)
    ap.add_argument("--fragment", type=int, default=512)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.iters, args.fragment = 2, 128

    import ray_tpu
    from ray_tpu.rllib import PPOConfig

    ray_tpu.init(ignore_reinit_error=True)
    algo = (PPOConfig()
            .environment(args.env)
            .env_runners(num_env_runners=args.runners,
                         rollout_fragment_length=args.fragment)
            .training(lr=3e-4)
            .debugging(seed=0)
            .build())
    result = {}
    try:
        for _ in range(args.iters):
            result = algo.train()
    finally:
        algo.stop()
    print(json.dumps({
        "workload": "rllib_ppo", "env": args.env,
        "iterations": result.get("training_iteration"),
        "episode_return_mean": round(
            float(result.get("episode_return_mean", float("nan"))), 2),
        "env_steps": result.get("num_env_steps_sampled_lifetime"),
    }))


if __name__ == "__main__":
    main()
