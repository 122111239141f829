"""The one general generator of request traffic. A traffic mix is a data
file of parameters; this turns (parameters, seed, duration) into a fixed
schedule of requests, the same for the same seed.

Parameters it reads (traffic/<name>.json, driver "serve"):

    rate_per_s       mean arrivals per second (open loop)
    arrival          "poisson" (exponential gaps) or "gamma" with `arrival_cv`
                     (cv > 1 is bursty)
    prompt_tokens    {"median", "sigma", "min", "max"}: lognormal, clipped
    output_tokens    the same for the output budget
    probe_share      share of arrivals sent with max_tokens = 1; their
                     latency is the time to the first token
    ramp_s           seconds of arrivals before the measured window opens
                     (they fill the batch and are not scored)
    ramp_rate_x      arrivals during the ramp come this many times faster
                     (default 1), so that a short ramp reaches the
                     steady number of requests in flight
    shared_prefix_tokens, prefix_pool
                     optional: that many leading tokens come from one of
                     `prefix_pool` prefixes fixed by the seed
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # relative to the opening of the measured window
    prompt: str           # ASCII; the byte tokenizer adds BOS
    prompt_tokens: int
    max_tokens: int
    probe: bool

    @property
    def scored(self) -> bool:
        return self.due_s >= 0.0


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _gaps(rng, traffic: dict, n: int) -> np.ndarray:
    mean = 1.0 / traffic["rate_per_s"]
    kind = traffic.get("arrival", "poisson")
    if kind == "poisson":
        return rng.exponential(mean, n)
    if kind == "gamma":
        shape = 1.0 / traffic["arrival_cv"] ** 2
        return rng.gamma(shape, mean / shape, n)
    raise ValueError(f"unknown arrival process {kind!r}")


def schedule(traffic: dict, seed: int, seconds: float) -> list:
    """Every request due in [-ramp_s, seconds), in order of its due time."""
    rng = np.random.default_rng([int(seed), 0x5E17E])
    ramp = float(traffic.get("ramp_s", 0.0))
    span = ramp + seconds
    n = int(span * traffic["rate_per_s"] * 1.5) + 64
    due = np.cumsum(_gaps(rng, traffic, n)) \
        - ramp * float(traffic.get("ramp_rate_x", 1.0))
    while due[-1] < seconds:                      # far tail of a bursty mix
        due = np.concatenate(
            [due, due[-1] + np.cumsum(_gaps(rng, traffic, n))])
    due = due[due < seconds]
    due[due < 0] /= float(traffic.get("ramp_rate_x", 1.0))
    n = len(due)
    p_len = _lognormal(rng, traffic["prompt_tokens"], n)
    o_len = _lognormal(rng, traffic["output_tokens"], n)
    probe = rng.random(n) < traffic.get("probe_share", 0.0)
    shared = int(traffic.get("shared_prefix_tokens", 0))
    pool = [_text(rng, shared) for _ in
            range(int(traffic.get("prefix_pool", 1)) if shared else 0)]
    out = []
    for i in range(n):
        body = int(p_len[i]) - 1                  # BOS is the first token
        if pool:
            prefix = pool[int(rng.integers(len(pool)))][:body]
            text = prefix + _text(rng, body - len(prefix))
        else:
            text = _text(rng, body)
        out.append(Request(i, float(due[i]), text, int(p_len[i]),
                           1 if probe[i] else int(o_len[i]),
                           bool(probe[i])))
    return out


def _text(rng, n: int) -> str:
    """n printable ASCII bytes (one token each under the byte tokenizer)."""
    return bytes(rng.integers(32, 127, max(0, n), dtype=np.uint8)
                 ).decode("ascii")


def prompt_buckets(traffic: dict, max_len: int) -> list:
    """The prefill buckets this mix can reach: powers of two from 64,
    capped (mirrors models.generate._bucket_len)."""
    def bucket(n):
        b = 64
        while b < n:
            b *= 2
        return min(b, max_len)
    lo = bucket(traffic["prompt_tokens"]["min"])
    hi = bucket(traffic["prompt_tokens"]["max"])
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


# ---------------------------------------------------------------------------
# sending it: a process of its own, one thread, one event loop
# ---------------------------------------------------------------------------
async def _fire(url: str, requests: list, t_open_unix: float,
                timeout_s: float, t_stop_unix: float) -> list:
    """Send each request when it is due; at t_stop_unix cancel what has
    not answered (it comes back with status 0, "unanswered")."""
    import asyncio
    import json
    import time

    import aiohttp

    async def one(session, r: Request, due_unix: float) -> dict:
        out = {"index": r.index, "due_unix": due_unix, "probe": r.probe,
               "max_tokens": r.max_tokens, "scored": r.scored,
               "prompt_tokens": r.prompt_tokens, "sent_unix": time.time()}
        try:
            async with session.post(url, json={
                    "prompt": r.prompt, "max_tokens": r.max_tokens}) as resp:
                raw = await resp.read()
                out["done_unix"] = time.time()
                out["status"] = resp.status
            reply = json.loads(raw)
            out["engine_steps"] = reply.get("engine_steps")
            out["platform"] = (reply.get("device") or {}).get("platform")
            out["text_ok"] = isinstance(reply.get("text"), str)
        except Exception as e:  # noqa: BLE001 — a failed request is a result
            out.setdefault("done_unix", time.time())
            out.setdefault("status", -1)
            out["error"] = repr(e)[:200]
        return out

    timeout = aiohttp.ClientTimeout(total=timeout_s)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout,
                                     connector=conn) as session:
        tasks = []
        for r in requests:
            due_unix = t_open_unix + r.due_s
            delay = due_unix - time.time()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(one(session, r, due_unix)))
        await asyncio.wait(tasks, timeout=max(0.0, t_stop_unix - time.time()))
        out = []
        for r, task in zip(requests, tasks):
            if task.done():
                out.append(task.result())
            else:
                task.cancel()
                out.append({"index": r.index, "probe": r.probe,
                            "due_unix": t_open_unix + r.due_s,
                            "max_tokens": r.max_tokens, "scored": r.scored,
                            "prompt_tokens": r.prompt_tokens,
                            "sent_unix": t_open_unix + r.due_s,
                            "done_unix": None, "status": 0})
        await asyncio.gather(*tasks, return_exceptions=True)
        return out


def main() -> int:
    """stdin: {"url", "traffic", "seed", "seconds", "t_open_unix",
    "t_stop_unix"} as JSON; stdout: the per-request results as JSON."""
    import asyncio
    import json
    import sys

    job = json.load(sys.stdin)
    reqs = schedule(job["traffic"], job["seed"], job["seconds"])
    results = asyncio.run(_fire(job["url"], reqs, job["t_open_unix"],
                                job["traffic"]["request_timeout_s"],
                                job["t_stop_unix"]))
    json.dump(results, sys.stdout)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
