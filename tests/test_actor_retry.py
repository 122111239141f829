"""Actor task retries: exception retries, death retries, and the data
actor pool surviving worker failures mid-stream.

Reference strategy: python/ray/tests/test_actor_failures.py
(max_task_retries / retry_exceptions on actor methods; actor restart
replays in-flight tasks) and data/tests for ActorPoolMapOperator worker
replacement (actor_pool_map_operator.py:34,446).
"""

import os
import tempfile

import numpy as np
import pytest

import ray_tpu as ray


@pytest.fixture(scope="module", autouse=True)
def _init():
    ray.init(num_cpus=4, ignore_reinit_error=True)
    yield


def _marker():
    fd, path = tempfile.mkstemp(prefix="ray_tpu_retry_")
    os.close(fd)
    os.unlink(path)
    return path


def test_actor_method_retry_exceptions():
    @ray.remote
    class Flaky:
        def __init__(self):
            self.calls = 0

        def hello(self):
            self.calls += 1
            if self.calls < 3:
                raise RuntimeError(f"transient {self.calls}")
            return self.calls

    a = Flaky.remote()
    got = ray.get(a.hello.options(retry_exceptions=True,
                                  max_task_retries=3).remote())
    assert got == 3


def test_actor_method_no_retry_by_default():
    @ray.remote
    class Flaky:
        def boom(self):
            raise RuntimeError("once")

    a = Flaky.remote()
    with pytest.raises(Exception, match="once"):
        ray.get(a.boom.remote())


def test_actor_death_retries_inflight_task():
    """A worker that dies MID-TASK: the actor restarts (max_restarts)
    and the in-flight call re-runs on the fresh instance
    (max_task_retries) instead of raising ActorDiedError."""
    marker = _marker()

    @ray.remote(max_restarts=1, max_task_retries=2)
    class DieOnce:
        def work(self, marker):
            if not os.path.exists(marker):
                open(marker, "w").close()
                os._exit(1)  # simulated crash mid-task
            return "survived"

    a = DieOnce.remote()
    try:
        assert ray.get(a.work.remote(marker), timeout=60) == "survived"
    finally:
        if os.path.exists(marker):
            os.unlink(marker)


def test_actor_death_without_retry_budget_fails():
    @ray.remote(max_restarts=1)  # restarts, but tasks have no budget
    class Dies:
        def work(self):
            os._exit(1)

    a = Dies.remote()
    with pytest.raises(ray.exceptions.ActorDiedError):
        ray.get(a.work.remote(), timeout=60)


def test_map_batches_actor_pool_survives_worker_death():
    """VERDICT r2 #5 done-when: an actor-pool map_batches pipeline
    completes even when one pool actor dies mid-run."""
    from ray_tpu import data as rdata

    marker = _marker()

    class KillerMapper:
        def __call__(self, batch):
            # First batch that sees no marker kills its worker.
            if not os.path.exists(marker):
                open(marker, "w").close()
                os._exit(1)
            batch["x"] = batch["x"] * 2
            return batch

    try:
        ds = rdata.from_items([{"x": float(i)} for i in range(64)])
        out = ds.map_batches(KillerMapper, batch_size=8,
                             concurrency=2).take_all()
        assert sorted(r["x"] for r in out) == [2.0 * i for i in range(64)]
    finally:
        if os.path.exists(marker):
            os.unlink(marker)


def test_map_batches_actor_pool_survives_transient_exception():
    """A transient in-actor exception (the regression class of an early
    bench record) retries instead of killing the pipeline."""
    from ray_tpu import data as rdata

    marker = _marker()

    class FlakyMapper:
        def __call__(self, batch):
            if not os.path.exists(marker):
                open(marker, "w").close()
                raise RuntimeError("transient backend error")
            batch["x"] = batch["x"] + 1
            return batch

    try:
        ds = rdata.from_items([{"x": float(i)} for i in range(32)])
        out = ds.map_batches(FlakyMapper, batch_size=8,
                             concurrency=2).take_all()
        assert sorted(r["x"] for r in out) == [float(i + 1)
                                               for i in range(32)]
    finally:
        if os.path.exists(marker):
            os.unlink(marker)
