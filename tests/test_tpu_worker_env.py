"""The chip path on CPU: what environment a worker pinned to chips gets,
and that the scheduler hands out, withholds and reuses specific chip ids.

The actors here never import jax, so RAY_TPU_NUM_CHIPS can pretend there
are chips: only the hand-out is under test, not libtpu.
"""
import dataclasses
import os

import pytest

import ray_tpu
from ray_tpu._private import resources


def test_chip_worker_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env = resources.tpu_worker_extra_env([2])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert env == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1",
        # Failing to open the chip is an error there, not a CPU run.
        "JAX_PLATFORMS": "tpu",
        # One fixed directory in the checkout: no pid, session or time.
        "JAX_COMPILATION_CACHE_DIR": os.path.join(repo, ".jax_cache"),
    }
    assert env == resources.tpu_worker_extra_env([2])
    four = resources.tpu_worker_extra_env([3, 2, 1, 0])
    assert four["TPU_VISIBLE_CHIPS"] == "0,1,2,3"    # ascending
    assert four["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    # Workers inherit os.environ; nothing in code sets another.
    assert "JAX_COMPILATION_CACHE_DIR" not in \
        resources.tpu_worker_extra_env([0])


@ray_tpu.remote(num_tpus=1)
class ChipHolder:
    def chips(self):
        return ray_tpu.get_tpu_ids(), os.environ["JAX_PLATFORMS"]


def test_two_chips_three_actors(monkeypatch, shutdown_only):
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "2")
    ray_tpu.init(num_cpus=4)
    assert ray_tpu.cluster_resources()["TPU"] == 2
    a, b = ChipHolder.remote(), ChipHolder.remote()
    (ids_a, plat_a), (ids_b, _) = ray_tpu.get(
        [a.chips.remote(), b.chips.remote()], timeout=60)
    assert plat_a == "tpu"
    assert sorted(ids_a + ids_b) == [0, 1]      # distinct, one each
    # Both chips are held: a third holder is not placed...
    c = ChipHolder.remote()
    pending = c.chips.remote()
    ready, _ = ray_tpu.wait([pending], timeout=1.0)
    assert not ready
    # ...until a holder dies, and then it gets exactly the freed chip.
    ray_tpu.kill(a)
    ids_c, _ = ray_tpu.get(pending, timeout=60)
    assert ids_c == ids_a
    assert ray_tpu.get(b.chips.remote(), timeout=60)[0] == ids_b


def test_cpu_pool_worker_sees_no_chips(monkeypatch, shutdown_only):
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "2")
    # A driver that itself was pinned must not leak its ids to workers.
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def probe():
        return ray_tpu.get_tpu_ids(), os.environ["JAX_PLATFORMS"]

    assert ray_tpu.get(probe.remote(), timeout=60) == ([], "cpu")


def test_on_start_refuses_isolated_runtimes(monkeypatch):
    """Workers whose runtimes did not join (one-chip workers of one TPU
    host under jax.distributed) must never pass for data-parallel."""
    from ray_tpu.train.backend import JaxBackendConfig
    from ray_tpu.train.session import TrainContext
    from ray_tpu.util.collective.collective_group import xla_collective_group

    monkeypatch.setattr(xla_collective_group, "_rendezvous",
                        lambda *a, **k: "127.0.0.1:1")
    monkeypatch.setattr(xla_collective_group, "ensure_distributed",
                        lambda *a, **k: None)      # joined nothing
    ctx = TrainContext(world_size=4, world_rank=0, experiment_name="x")
    with pytest.raises(RuntimeError, match="runtimes are isolated"):
        JaxBackendConfig().on_start(ctx)
    JaxBackendConfig().on_start(
        dataclasses.replace(ctx, world_size=1))     # one worker: no gang
