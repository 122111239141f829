"""Rehearsal of chip_smoke.py's control flow at GPTConfig.tiny() size.

The script itself has no CPU mode (its __main__ fails without a chip);
here its phase functions run with the expected platform passed as "cpu",
so that a mistake in the script costs seconds here and not chip time.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
import ray_tpu
from ray_tpu import serve
from ray_tpu.models import GPTConfig


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()      # a session an earlier module left running
    ray_tpu.init(num_cpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_train_then_serve_phases(cluster, tmp_path):
    cfg = dataclasses.replace(GPTConfig.tiny(), remat=False)
    train = chip_smoke.run_train_phase(
        cfg, platform="cpu", batch=2, seq=64, steps=5,
        out_dir=str(tmp_path))
    assert train["device"]["platform"] == "cpu"
    assert len(train["losses"]) == 6
    assert train["losses"][-1] < train["losses"][0]
    assert train["mosaic_kernels"] == []        # reference path on CPU
    assert train["profile"]["planes"]           # ProfileData read a trace
    assert train["roadmap_facts"]["device_kind"] == "cpu"
    assert not (tmp_path / "trace").exists()
    # The trainer's worker is gone; serving starts in another process.
    served = chip_smoke.run_serve_phase(
        cfg, platform="cpu", max_batch=4, requests=8, max_tokens=16,
        deadline_s=120.0)
    assert served["requests_answered"] >= 9 and served["streamed"] == 1
    (replica,) = served["replicas"]
    assert replica["platform"] == "cpu"
    assert replica["pid"] != train["device"]["pid"]


def test_four_workers_phase_accepts_one_joined_runtime(cluster, tmp_path):
    """On CPU jax.distributed does join the four workers (the chip run
    takes the other branch: isolated runtimes refused at on_start)."""
    out = chip_smoke.run_four_workers_phase(str(tmp_path), platform="cpu")
    assert out["outcome"] == "one runtime"
    assert out["device_count"] == 4 * out["local_device_count"]
    assert out["psum"] == out["expected"]


def test_wrong_platform_fails_in_the_worker(cluster, tmp_path):
    """A chip worker on another platform than expected ends the phase
    with an error naming what the worker saw."""
    with pytest.raises(chip_smoke.SmokeFailure, match="computes on 'cpu'"):
        chip_smoke.run_train_phase(
            GPTConfig.tiny(), platform="gpu", batch=2, seq=64, steps=1,
            out_dir=str(tmp_path))


def test_mosaic_kernel_names_sees_the_three_kernels(monkeypatch):
    """The lowered-text probe the smoke asserts on the chip, checked here
    on a program lowered FOR the tpu platform (no chip needed)."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda *a: attention.flash_attention(
            *a, True, None).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    text = jax.jit(grads).trace(x, x, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert chip_smoke.mosaic_kernel_names(text) == chip_smoke.KERNELS
    assert chip_smoke.mosaic_kernel_names(
        jax.jit(lambda a: a * 2).lower(1.0).as_text()) == set()
