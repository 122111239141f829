"""ops.attention under a window and with values wider than keys: the
Pallas kernels in interpreter mode against a dense masked softmax written
here (a query sees itself and the window - 1 positions before it), for
windows below, equal to and above the sub-block and block sizes, with the
swept side resident and on the grid; and `attention_plan`'s window counts
against a brute-force count of the sub-blocks the band touches. float32,
seeded inputs; tolerances as tests/test_models_ops.py's kernel tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (attention_plan, flash_attention,
                                   mha_reference)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _dense(q, k, v, window, scale):
    """The definition, nothing shared with ops.attention."""
    S = q.shape[-2]
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen &= ahead < window
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _inputs(S, hd, vd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + S), 4)
    return (jax.random.normal(ks[0], (1, 2, S, hd)),
            jax.random.normal(ks[1], (1, 2, S, hd)),
            jax.random.normal(ks[2], (1, 2, S, vd)),
            jax.random.normal(ks[3], (1, 2, S, vd)))


def _check(S, hd, vd, window, scale=0.125):
    q, k, v, w = _inputs(S, hd, vd)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2),
            has_aux=False))(q, k, v)

    got, got_grads = both(lambda q, k, v: flash_attention(
        q, k, v, True, scale, window))
    want, want_grads = both(lambda q, k, v: _dense(q, k, v, window, scale))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    for a, b in zip(got_grads, want_grads, strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)
    out = flash_attention(q, k, v, True, scale, window)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, _dense(q, k, v, window, scale),
                               atol=2e-5, rtol=2e-4)


# S = 512 runs sub-blocks of 128 (a windowed kernel's own block), S = 2048
# of 256: windows of one position, under a sub-block, a sub-block, between
# two, several, one short of the sequence, the sequence and beyond it.
@pytest.mark.parametrize("S,window", [
    (512, 1), (512, 64), (512, 128), (512, 200), (512, 256), (512, 511),
    (512, 512), (512, 1000), (1280, 128), (2048, 512), (2048, 300)])
def test_window_matches_the_dense_masked_softmax(S, window):
    _check(S, 64, 64, window)


@pytest.mark.parametrize("S,window", [(512, None), (512, 128), (2048, 512)])
def test_values_twice_as_wide_as_keys(S, window):
    """Differential attention's call: one score map of 64-wide q and k
    times a pair's 128-wide values."""
    _check(S, 64, 128, window)


@pytest.mark.parametrize("window", [100, 256, 700])
def test_window_with_the_swept_side_on_the_grid(window, monkeypatch):
    """Under a budget whole sequences do not fit, keys (forward, dQ) and
    queries (dK/dV) come in blocks on the grid: the band's chunk numbers
    are traced values there and run negative and past the block."""
    monkeypatch.setattr(attention, "VMEM_BUDGET", 1_500_000)
    plan = attention_plan(1024, 64, True, jnp.float32, window, 128)
    assert plan.fwd.swept < 1024 and plan.dkv.swept < 1024, plan
    assert plan.fwd.block == plan.fwd.sub == 128
    _check(1024, 64, 128, window)


def test_reference_path_takes_the_window_too(monkeypatch):
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    q, k, v, _ = _inputs(96, 16, 32)          # not a kernel shape
    for window in (None, 1, 17, 200):
        np.testing.assert_allclose(
            flash_attention(q, k, v, True, 0.25, window),
            _dense(q, k, v, window, 0.25), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(
            mha_reference(q, k, v, True, 0.25, window),
            _dense(q, k, v, window, 0.25), atol=1e-5, rtol=1e-5)


def _brute(S, sub, window):
    """(touched, crossed) sub-blocks of the S x S square: those holding a
    visible (query, key) pair, and of them those holding an invisible one
    too. By every pair."""
    ahead = np.arange(S)[:, None] - np.arange(S)[None, :]
    seen = (ahead >= 0) & (ahead < window)
    tiles = seen.reshape(S // sub, sub, S // sub, sub)
    touched = tiles.any(axis=(1, 3))
    whole = tiles.all(axis=(1, 3))
    return int(touched.sum()), int((touched & ~whole).sum())


@pytest.mark.parametrize("budget", [None, 1_500_000],
                         ids=["resident", "grid"])
@pytest.mark.parametrize("S,window", [
    (512, 1), (512, 64), (512, 128), (512, 129), (512, 200), (512, 256),
    (512, 511), (512, 512), (512, 4096), (1024, 300), (2048, 512),
    (2048, 513), (4096, 1024)])
def test_plan_counts_the_band_as_a_brute_force_count_does(S, window, budget,
                                                          monkeypatch):
    if budget is not None:     # tiles of 256 x 256 need more than of 128
        monkeypatch.setattr(attention, "VMEM_BUDGET",
                            budget if S <= 1024 else 3 * budget)
    plan = attention_plan(S, 64, True, jnp.float32, window, 128)
    assert (plan.dkv.swept < S) == (budget is not None)
    touched, crossed = _brute(S, plan.fwd.sub, window)
    n = (S // plan.fwd.sub) ** 2
    assert plan.window == window
    for kernel in (plan.fwd, plan.dq, plan.dkv):
        assert kernel.block == kernel.sub
        assert kernel.computed == touched          # nothing outside the band
        assert kernel.skipped == n - touched
        # every sub-block the band's edges cross is masked; one the far
        # edge only just reaches whole may be masked besides (the rule
        # works whole chunks: `reach` rounds up)
        assert crossed <= kernel.masked <= kernel.computed
    assert plan.executed_share == touched / n


def test_the_cells_windowed_layers_compute_a_sixteenth_of_the_triangle():
    band = attention_plan(16384, 64, True, jnp.bfloat16, 512, 128)
    causal = attention_plan(16384, 64, True, jnp.bfloat16, None, 128)
    assert band.fwd.computed == 189 and causal.fwd.computed == 2080
    assert band.fwd.masked == 126
    # and an unwindowed call's plan is what it was before windows existed
    assert attention_plan(16384, 64) == attention_plan(
        16384, 64, True, jnp.bfloat16, None, None)


def test_a_window_is_causal_and_positive():
    with pytest.raises(ValueError, match="causal"):
        attention_plan(512, 64, False, jnp.float32, 128)
    with pytest.raises(ValueError, match="at least 1"):
        attention_plan(512, 64, True, jnp.float32, 0)
