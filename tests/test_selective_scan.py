"""ops.selective_scan (Mamba-1's scan) against the float32 recurrence of
chipbench/families/sambay.py, one token after another, nothing shared with
ray_tpu. CPU, small shapes, float32 at highest matmul precision, seeded
inputs; the kernels run interpreted (RAY_TPU_PALLAS_INTERPRET=1) beside
their jax.numpy form. Tolerance 1e-4 of the largest value: both forms are
float32 throughout and differ from the recurrence by the order of their
sums alone (readings: 1e-6 and under)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import sambay as reference
from ray_tpu.ops import (selective_scan, selective_scan_plan,
                         selective_scan_reference)
from ray_tpu.ops.attention import VMEM_BUDGET
from ray_tpu.ops.selective_scan import CHUNK

TOL = 1e-4


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["jax", "interpreted"])
def form(request, monkeypatch):
    """Both forms of the scan: the jax.numpy one (what the CPU runs) and
    the Pallas kernels in interpreter mode."""
    if request.param == "interpreted":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    return request.param


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = max(1.0, float(np.max(np.abs(want))))
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _inputs(S, channels, N, with_state, decay=1.0, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + S), 9)
    x = jax.random.normal(ks[0], (b, S, channels))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, channels)) - 1.0)
    A = -decay * jnp.exp(jax.random.uniform(
        ks[2], (channels, N), minval=-3.0, maxval=1.5))
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    D = jax.random.normal(ks[5], (channels,))
    init = jax.random.normal(ks[6], (b, channels, N)) if with_state else None
    weights = (jax.random.normal(ks[7], (b, S, channels)),
               jax.random.normal(ks[8], (b, channels, N)))
    return (x, dt, A, B, C, D, init), weights


CASES = [
    (CHUNK, 128, 4, False, 1.0),        # one chunk
    (3 * CHUNK, 128, 4, False, 1.0),    # several: the state crosses chunks
    (2 * CHUNK, 256, 16, True, 1.0),    # from an initial state, two rows
    (CHUNK + 37, 128, 4, True, 1.0),    # a length that is no whole chunks
    (2 * CHUNK, 1024, 2, True, 1.0),    # a block of 8 rows of channels
    (2 * CHUNK, 128, 4, True, 40.0),    # decays that underflow
]
IDS = ["one-chunk", "chunks", "initial-state", "ragged", "eight-rows",
       "strong-decay"]


@pytest.mark.parametrize("S,channels,N,with_state,decay", CASES, ids=IDS)
def test_scan_gives_the_recurrence(form, S, channels, N, with_state, decay):
    args, _ = _inputs(S, channels, N, with_state, decay)
    m, final = jax.jit(selective_scan)(*args)
    want_m, want_final = reference.recurrence(*args)
    _close(m, want_m)
    _close(final, want_final)
    assert final.dtype == jnp.float32 and m.dtype == args[0].dtype


@pytest.mark.parametrize("S,channels,N,with_state,decay", CASES, ids=IDS)
def test_every_gradient_is_the_recurrences(form, S, channels, N, with_state,
                                           decay):
    args, (wm, ws) = _inputs(S, channels, N, True, decay)

    def scalar(fn):
        def f(*given):
            m, final = fn(*given)
            return jnp.sum(m * wm) + jnp.sum(final * ws)
        return jax.jit(jax.grad(f, argnums=tuple(range(7))))

    got = scalar(selective_scan)(*args)
    want = scalar(reference.recurrence)(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D", "init"), got,
                          want, strict=True):
        assert g.shape == w.shape, name
        _close(g, w)


def test_kernels_take_bfloat16_in_and_give_it_out(form):
    """bfloat16 x, B, C: the scan itself stays float32, so the result is
    the recurrence on the same rounded inputs, rounded once at the end."""
    args, _ = _inputs(2 * CHUNK, 128, 4, True)
    x, dt, A, B, C, D, init = args
    low = (x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
           C.astype(jnp.bfloat16), D, init)
    m, final = jax.jit(selective_scan)(*low)
    want_m, want_final = reference.recurrence(
        *(t.astype(jnp.float32) for t in low))
    assert m.dtype == jnp.bfloat16 and final.dtype == jnp.float32
    _close(m, want_m, 2 ** -7)         # one rounding to bfloat16
    _close(final, want_final)


def test_reference_form_is_the_recurrence_too():
    args, _ = _inputs(50, 96, 3, True)    # no multiple of 128 channels
    m, final = selective_scan_reference(*args)
    want_m, want_final = reference.recurrence(*args)
    _close(m, want_m)
    _close(final, want_final)


def test_plan_counts_what_runs():
    """The cell's shape: 16,384 tokens of 5,120 channels x 16 states."""
    plan = selective_scan_plan(16384, 5120, 16)
    assert (plan.chunk, plan.chunks, plan.block, plan.channel_blocks) == (
        64, 256, (8, 128), 5)
    assert plan.grid == (256, 5) and plan.padded_len == 16384
    assert plan.fwd_exponentials == 16384 * 5120 * 16
    assert plan.bwd_exponentials == 2 * plan.fwd_exponentials
    # one float32 state a chunk, 84 MB; a state a token would be 5.4 GB
    assert plan.state_bytes == 256 * 5120 * 16 * 4
    assert plan.vmem_bytes <= VMEM_BUDGET
    ragged = selective_scan_plan(CHUNK + 37, 128, 4)
    assert (ragged.padded_len, ragged.chunks, ragged.block) == (
        2 * CHUNK, 2, (1, 128))
    with pytest.raises(ValueError, match="128 to a row"):
        selective_scan_plan(64, 96, 4)
