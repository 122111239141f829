"""ops.gated_delta against the gated delta rule as it is defined, token by
token (chipbench/families/olmo_hybrid.py `recurrence`, nothing shared with
ray_tpu): the jax.numpy chunked form and the Pallas kernels in interpreter
mode (RAY_TPU_PALLAS_INTERPRET=1), forward, final state and every
gradient, at small shapes on the CPU in float32 at highest matmul
precision. 1e-4 of the largest value: float32 rounding through a few
hundred dependent steps, and the inverse made by doubling blocks, stay
under 1e-5 here; an all-bfloat16 state is off by 5e-3."""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import olmo_hybrid as reference
from ray_tpu.ops import (gated_delta_plan, gated_delta_reference,
                         gated_delta_rule)
from ray_tpu.ops import gated_delta as gd

TOL = 1e-4
NAMES = ("o", "state", "dq", "dk", "dv", "dg", "dbeta", "dinit")


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["jax", "interpreted"])
def form(request, monkeypatch):
    """Both forms of the rule: the jax.numpy chunked one (what the CPU
    runs) and the Pallas kernels in interpreter mode."""
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


def _inputs(L, decay, beta, with_state, b=2, H=3, K=12, V=20, seed=0):
    """K = 12 and V = 20: no multiple of any tile. `decay` scales g (1e-3:
    hardly any; 30: exp(g) underflows), `beta` is "mid" (0, 2), "low"
    near 0 or "high" near 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed + L), 8)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, L, H, K))) * K ** -0.5
    # "-lean": every key leans one way (k_i . k_j about 0.8), as silu
    # leaves them and more: every entry of A near beta
    lean = 2.0 if beta.endswith("-lean") else 0.0
    beta = beta.removesuffix("-lean")
    k = unit(jax.random.normal(ks[1], (b, L, H, K)) + lean)
    v = jax.random.normal(ks[2], (b, L, H, V))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, L, H)))
    s = jax.nn.sigmoid(jax.random.normal(ks[4], (b, L, H)))
    bt = {"mid": 2 * s, "low": 0.01 * s, "high": 2 - 0.01 * s}[beta]
    init = jax.random.normal(ks[5], (b, H, K, V)) if with_state else None
    weights = (jax.random.normal(ks[6], (b, L, H, V)),
               jax.random.normal(ks[7], (b, H, K, V)))
    return (q, k, v, g, bt, init), weights


def _all_of(fn, args, weights, jitted=False):
    """o, the final state and the gradient of a weighted sum of both by
    every input (the initial state's too where there is one). `jitted`:
    as one program, which the plain forms build faster than op by op (the
    interpreted kernels do not: a jit round them inlines the interpreter)."""
    n = 6 if args[5] is not None else 5

    def scalar(*given):
        o, state = fn(*given, *args[n:])
        return (jnp.sum(o * weights[0]) + jnp.sum(state * weights[1]),
                (o, state))

    run = jax.value_and_grad(scalar, argnums=tuple(range(n)), has_aux=True)
    (_, (o, state)), grads = (jax.jit(run) if jitted else run)(*args[:n])
    return (o, state, *grads)


CASES = [
    (8, 8, 1.0, "mid", False),          # one chunk
    (32, 8, 1.0, "mid", False),         # several: the state crosses over
    (32, 8, 1.0, "mid", True),          # from an initial state
    (64, 16, 1.0, "mid", True),         # chunks of 16: four levels of powers
    (64, 16, 1e-3, "high", True),       # g near 0 and beta near 2
    (64, 16, 1e-3, "high-lean", True),  # the same, keys all leaning one way
    (32, 8, 30.0, "low", True),         # exp(g) underflows, beta near 0
    (48, 16, 30.0, "high", False),      # strong decay, beta near 2
]
IDS = ["one-chunk", "chunks", "initial-state", "chunk-16", "no-decay-beta-2",
       "no-decay-beta-2-lean-keys",
       "strong-decay-beta-0", "strong-decay-beta-2"]


@functools.lru_cache(maxsize=None)
def _wanted(L, decay, beta, with_state):
    """A case's inputs and what the recurrence makes of them, once for
    both forms of the rule (the chunk is the rule's, not the
    recurrence's): 2 x L dependent steps and their gradients, traced and
    built a case and not a case and form."""
    args, weights = _inputs(L, decay, beta, with_state)
    return args, weights, _all_of(reference.recurrence, args, weights,
                                  jitted=True)


@pytest.mark.parametrize("L,chunk,decay,beta,with_state", CASES, ids=IDS)
def test_rule_and_every_gradient_match_the_recurrence(form, L, chunk, decay,
                                                      beta, with_state):
    args, weights, want = _wanted(L, decay, beta, with_state)
    got = _all_of(lambda *a: gated_delta_rule(*a[:5], chunk, *a[5:]), args,
                  weights, jitted=form == "jax")
    for name, g, w in zip(NAMES, got, want, strict=False):
        _close(g, w), name


def _remade(kept_backward, heads=3):
    """The backward as it was before the forward kept T: one head's T made
    again from the same k, g and beta, and the kept one not read. Made as
    the forward makes it, in the head's own place in its 128-lane tile
    (the kernel walks the heads in order) beside copies of itself: what a
    neighbour adds to a head's sums are exact zeros whatever it holds, but
    where in a longer contraction a backend sums a head's own terms is its
    own affair."""
    calls = itertools.count()

    def head_backward(q, k, v, gc, gr, bc, S0, Tm, dO, dS1, chunk):
        h = next(calls) % heads
        first = h - h % gd.heads_per_tile(chunk)
        width = min(first + gd.heads_per_tile(chunk), heads) - first
        rows, cols, D, KK = gd._head_tiles([k], [gc], gr, chunk)
        A = jnp.where(rows > cols, bc * KK * D, 0.0)
        again = gd._unit_lower_inverse(
            jnp.concatenate([A] * width, axis=1), chunk).astype(q.dtype)
        own = slice((h - first) * chunk, (h - first + 1) * chunk)
        return kept_backward(q, k, v, gc, gr, bc, S0, again[:, own], dO, dS1,
                             chunk)
    return head_backward


@pytest.mark.parametrize(
    "L,chunk,decay,beta,with_state,dtype",
    [(*case, jnp.float32) for case in CASES]
    + [(*CASES[3], jnp.bfloat16), (*CASES[5], jnp.bfloat16)],
    ids=IDS + ["chunk-16-bfloat16", "no-decay-beta-2-lean-keys-bfloat16"])
def test_kept_inverse_gives_the_gradients_of_one_made_again(
        monkeypatch, L, chunk, decay, beta, with_state, dtype):
    """The T - I a differentiated forward leaves in HBM is the one the
    backward kernel made itself, rounded once: every gradient is the same
    number, not a close one."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    args, weights = _inputs(L, decay, beta, with_state)
    args = (*(t.astype(dtype) for t in args[:3]), *args[3:])

    def rule(*a):
        o, state = gated_delta_rule(*a[:5], chunk, *a[5:])
        return o.astype(jnp.float32), state

    kept = _all_of(rule, args, weights)
    # the kernel looks `_head_backward` up when it is traced, and the
    # jitted call is traced once a shape
    gd._backward_call.clear_cache()
    monkeypatch.setattr(gd, "_head_backward", _remade(gd._head_backward))
    try:
        again = _all_of(rule, args, weights)
    finally:
        gd._backward_call.clear_cache()
    for name, got, want in zip(NAMES, kept, again, strict=False):
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32)), name
        assert np.isfinite(np.asarray(got, np.float32)).all(), name


def test_call_that_is_not_differentiated_writes_no_inverse(monkeypatch):
    """Prefill and `cached_forward` take the primal call, which no backward
    pass follows: its kernel has no T output, the differentiated one's
    has one, [b, chunks, chunk, heads * chunk]."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    (q, k, v, g, beta, _), _ = _inputs(32, 1.0, "mid", False)
    kept = "tensor<2x4x8x24xf32>"       # 32 / 8 chunks, 3 heads of 8 columns

    def primal(*a):
        return gated_delta_rule(*a, 8)[0]

    assert kept not in jax.jit(primal).lower(q, k, v, g, beta).as_text()
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(primal(*a))))
    assert kept in grad.lower(q, k, v, g, beta).as_text()


def test_reference_pads_a_length_that_is_no_whole_number_of_chunks():
    args, weights = _inputs(27, 1.0, "mid", True)
    want = _all_of(reference.recurrence, args, weights, jitted=True)
    got = _all_of(lambda *a: gated_delta_reference(*a[:5], 8, *a[5:]), args,
                  weights, jitted=True)
    for g, w in zip(got, want, strict=True):
        _close(g, w)
    # and the public rule takes the same path for such a length
    o, state = gated_delta_rule(*args[:5], 8, args[5])
    _close(o, want[0])
    _close(state, want[1])


def test_an_all_bfloat16_state_fails_the_tolerance():
    """The tolerance is tight enough to tell: the same recurrence with
    every value and the state in bfloat16 is off by more than 1e-3, ten
    times the tolerance."""
    args, _ = _inputs(64, 1.0, "mid", True)
    want, _ = reference.recurrence(*args)
    low, _ = reference.recurrence(*(t.astype(jnp.bfloat16) for t in args))
    err = float(jnp.max(jnp.abs(low.astype(jnp.float32) - want)))
    assert err > 10 * TOL * max(1.0, float(jnp.max(jnp.abs(want))))


def test_bfloat16_inputs_come_back_bfloat16_with_a_float32_state(form):
    args, _ = _inputs(32, 1.0, "mid", True)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    o, state = gated_delta_rule(q, k, v, *args[3:5], 8, args[5])
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want, _ = reference.recurrence(
        *(t.astype(jnp.float32) for t in (q, k, v)), *args[3:])
    _close(o, want, tol=3e-2)


def _lower(chunk, lean, key):
    """A strictly lower A: `lean` 0.3 noise in (-0.3, 0.3), else every
    entry near `lean`."""
    noise = jax.random.uniform(jax.random.PRNGKey(key), (chunk, chunk),
                               minval=-0.3, maxval=0.3)
    return jnp.tril(noise if lean == 0.3 else lean * (1.0 + 0.1 * noise), -1)


@pytest.mark.parametrize("chunk,levels,lean,heads", [
    (8, 2, 0.3, 1), (16, 3, 0.3, 1), (64, 5, 0.3, 1), (64, 5, 1.0, 1),
    (64, 5, 2.0, 1), (24, 4, 1.0, 1),
    (8, 2, 0.3, 2), (16, 3, 1.0, 2), (64, 5, 0.3, 2), (64, 5, 1.0, 2),
    (64, 5, 2.0, 2)])
def test_inverse_by_doubling_blocks_holds_float32(chunk, levels, lean, heads):
    """(I + A)^-1 for a strictly lower A against numpy's float64 inverse.
    `lean` 1 and 2: every entry of A near beta (k_i . k_j) for keys that
    all point one way, at beta = 1 and 2, where the powers of A reach 1e17
    and a product of powers has no digit left; 24: no power of two. Two
    heads: two different A side by side, as the forward kernel's tile holds
    them: each half is its own matrix's inverse, and the number the call
    on that matrix alone gives (the other head's terms in a sum are exact
    zeros)."""
    assert gd._inverse_levels(chunk) == levels
    As = [_lower(chunk, lean, chunk + 1000 * h) for h in range(heads)]
    got = gd._unit_lower_inverse(jnp.concatenate(As, axis=1), chunk)
    assert got.shape == (chunk, heads * chunk)
    for h, A in enumerate(As):
        own = got[:, h * chunk:(h + 1) * chunk]
        want = np.linalg.inv(np.eye(chunk) + np.asarray(A, np.float64))
        np.testing.assert_allclose(own + jnp.eye(chunk), want,
                                   atol=2e-5 * np.abs(want).max())
        if heads > 1:
            alone = np.asarray(gd._unit_lower_inverse(A, chunk))
            # XLA's CPU dot has another kernel, and another order of sums,
            # for a contraction of 32 than for one of 16: to rounding there
            np.testing.assert_allclose(
                own, alone, rtol=0,
                atol=1e-6 * np.abs(want).max() if chunk == 16 else 0)


def _kernel_wants(q, k, g, beta, v, init, chunk):
    """What the forward kernel leaves besides o and the final state, from
    the definitions: the state entering each chunk (the reference's final
    state of the chunks before it) and T - I a head and chunk (numpy's
    float64 inverse of I + A), [b, chunks, chunk, heads * chunk]."""
    b, L, H, _ = q.shape
    nc = L // chunk
    # a chunk at a time from the state the chunk before left: the same
    # states as the prefixes' final ones, and one shape for every call
    states = [init]
    for n in range(chunk, L, chunk):
        at = slice(n - chunk, n)
        states.append(gated_delta_reference(
            q[:, at], k[:, at], v[:, at], g[:, at], beta[:, at], chunk,
            states[-1])[1])
    k64, b64 = np.asarray(k, np.float64), np.asarray(beta, np.float64)
    cum = np.asarray(gd._chunk_sums(g, chunk), np.float64)
    T = np.zeros((b, nc, chunk, H * chunk))
    for i, c, h in np.ndindex(b, nc, H):
        at = slice(c * chunk, (c + 1) * chunk)
        kc, G = k64[i, at, h], cum[i, at, h]
        A = np.tril(b64[i, at, h, None] * (kc @ kc.T)
                    * np.exp(np.tril(G[:, None] - G[None, :])), -1)
        T[i, c, :, h * chunk:(h + 1) * chunk] = (
            np.linalg.inv(np.eye(chunk) + A) - np.eye(chunk))
    return jnp.stack(states, axis=1), T


@pytest.mark.parametrize("H,chunk", [(4, 8), (3, 8), (1, 8), (3, 16)],
                         ids=["even", "odd", "one-head", "odd-chunk-16"])
def test_forward_kernel_works_the_heads_in_pairs(monkeypatch, H, chunk):
    """The forward kernel makes T for two heads a tile and works a last
    odd head alone: o, the state entering each chunk, the final state and
    every head's T - I, in its own columns, are the definition's."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert gd.heads_per_tile(chunk) == 2
    (q, k, v, g, beta, init), _ = _inputs(4 * chunk, 1.0, "mid-lean", True,
                                          H=H)
    o, states, final, T = gd._run_forward(q, k, v, g, beta, init, chunk,
                                          keep_inverse=True)
    want_o, want_final = gated_delta_reference(q, k, v, g, beta, chunk, init)
    want_states, want_T = _kernel_wants(q, k, g, beta, v, init, chunk)
    _close(o.reshape(v.shape), want_o)
    _close(final, want_final)
    _close(states, want_states)
    assert T.shape == want_T.shape
    _close(T, want_T, tol=2e-5)


@pytest.mark.parametrize("seq,heads,K,V,chunk", [
    (16384, 30, 96, 192, 64), (256, 3, 12, 20, 8), (2048, 4, 128, 128, 128)])
def test_plan_counts_against_the_loops(seq, heads, K, V, chunk):
    plan = gated_delta_plan(seq, heads, K, V, chunk)
    chunks = seq // chunk
    assert (plan.chunks, plan.grid, plan.heads_per_block) == (
        chunks, (chunks,), heads)
    assert plan.key_tile == -(-K // 128) * 128 >= K
    assert plan.value_tile == -(-V // 128) * 128 >= V
    assert plan.state_bytes == chunks * heads * K * V * 4
    assert plan.kept_bytes == chunks * heads * chunk * chunk * 2
    # the loops: the forward walks a chunk's heads by the 128-lane tile,
    # two products a level and one exponential a tile, none of the
    # inverse's in the backward, and what _tile_forward / _head_backward
    # run a head besides: the forward's eight, five of them again and
    # sixteen more
    levels = 0
    while 2 ** (levels + 1) < chunk:        # block sizes 2, 4, ... < chunk
        levels += 1
    assert plan.heads_per_tile == (2 if 2 * chunk <= 128 else 1)
    tiles = chunks * len(range(0, heads, plan.heads_per_tile))
    assert plan.inverse_matmuls == tiles * 2 * levels
    assert plan.fwd_matmuls == plan.inverse_matmuls + chunks * heads * 8
    assert plan.bwd_matmuls == chunks * heads * (5 + 16)
    assert (plan.fwd_exps, plan.bwd_exps) == (tiles, chunks * heads)
    assert plan.vmem_bytes <= gd.VMEM_LIMIT


def test_plan_refuses_what_the_kernels_cannot_run():
    with pytest.raises(ValueError, match="whole chunks"):
        gated_delta_plan(100, 4, 16, 16, 64)
    with pytest.raises(ValueError, match="do not fit"):
        gated_delta_plan(4096, 256, 128, 256, 64)


def test_kernels_count_the_products_the_plan_says():
    """The dot_generals in one tile's forward and one head's backward,
    traced: the plan's 8 a head + 2 levels a tile, the inverse's at
    Precision.HIGHEST, whether the tile holds one head or two, and in the
    backward, which is handed T - I, 5 of the 8 again, 16 more and none at
    HIGHEST."""
    chunk, K, V = 16, 12, 20
    f32 = jnp.float32

    def dots(fn, *shapes):
        """(products, those of them at Precision.HIGHEST)"""
        # the precision the products name themselves, not this file's
        with jax.default_matmul_precision("default"):
            jaxpr = jax.make_jaxpr(fn)(*(jnp.zeros(s, f32) for s in shapes))
        found = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
        highest = (jax.lax.Precision.HIGHEST,) * 2
        return len(found), sum(e.params["precision"] == highest
                               for e in found)

    head = ((chunk, K), (chunk, K), (chunk, V), (chunk, 1), (chunk, 1),
            (K, V))
    levels = gd._inverse_levels(chunk)
    for n in (1, 2):
        def tile(gr, *heads, n=n):
            return gd._tile_forward(
                [heads[i * len(head):(i + 1) * len(head)] for i in range(n)],
                gr, chunk)
        assert dots(tile, (1, n * chunk), *head * n) == (
            n * gd._FWD_PRODUCTS + 2 * levels, 2 * levels)
    assert dots(lambda *a: gd._head_backward(*a, chunk), *head[:4],
                (1, chunk), *head[4:], (chunk, chunk), (chunk, V),
                (K, V)) == (gd._AGAIN_PRODUCTS + gd._BWD_PRODUCTS, 0)
    for heads, tiles in ((1, 1), (2, 1), (3, 2)):
        plan = gated_delta_plan(4 * chunk, heads, K, V, chunk)
        assert plan.inverse_matmuls == 4 * tiles * 2 * levels
        assert plan.fwd_matmuls == 4 * (heads * gd._FWD_PRODUCTS
                                        + tiles * 2 * levels)
        assert plan.bwd_matmuls == 4 * heads * (gd._AGAIN_PRODUCTS
                                                + gd._BWD_PRODUCTS)
