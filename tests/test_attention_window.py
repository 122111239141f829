"""ops.attention under a window and with values wider than keys: the
Pallas kernels in interpreter mode against a dense masked softmax written
here (a query sees itself and the window - 1 positions before it), for
windows below, equal to and above the sub-block and block sizes, with the
swept side resident and on the grid; and `attention_plan`'s window counts
against a brute-force count of the sub-blocks the band touches. float32,
seeded inputs; tolerances as tests/test_models_ops.py's kernel tests.
Where a kernel's own block is several sub-blocks (S = 2,048 and 4,096:
strips of one tile each, the first block's cut by the sequence's start)
bfloat16 in and out as the cells run it, each of the output and the three
gradients against `mha_reference` in float32."""

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


def _check(S, hd, vd, window, scale=0.125, dense=_dense):
    q, k, v, w = _inputs(S, hd, vd)

    def reference(q, k, v):
        return dense(q, k, v, window, scale)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2),
            has_aux=False))(q, k, v)

    got, got_grads = both(lambda q, k, v: flash_attention(
        q, k, v, True, scale, window))
    want, want_grads = both(reference)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    for a, b in zip(got_grads, want_grads, strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)
    out = flash_attention(q, k, v, True, scale, window)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, reference(q, k, v), atol=2e-5, rtol=2e-4)


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
    queries (dK/dV) come in blocks on the grid: a strip's sub-block
    numbers are traced values there and run negative and past the block.
    Swept blocks of two sub-blocks: a window of 100 has every other
    strip's tile inside one, the wider ones none, and those strips go in
    pieces."""
    monkeypatch.setattr(attention, "VMEM_BUDGET", 1_500_000)
    plan = attention_plan(1024, 64, True, jnp.float32, window, 128)
    assert plan.fwd.swept < 1024 and plan.dkv.swept < 1024, plan
    assert plan.fwd.block == plan.fwd.sub == 128
    assert (attention._strip_tile(128, window, plan.fwd.swept)
            == (256 if window == 100 else None))
    _check(1024, 64, 128, window)


_FIELDS = ("block", "swept", "sub", "vmem_bytes", "computed", "masked",
           "skipped", "tiles")


@pytest.mark.parametrize("hd,budget", [(192, 4_000_000), (64, 3_000_000)],
                         ids=["192|128", "64|128"])
def test_causal_wide_keys_with_the_swept_side_on_the_grid(hd, budget,
                                                          monkeypatch):
    """The path a 16k call at 192 | 128 takes (latent attention): causal,
    no window, values not as wide as keys, own blocks of several
    sub-blocks and the swept side in blocks on the grid in all three
    kernels, so `acc`, `m`, `l`, dq, dk and dv are carried from one swept
    block to the next and the diagonal's block lies in one of them."""
    monkeypatch.setattr(attention, "VMEM_BUDGET", budget)
    plan = attention_plan(1024, hd, True, jnp.float32, None, 128)
    for kernel in (plan.fwd, plan.dq, plan.dkv):
        assert kernel.sub < kernel.block <= kernel.swept < 1024, plan
    assert (plan.fwd.block, plan.fwd.swept) == (256, 512)
    assert (plan.dq.block, plan.dq.swept) == (256, 512)
    _check(1024, hd, 128, None, 0.14468,
           lambda q, k, v, window, scale: mha_reference(
               q, k, v, True, scale, window))


@pytest.mark.parametrize("budget,blocks", [
    (2_500_000, [(128, 256), (128, 256), (128, 128)]),
    (5_000_000, [(256, 512), (256, 256), (256, 256)])],
    ids=["4-4-8_grid_blocks", "own_blocks_of_two"])
def test_256_wide_heads_with_the_swept_side_in_four_and_eight_grid_blocks(
        budget, blocks, monkeypatch):
    """The path a 16k call at 256 | 256 takes (GLM-4.7-Flash's latent
    attention: 192 no-rope + 64 rope columns against values of 256): q, k
    and v alike wide, the scale 1/16 an exact one (moved onto q), the swept
    side in FOUR grid blocks in forward and dQ and EIGHT in dK/dV, as the
    16k plan has it, and own blocks of two sub-blocks against four; `acc`,
    `m`, `l`, dq, dk and dv are carried across every one of them."""
    assert attention._scale_is_exact(256 ** -0.5)
    monkeypatch.setattr(attention, "VMEM_BUDGET", budget)
    plan = attention_plan(1024, 256, True, jnp.float32, None, 256)
    assert [(k.block, k.swept) for k in (plan.fwd, plan.dq, plan.dkv)] \
        == blocks
    assert max(1024 // k.swept for k in (plan.fwd, plan.dq, plan.dkv)) >= 4
    _check(1024, 256, 256, None, 256 ** -0.5,
           lambda q, k, v, window, scale: mha_reference(
               q, k, v, True, scale, window))


def test_the_plan_at_16k_and_256_wide_heads_is_pinned():
    """`attention_plan(16384, 256, v_dim=256)`: forward and dQ hold 1,024
    queries against K and V in four grid blocks of 4,096, dK/dV 1,024 keys
    against queries in four of 4,096 (eight of 2,048 while lse and delta
    travelled padded to 128 lanes: 2 MiB a block of them at 2,048); 25.2,
    26.3 and 27.8 MB of the 32 MiB; the same 184 tiles a head as every
    other 16k shape."""
    plan = attention_plan(16384, 256, True, jnp.bfloat16, None, 256)
    assert [tuple(getattr(kernel, f) for f in _FIELDS)
            for kernel in (plan.fwd, plan.dq, plan.dkv)] == [
        (1024, 4096, 256, 25231360, 2080, 64, 2016, 184),
        (1024, 4096, 256, 26345472, 2080, 64, 2016, 184),
        (1024, 4096, 256, 27787264, 2080, 64, 2016, 184)]
    assert [16384 // k.swept for k in (plan.fwd, plan.dq, plan.dkv)] \
        == [4, 4, 4]
    # v as wide as q and k: naming v's width changes nothing
    assert plan == attention_plan(16384, 256)


def _relative_errors(S, hd, vd, window, heads=1):
    """|kernel - float32| / |float32| of the output, dq, dk and dv, the
    kernels on bfloat16 operands and `mha_reference` on the same values
    in float32."""
    ks = jax.random.split(jax.random.PRNGKey(S + (window or 0)), 4)
    q, k = (jax.random.normal(key, (1, heads, S, hd), jnp.bfloat16)
            for key in ks[:2])
    v, g = (jax.random.normal(key, (1, heads, S, vd), jnp.bfloat16)
            for key in ks[2:])
    scale = hd ** -0.5

    def f32(x):
        return x.astype(jnp.float32)
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, True, scale, window),
                       q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: mha_reference(*a, True, scale, window), f32(q), f32(k),
        f32(v))
    got = (out, *vjp(g))
    assert all(a.dtype == jnp.bfloat16 for a in got)
    return [float(jnp.linalg.norm(f32(a) - b) / jnp.linalg.norm(b))
            for a, b in zip(got, (want, *want_vjp(f32(g))), strict=True)]


# One rounding of bfloat16 is 2 ** -9 = 0.002 of a value on average; the
# kernels read 0.0021 to 0.0026 here, a fault of structure 0.1 and more.
BF16_LIMIT = 0.005


# Own blocks of 1,024 in sub-blocks of 256: a window under a sub-block
# (2 and 100: tiles of two), of one, of two (the cell's: tiles of three), between two
# and three (the far edge crosses two sub-blocks), of four, one short of
# and beyond the sequence (every strip cut by its start, in pieces).
@pytest.mark.parametrize("S,window", [
    (2048, 2), (2048, 100), (2048, 256), (2048, 512), (2048, 700),
    (2048, 1024), (2048, 2047), (2048, 5000), (4096, 512), (4096, 700),
    (4096, 1024)])
def test_large_own_blocks_in_bfloat16_against_float32(S, window):
    plan = attention_plan(S, 64, True, jnp.bfloat16, window, 128)
    for kernel in (plan.fwd, plan.dq, plan.dkv):
        assert (kernel.block, kernel.swept, kernel.sub) == (1024, S, 256)
    # the first block's strips are cut by the sequence's start unless the
    # window is under a sub-block and one: tiles < strips only if none is
    assert plan.fwd.tiles >= S // 256
    errors = _relative_errors(S, 64, 128, window)
    assert max(errors) < BF16_LIMIT, errors


def test_large_own_blocks_at_head_dim_128():
    """The next window/global model's shape (window 1,024 at head_dim 128,
    PERF.md §7): a scale that is no power of two stays on the scores."""
    errors = _relative_errors(4096, 128, 128, 1024)
    assert max(errors) < BF16_LIMIT, errors


@pytest.mark.parametrize("S,window,dtype,budget,whole", [
    (4096, 256, jnp.bfloat16, 3_250_000, (True, True, True)),
    (4096, 512, jnp.bfloat16, 4_250_000, (False, False, False)),
    (2048, 100, jnp.float32, 5_000_000, (True, True, True))])
def test_strips_across_swept_blocks(S, window, dtype, budget, whole,
                                    monkeypatch):
    """Own blocks of several sub-blocks with the swept side on the grid:
    a strip whose tile lies inside a swept block works it whole, one that
    lies across two works its pieces in both; against swept blocks of two
    sub-blocks (dK/dV's, and at 4,096 all three kernels') every other
    strip does, and every strip where the tile is three sub-blocks wide
    (a window of 512)."""
    monkeypatch.setattr(attention, "VMEM_BUDGET", budget)
    plan = attention_plan(S, 64, True, dtype, window, 128)
    for kernel, tile in zip((plan.fwd, plan.dq, plan.dkv), whole,
                            strict=True):
        assert kernel.swept < S and kernel.block > kernel.sub, plan
        assert (attention._strip_tile(kernel.sub, window, kernel.swept)
                is not None) == tile
        assert kernel.tiles > S // kernel.sub       # some strip in pieces
    assert plan.dkv.swept == 2 * plan.dkv.sub
    if dtype == jnp.float32:
        _check(S, 64, 128, window)
    else:
        errors = _relative_errors(S, 64, 128, window)
        assert max(errors) < BF16_LIMIT, errors


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
    """(touched, crossed) sub-blocks of the S x S square, as boolean
    [query sub-block, key sub-block] maps: those holding a visible (query,
    key) pair, and of them those holding an invisible one too. By every
    pair."""
    ahead = np.arange(S)[:, None] - np.arange(S)[None, :]
    seen = (ahead >= 0) & (ahead < window)
    tiles = seen.reshape(S // sub, sub, S // sub, sub)
    touched = tiles.any(axis=(1, 3))
    whole = tiles.all(axis=(1, 3))
    return touched, touched & ~whole


def _brute_tiles(touched, crossed, kernel, window, mirrored):
    """Tiles a head's `touched` sub-blocks are worked in, counted from the
    maps and the plan's sizes alone: a strip (a row of the map; a column in
    dK/dV) whose touched sub-blocks in a swept block are all the band can
    touch is one tile, if that tile is allowed (`_strip_tile`); any other
    goes sub-block by sub-block, but the ones wholly inside the band in
    chunks of `wide` where the window holds one."""
    if mirrored:
        touched, crossed = touched.T, crossed.T
    n = kernel.swept // kernel.sub
    reach = (window + kernel.sub - 2) // kernel.sub
    wide = min(1024, kernel.swept) // kernel.sub
    whole_tile = attention._strip_tile(kernel.sub, window, kernel.swept)
    tiles = 0
    for strip in range(touched.shape[0]):
        for other in range(0, touched.shape[1], n):
            seen = touched[strip, other:other + n]
            if whole_tile and seen.sum() == reach + 1:
                tiles += 1
                continue
            inside = int((seen & ~crossed[strip, other:other + n]).sum())
            if window // kernel.sub - 1 < wide:     # no chunk fits the band
                tiles += int(seen.sum())
            else:
                tiles += int(seen.sum()) - inside + inside // wide \
                    + inside % wide
    return tiles


@pytest.mark.parametrize("budget", [None, 1_500_000],
                         ids=["resident", "grid"])
@pytest.mark.parametrize("S,window", [
    (512, 1), (512, 64), (512, 128), (512, 129), (512, 200), (512, 256),
    (512, 511), (512, 512), (512, 4096), (1024, 300), (2048, 512),
    (2048, 513), (4096, 1024), (4096, 2000), (4096, 4096)])
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
        # the causal path's sizes: the largest block up to 1,024 that
        # tiles the swept side
        assert kernel.block % kernel.sub == 0
        assert kernel.block == min(1024, kernel.swept) or budget
        assert kernel.computed == touched.sum()    # nothing outside the band
        assert kernel.skipped == n - touched.sum()
        # every sub-block the band's edges cross is masked; one the far
        # edge only just reaches whole may be masked besides (the rule
        # works whole sub-blocks: `reach` rounds up), and a strip in
        # pieces masks each piece it does not take in a chunk
        assert crossed.sum() <= kernel.masked <= kernel.computed
        assert kernel.tiles == _brute_tiles(
            touched, crossed, kernel, window, kernel is plan.dkv)
        assert S // kernel.sub <= kernel.tiles <= kernel.computed
    assert plan.executed_share == touched.sum() / n


def test_the_cells_windowed_layers_compute_a_sixteenth_of_the_triangle():
    band = attention_plan(16384, 64, True, jnp.bfloat16, 512, 128)
    causal = attention_plan(16384, 64, True, jnp.bfloat16, None, 128)
    assert band.fwd.computed == 189 and causal.fwd.computed == 2080
    # 16 programs a head of four strips, a strip one tile of three
    # sub-blocks (768 keys: far edge and diagonal masked, 126 in all, one
    # between whole); the sequence's first two strips have no three to see
    # and go in 1 + 2 pieces, each masked: 65 tiles where blocks of one
    # sub-block made 189 of 64 programs
    for kernel in (band.fwd, band.dq):
        assert (kernel.block, kernel.swept, kernel.sub) == (1024, 16384, 256)
        assert (kernel.tiles, kernel.masked) == (65, 127)
    assert attention._strip_tile(256, 512, 16384) == 768
    # dK/dV: the queries resident too (lse and delta are lane rows of four
    # bytes a query; padded to 128 lanes they took 8 MiB at 4,096 queries,
    # the queries came in two grid blocks of 8,192, and the two key strips
    # across the boundary went in 3 pieces each: 69 tiles), so only the
    # last two strips go in pieces, 2 + 1
    assert (band.dkv.block, band.dkv.swept) == (1024, 16384)
    assert (band.dkv.tiles, band.dkv.masked) == (65, 127)
    # the full layers' dK/dV: its own block sized first, as the forward's,
    # and the queries whole
    assert (causal.dkv.block, causal.dkv.swept) == (1024, 16384)
    assert causal.dkv.tiles == causal.fwd.tiles == 184
    for kernel in (band.fwd, band.dq, band.dkv):
        assert kernel.tiles <= 16384 // 256 + 8
        assert kernel.vmem_bytes <= attention.VMEM_BUDGET
    # and an unwindowed call's plan is what it was before windows existed
    assert attention_plan(16384, 64) == attention_plan(
        16384, 64, True, jnp.bfloat16, None, None)


@pytest.mark.parametrize("args,want", [
    ((1024, 64), [(1024, 1024, 128, 15007744, 36, 8, 28, 8),
                  (1024, 1024, 128, 15335424, 36, 8, 28, 8),
                  (1024, 1024, 128, 14811136, 36, 8, 28, 8)]),
    ((4096, 128), [(1024, 4096, 256, 19464192, 136, 16, 120, 22),
                   (1024, 4096, 256, 20054016, 136, 16, 120, 22),
                   (1024, 4096, 256, 20447232, 136, 16, 120, 22)]),
    ((16384, 64), [(1024, 16384, 256, 22872064, 2080, 64, 2016, 184),
                   (1024, 16384, 256, 23199744, 2080, 64, 2016, 184),
                   (1024, 16384, 256, 24641536, 2080, 64, 2016, 184)]),
    ((16384, 64, True, jnp.bfloat16, None, 128),
     [(1024, 16384, 256, 27590656, 2080, 64, 2016, 184),
      (1024, 16384, 256, 27656192, 2080, 64, 2016, 184),
      (1024, 16384, 256, 29622272, 2080, 64, 2016, 184)]),
    ((16384, 128), [(1024, 16384, 256, 32047104, 2080, 64, 2016, 184),
                    (1024, 16384, 256, 32636928, 2080, 64, 2016, 184),
                    (1024, 8192, 256, 25165824, 2080, 64, 2016, 184)]),
    ((8192, 64), [(1024, 8192, 256, 18677760, 528, 32, 496, 60),
                  (1024, 8192, 256, 19005440, 528, 32, 496, 60),
                  (1024, 8192, 256, 19398656, 528, 32, 496, 60)]),
    ((16384, 192, True, jnp.bfloat16, None, 128),
     [(1024, 8192, 256, 26017792, 2080, 64, 2016, 184),
      (1024, 8192, 256, 27131904, 2080, 64, 2016, 184),
      (1024, 8192, 256, 28049408, 2080, 64, 2016, 184)])],
    ids=["1024x64", "4096x128", "16384x64", "16384x64|128", "16384x128",
         "8192x64", "16384x192|128"])
def test_unwindowed_plans_are_what_they_were(args, want):
    """Every window=None plan the nine cells run, field for field, beside
    the count of tiles. Blocks, sub-blocks and counts of forward and dQ
    are PR 54's (a kernel's own block sized before its swept side: 1,024
    queries against K and V whole, in two grid blocks of 8,192 at 192 |
    128). Since PR 58 lse and delta are lane rows of four bytes a position
    (32 in the estimate: a [1, n] block fills eight sublanes) where they
    were padded to 128 lanes, 512 bytes: every estimate is smaller, and
    dK/dV, which holds the swept queries' two rows, keeps the queries
    whole at 64 | 64, 64 | 128 and 8k and in two grid blocks of 8,192 at
    128 | 128 and 192 | 128 where it swept them in blocks of 4,096."""
    plan = attention_plan(*args)
    assert (plan.seq_len, plan.head_dim, plan.causal, plan.window,
            plan.vmem_budget) == (*args[:2], True, None, 32 * 2 ** 20)
    got = [tuple(getattr(kernel, f) for f in _FIELDS)
           for kernel in (plan.fwd, plan.dq, plan.dkv)]
    assert got == want


def test_a_window_is_causal_and_positive():
    with pytest.raises(ValueError, match="causal"):
        attention_plan(512, 64, False, jnp.float32, 128)
    with pytest.raises(ValueError, match="at least 1"):
        attention_plan(512, 64, True, jnp.float32, 0)


# The kernels' two row residuals, lse and delta, are lane rows of four
# bytes a position: [batch, heads, 1, S] float32, q's leading axes and rank.
# (S, head_dim, v_dim, window): one block of eight strips; four blocks of
# 1,024 with the sequence resident; a band with values twice as wide; latent
# attention's 192 | 128.
ROW_CASES = pytest.mark.parametrize("S,hd,vd,window", [
    (1024, 64, 64, None), (4096, 128, 128, None), (2048, 64, 128, 512),
    (2048, 192, 128, None)],
    ids=["1024x64", "4096x128", "2048x64|128w512", "2048x192|128"])


def _pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, those of its calls too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@ROW_CASES
def test_lse_is_a_lane_row_a_head_and_the_backward_reads_it(S, hd, vd,
                                                            window):
    """`_flash_forward` hands back the log-sum-exp of each query's visible
    scores as [batch, heads, 1, S] float32, and `_flash_backward` through
    that value (and a delta of the same form) gives `mha_reference`'s
    gradients."""
    heads = 1 if S == 4096 else 2
    q, k, v, g = (t[:, :heads] for t in _inputs(S, hd, vd))
    scale = hd ** -0.5
    plan = attention_plan(S, hd, True, jnp.float32, window, vd)
    out, lse = attention._flash_forward(q, k, v, True, scale, plan.fwd,
                                        True, window)
    assert lse.shape == (1, heads, 1, S) and lse.dtype == jnp.float32
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = (ahead >= 0) if window is None else (ahead >= 0) & (ahead < window)
    want = jax.nn.logsumexp(jnp.where(seen, sc, -jnp.inf), -1)
    np.testing.assert_allclose(lse[:, :, 0], want, atol=2e-5, rtol=2e-5)
    ref, vjp = jax.vjp(lambda *a: mha_reference(*a, True, scale, window),
                       q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-4)
    grads = attention._flash_backward(q, k, v, out, lse, g, True, scale,
                                      plan.dq, plan.dkv, window)
    for a, b in zip(grads, vjp(g), strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


@ROW_CASES
def test_the_kernels_results_and_operands_hold_no_padded_row(S, hd, vd,
                                                             window):
    """What the three kernels are called with: a forward that saves lse
    has [bh, 1, S] float32 as its second result, one that does not
    (`save_lse=False`, the serving path) has ONE result, and both backward
    kernels read lse and delta as [bh, 1, S] behind q, k, v and dO: no
    operand or result is a row padded to [.., S, 128]."""
    q, k, v, g = _inputs(S, hd, vd)
    scale = hd ** -0.5
    plan = attention_plan(S, hd, True, jnp.float32, window, vd)

    def results(save_lse):
        call, = _pallas_calls(jax.make_jaxpr(
            lambda q, k, v: attention._flash_forward(
                q, k, v, True, scale, plan.fwd, save_lse, window))(
                    q, k, v).jaxpr)
        return [(o.aval.shape, o.aval.dtype) for o in call.outvars]
    row = ((2, 1, S), jnp.float32)
    assert results(True) == [((2, S, vd), q.dtype), row]
    assert results(False) == [((2, S, vd), q.dtype)]
    lse = jnp.zeros((1, 2, 1, S), jnp.float32)
    calls = list(_pallas_calls(jax.make_jaxpr(
        lambda *a: attention._flash_backward(
            *a, True, scale, plan.dq, plan.dkv, window))(
                q, k, v, v, lse, g).jaxpr))
    assert len(calls) == 2
    wide, narrow = ((2, S, hd), q.dtype), ((2, S, vd), q.dtype)
    for call in calls:
        assert [(x.aval.shape, x.aval.dtype) for x in call.invars] == [
            wide, wide, narrow, narrow, row, row]


# ---------------------------------------------------------------------------
# under a selection (PR 60): a [batch, seq, seq] int8 mask in all three
# ---------------------------------------------------------------------------
def test_the_plan_under_a_selection_at_16k_and_128_is_pinned():
    """`attention_plan(16384, 128, selected=2048)`, field for field: the
    kernels' own blocks stay 1,024, and a program's [1024, swept] tile of
    the selection, twice in VMEM, brings the swept side to FOUR grid blocks
    of 4,096 in all three (K and V whole and dK/dV's queries in two of
    8,192 without one: `test_unwindowed_plans_are_what_they_were`); the
    counts are the causal triangle's, since every tile is computed and
    masked; of 136,314,880 pairs a head computed the softmax runs over
    31,458,304, sum_t min(t + 1, 2048)."""
    plan = attention_plan(16384, 128, selected=2048)
    assert (plan.seq_len, plan.head_dim, plan.causal, plan.window,
            plan.selected, plan.vmem_budget) == (
        16384, 128, True, None, 2048, 32 * 2 ** 20)
    assert [tuple(getattr(kernel, f) for f in _FIELDS)
            for kernel in (plan.fwd, plan.dq, plan.dkv)] == [
        (1024, 4096, 256, 27852800, 2080, 64, 2016, 184),
        (1024, 4096, 256, 28442624, 2080, 64, 2016, 184),
        (1024, 4096, 256, 28835840, 2080, 64, 2016, 184)]
    assert plan.executed_pairs == 2080 * 256 * 256 == 136_314_880
    assert plan.required_pairs == 31_458_304
    assert plan.executed_share == attention_plan(16384, 128).executed_share
    # without one the same fields are what they were, and the two counts
    # are the triangle's and the band's
    dense = attention_plan(16384, 128)
    assert dense.selected is None
    assert dense.required_pairs == 16384 * 16385 // 2
    assert attention_plan(2048, 64, window=512).required_pairs \
        == 512 * 513 // 2 + (2048 - 512) * 512
    for bad in (dict(causal=False), dict(window=128)):
        with pytest.raises(ValueError, match="selection is causal"):
            attention_plan(1024, 64, selected=256, **bad)


@pytest.mark.parametrize("S,budget", [(512, None), (2048, None),
                                      (2048, 3 * 2 ** 20)])
def test_the_three_kernels_under_a_random_selection(S, budget, monkeypatch):
    """Forward, dQ and dK/dV in interpret mode with one [S, S] int8 selection
    for both heads of two batch rows (a third of the pairs, every query's
    own key among them or not) against `mha_reference` with the same mask:
    one block, several sub-blocks, and the swept side on the grid, where a
    program's first tiles can hold no selected key at all."""
    if budget is not None:
        monkeypatch.setattr(attention, "VMEM_BUDGET", budget)
    ks = jax.random.split(jax.random.PRNGKey(S), 5)
    q, k, v, w = (jax.random.normal(kk, (2, 2, S, 64)) for kk in ks[:4])
    selected = (jax.random.uniform(ks[4], (2, S, S)) < 0.33).astype(jnp.int8)
    # every query sees its first key, so no softmax is empty
    selected = selected.at[:, :, 0].set(1)
    plan = attention_plan(S, 64, True, jnp.float32, None, 64, S)
    if budget is not None:
        assert plan.fwd.swept < S and plan.dkv.swept < S

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w),
            argnums=(0, 1, 2)))(q, k, v)

    got, got_grads = both(lambda q, k, v: flash_attention(
        q, k, v, True, 0.125, None, selected))
    out, lse = attention.attention_and_lse(q, k, v, 0.125, selected)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "0")
    want, want_grads = both(lambda q, k, v: mha_reference(
        q, k, v, True, 0.125, None, selected))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    for a, b in zip(got_grads, want_grads, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(out, mha_reference(
        q, k, v, True, 0.125, None, selected), atol=2e-5, rtol=2e-4)
    assert lse.shape == (2, 2, 1, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        lse, attention._reference_lse(q, k, 0.125, selected), atol=1e-4)
    # and it differs from the unselected call: the mask is applied
    assert float(jnp.max(jnp.abs(out - flash_attention(
        q, k, v, True, 0.125)))) > 0.1
