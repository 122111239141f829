"""The keye_vl2 family (Keye-VL-2.0-30B-A3B's decoder: attention over a
learned sparse subset of positions, a softmax router's held share) against
chipbench/families/keye_vl2.py's plain float32 reference, tiny and seeded
on the CPU: logits, the summed loss L and every gradient, the two disjoint
sets of leaves one step trains, the selection, the cache path, the shares
of the expert layer, the indexer's kernels in interpret mode, the hand-made
counts, every planted fault, and what a call without a selection lowers
to."""

import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import keye_vl2 as family
from ray_tpu.models import decoder
from ray_tpu.models.keye_vl2 import (INDEXER_LEAVES, KeyeVL2Config,
                                     keye_vl2_forward, keye_vl2_init,
                                     keye_vl2_loss_and_counters)
from ray_tpu.ops import attention, sparse_index
from ray_tpu.parallel.moe import dropless_moe_layer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dataclasses.replace(KeyeVL2Config.tiny(), dtype=jnp.float32)
SEQ = 128


def _batch(cfg, seq=SEQ, rows=2):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0,
                                cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, 1)


@pytest.fixture(scope="module")
def both():
    """(params, the program's (loss, counters, gradients), the reference's
    (parts, gradients)) on one seeded batch, in float32."""
    params = keye_vl2_init(jax.random.PRNGKey(3), CFG)
    batch = _batch(CFG)
    with jax.default_matmul_precision("highest"):
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            lambda p: keye_vl2_loss_and_counters(p, batch, CFG),
            has_aux=True))(params)

        def reference(p):
            parts = family.reference_parts(p, *batch, CFG)
            return parts["loss"], parts
        (_, parts), want = jax.jit(jax.value_and_grad(
            reference, has_aux=True))(params)
    return params, (loss, counters, grads), (parts, want)


def _close(got, want, tol=1e-4):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    return float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("part", ["loss", "loss_ce", "balance_loss",
                                  "index_loss"])
def test_the_loss_and_its_three_terms_are_the_references(both, part):
    _, (loss, counters, _), (parts, _) = both
    got = loss if part == "loss" else counters[part]
    assert _close(got, parts[part]), (part, got, parts[part])
    # L is the sum it is said to be
    total = counters["loss_ce"] + 0.001 * counters["balance_loss"] \
        + jnp.sum(counters["index_loss"])
    assert _close(loss, total, 1e-6)
    assert counters["index_loss"].shape == (CFG.n_layers,)
    assert bool(jnp.all(counters["index_loss"] > 0))


def test_logits_are_the_references(both):
    params = both[0]
    tokens, _ = _batch(CFG)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: keye_vl2_forward(p, tokens, CFG))(params)
        want = jax.jit(
            lambda p: family.reference_logits(p, tokens, CFG))(params)
    assert got.shape == (2, SEQ, CFG.vocab_size)
    assert _close(got, want)


LEAVES = sorted(jax.eval_shape(CFG.init, jax.random.PRNGKey(0))["layers"][0])


@pytest.mark.parametrize("leaf", ["embed", "head", "lnf", *LEAVES])
def test_every_gradient_is_the_references(both, leaf):
    _, (_, _, got), (_, want) = both
    if leaf in ("embed", "head", "lnf"):
        assert _close(got[leaf], want[leaf]), leaf
        return
    for i in range(CFG.n_layers):
        g, w = got["layers"][i][leaf], want["layers"][i][leaf]
        assert float(jnp.max(jnp.abs(w))) > 0, (leaf, i)
        assert _close(g, w), (leaf, i)


@pytest.mark.parametrize("term", ["loss_ce", "index_loss"])
def test_one_step_trains_two_disjoint_sets_of_leaves(both, term):
    """The cross entropy (and the balance loss) reach no indexer leaf; L_I
    reaches the indexer's five and nothing else."""
    params = both[0]
    batch = _batch(CFG)

    def one_term(p):
        counters = keye_vl2_loss_and_counters(p, batch, CFG)[1]
        return jnp.sum(counters[term]) + (
            counters["balance_loss"] if term == "loss_ce" else 0.0)

    grads = jax.jit(jax.grad(one_term))(params)
    for name in ("embed", "head", "lnf"):
        assert bool(jnp.any(grads[name] != 0)) == (term == "loss_ce"), name
    for layer in grads["layers"]:
        for name, g in layer.items():
            reached = bool(jnp.any(g != 0))
            mine = (name in INDEXER_LEAVES) == (term == "index_loss")
            assert reached == mine, (term, name)


def test_kept_or_made_again_is_the_same_step_bit_for_bit(monkeypatch):
    """A two-layer sparse stack under remat, the kernels interpreted:
    with room for everything (`step_memory`'s forced capacity) each block
    keeps q, what its q, k and v are made from and the branch's output,
    with none the base set alone, and the loss, L_I and every gradient
    are equal to the bit. Keeping a value changes no arithmetic."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(CFG, n_layers=2)
    params = keye_vl2_init(jax.random.PRNGKey(5), cfg)
    batch = _batch(cfg)
    asked = []
    real = decoder.remat_plan
    monkeypatch.setattr(decoder, "remat_plan", lambda *a, **k: asked.append(
        real(*a, **k)) or asked[-1])

    def step(capacity):
        with attention.step_memory(state_bytes=0, capacity=capacity):
            return jax.jit(jax.value_and_grad(
                lambda p: keye_vl2_loss_and_counters(p, batch, cfg),
                has_aux=True))(params)

    (loss, counters), grads = step(1 << 40)
    (base_loss, base_counters), base = step(0)
    names = ("attention_k_heads", "attention_kv_proj", "attention_q_proj",
             "flash_attention_q", "moe_choice", "sparse_attention_out")
    assert [plan.extras for plan in asked] == [(names,) * 2, ((),) * 2]
    assert float(loss) == float(base_loss)
    np.testing.assert_array_equal(counters["index_loss"],
                                  base_counters["index_loss"])
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(base)):
        np.testing.assert_array_equal(got, want)
        assert float(jnp.max(jnp.abs(want))) > 0


def _selections(cfg, params, tokens):
    """(the program's selection of layer 0, the reference's)."""
    lay, dec = params["layers"][0], cfg.decoder()
    x = params["embed"][tokens]
    y = decoder._norm(x, lay, "ln1", dec.norm_eps)
    q, k, w = decoder._index_heads(decoder._detached(y), lay, dec, None)
    got = decoder.select(decoder.index_scores(q, k, w), dec.sparse_topk)[0]
    want = family.selection(
        family.index_scores(*family.index_inputs(y, lay, cfg)),
        cfg.index_topk)
    return got != 0, want


@pytest.mark.parametrize("topk", [48, 1000])
def test_the_selection_is_the_references(both, topk):
    """At a topk under the sequence the same keys as the reference's sort
    picks, min(t + 1, topk) of them or a tie's more; at one over it every
    causal key."""
    cfg = dataclasses.replace(CFG, index_topk=topk)
    tokens, _ = _batch(cfg)
    got, want = _selections(cfg, both[0], tokens)
    assert bool(jnp.all(got == want))
    causal = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    assert not bool(jnp.any(got & ~causal))
    if topk >= SEQ:
        assert bool(jnp.all(got == causal))
    else:
        seen = jnp.sum(got, axis=-1)
        assert bool(jnp.all(seen >= jnp.minimum(jnp.arange(SEQ) + 1, topk)))
        assert bool(jnp.all(seen[:, :topk] == jnp.arange(topk) + 1))


def test_the_mixer_hands_its_loss_and_counters_out_of_the_stack(both):
    params, (_, counters, _), (parts, _) = both
    tokens, _ = _batch(CFG)
    stats = jax.jit(lambda p: decoder.decoder_hidden(
        p, tokens, CFG.decoder())[2])(params)
    assert len(stats) == CFG.n_layers
    for layer in stats:     # the mixer's two beside the channel mixer's
        assert {"index_loss", "selected_keys_mean", "expert_tokens",
                "expert_rows_held", "expert_passes",
                "router_prob_sum"} <= set(layer)
        assert "router_bias" not in layer       # a softmax router has none
    want = np.mean([float(jnp.sum(s)) / (2 * SEQ)
                    for s in parts["selections"]])
    assert float(counters["selected_keys_mean"]) == pytest.approx(want)
    assert float(counters["router_bias_abs_max"]) == 0.0


def test_prefill_then_decode_is_the_references_full_forward(both):
    """Through the {"k", "v", "k_index"} cache, under the selection: 100
    tokens at once, then one at a time, a query past the 48th choosing
    among the cached keys."""
    params = both[0]
    tokens, _ = _batch(CFG)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(
            lambda p: family.reference_logits(p, tokens, CFG))(params)
        dec = CFG.decoder()
        cache = decoder.empty_cache(dec, params["layers"], 2, SEQ,
                                    jnp.float32)
        assert all(set(layer) == {"k", "v", "k_index"} for layer in cache)
        assert cache[0]["k_index"].shape == (2, SEQ, CFG.index_head_dim)

        @jax.jit
        def forward(ids, cache, at):
            x, head, stats, cache = decoder.decoder_hidden(
                params, ids, dec, cache, at)
            return decoder.decoder_logits(x, head), stats, cache

        logits, stats, cache = forward(tokens[:, :100], cache, 0)
        assert _close(logits, want[:, :100])
        assert float(stats[0]["index_loss"]) == 0.0
        for i in range(100, 104):
            logits, _, cache = forward(tokens[:, i:i + 1], cache, i)
            assert _close(logits, want[:, i:i + 1]), i
        # every row at its own position (continuous batching)
        logits, _, _ = forward(tokens[:, 104:105], cache,
                               jnp.array([104, 104]))
        assert _close(logits, want[:, 104:105])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """32 experts in eight shares of four under the softmax router: the
    shares' parts sum to what the reference's uncut layer gives, the
    counters are the dropless layer's, and no share holds a bias."""
    d, E, k, f, T = 64, 32, 3, 24, 96
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (T, d))
    router = jax.random.normal(ks[1], (d, E)) * d ** -0.5
    gate_up = jax.random.normal(ks[2], (E, d, 2 * f)) * d ** -0.5
    down = jax.random.normal(ks[3], (E, f, d)) * f ** -0.5
    with jax.default_matmul_precision("highest"):
        want, chosen, probs = family._plain_experts(
            x, router, gate_up, down, k=k, first=0)
        total, stats = jnp.zeros_like(x), []
        for first in range(0, E, 4):
            out, s = decoder.held_moe_layer(
                x, router, None, gate_up[first:first + 4],
                down[first:first + 4], experts_per_token=k, first=first,
                gated=True, weight_eps=0.0, softmax=True)
            total, stats = total + out, stats + [s]
        whole, whole_stats = decoder.held_moe_layer(
            x, router, None, gate_up, down, experts_per_token=k, first=0,
            gated=True, weight_eps=0.0, softmax=True)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        dropless, dropless_stats = dropless_moe_layer(
            x, router, gate, up, down, experts_per_token=k,
            norm_topk_prob=True)
    assert _close(total, want) and _close(whole, want)
    assert _close(whole, dropless)
    assert sum(int(s["expert_rows_held"]) for s in stats) == T * k
    for s in (*stats, whole_stats):
        assert "router_bias" not in s
        assert _close(s["router_prob_sum"], jnp.sum(probs, 0))
        assert _close(s["router_prob_sum"],
                      dropless_stats["router_prob_sum"])
        assert bool(jnp.all(s["expert_tokens"]
                            == dropless_stats["expert_tokens"]))


@pytest.mark.parametrize("occasions", [1, 8])
def test_the_placement_gives_every_chip_its_share_of_made_up_loads(occasions):
    """128 experts over 8 chips, 16 each: log-normal loads on one or eight
    occasions, every chip within 2% of an eighth on each; and the collapse
    the chip read at a seeded start, every token on the same eight experts:
    a hot one a chip, an eighth exactly."""
    from ray_tpu.parallel.moe import place_experts, placement_order
    rng = np.random.default_rng(5)
    base = rng.lognormal(0.0, 0.6, 128) * 1024
    loads = rng.poisson(np.broadcast_to(base, (occasions, 128)))
    chip_of = place_experts(loads if occasions > 1 else loads[0], 8)
    assert chip_of.shape == (128,) and chip_of.dtype == np.int32
    np.testing.assert_array_equal(np.bincount(chip_of), [16] * 8)
    on_chip = np.stack([loads[:, chip_of == c].sum(1) for c in range(8)], 1)
    assert np.abs(on_chip / loads.sum(1, keepdims=True) * 8 - 1).max() <= 0.02
    order = placement_order(chip_of)
    np.testing.assert_array_equal(np.sort(order), np.arange(128))
    np.testing.assert_array_equal(chip_of[order], np.repeat(np.arange(8), 16))
    hot = np.zeros(128, np.int64)
    hot[rng.choice(128, 8, replace=False)] = 16384
    chip_of = place_experts(hot, 8)
    assert [hot[chip_of == c].sum() for c in range(8)] == [16384] * 8
    with pytest.raises(ValueError, match="do not divide"):
        place_experts(np.ones(10), 4)


def test_the_family_places_the_experts_on_the_cells_ring():
    """`placed`: the walk over the layers on the ring the driver draws from
    the seed gives this chip (the second 4 of 8) half of every layer's
    assignments, as the program then counts them, by permuting the routers'
    columns and nothing else; a config `build` did not make, or a traced
    key or tree, is left alone."""
    with open(os.path.join(ROOT, "chipbench/tests/rehearsal/data/configs/"
                                 "keyevl2-tiny.json")) as f:
        config = json.load(f)
    cfg = family.build(config, dtype=jnp.float32, remat=False)
    key = jax.random.PRNGKey(2147483900)
    assert family._seed_of(key) == 2147483900
    raw = keye_vl2_init(key, cfg)
    params = family.placed(raw, key, cfg)
    on = config["assumed"]["placement"]
    ids, = family.ring_of(2147483900, cfg.vocab_size, on["ring_batches"],
                          on["global_batch"], on["seq"])
    assert ids.shape == (1, 128)
    even = ids.size * cfg.experts_per_token * 4 / 8
    batch = (ids, np.roll(ids, -1, 1))
    held, unplaced = (np.asarray(keye_vl2_loss_and_counters(
        p, batch, cfg)[1]["expert_rows_held"]) / even for p in (params, raw))
    # 8 experts of some 48 rows each: a chip's count moves in steps of 0.5%,
    # and two chips of four cannot always be made even
    assert np.abs(held - 1).max() <= 0.06, (held, unplaced)
    for got, old in zip(params["layers"], raw["layers"]):
        assert sorted(np.asarray(got["router"]).sum(0).tolist()) == sorted(
            np.asarray(old["router"]).sum(0).tolist())
        assert got["expert_gate_up"] is old["expert_gate_up"]
    other = dataclasses.replace(cfg, max_seq_len=cfg.max_seq_len + 1)
    assert family.placed(raw, key, other) is raw
    shapes = jax.eval_shape(lambda k: family.placed(
        keye_vl2_init(k, cfg), k, cfg), key)
    assert jax.tree.structure(shapes) == jax.tree.structure(raw)


# ---------------------------------------------------------------------------
# the indexer's passes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seq", [384])
def test_the_indexers_kernels_in_interpret_mode(monkeypatch, seq):
    """`sparse_index_fwd` and `_bwd` (three by three tiles of 128: under,
    on and above the diagonal) against the float32 formula: the scores to rounding of a sum, the
    gradients to bfloat16's, -inf above the diagonal and nowhere else."""
    b, H, D = 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    q = jax.random.normal(ks[0], (b, H, seq, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, seq, D)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[2], (b, seq, H)) * 0.1
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    g = jnp.where(causal, jax.random.normal(ks[3], (b, seq, seq)), 0.0)
    want = sparse_index.index_scores_reference(q, k, w)
    want_grads = sparse_index.index_grads(q, k, w, g)    # the plain form's
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    plan = sparse_index.sparse_index_plan(seq, H, D)
    assert (plan.tile, plan.grid) == (128, 3)
    assert sparse_index.sparse_index_plan(256, H, D).grid == 1
    got = sparse_index.index_scores(q, k, w)
    assert bool(jnp.all(jnp.isfinite(got) == causal))
    assert _close(jnp.where(causal, got, 0), jnp.where(causal, want, 0),
                  1e-5)
    for name, a, r in zip(("dq", "dk", "dw"),
                          sparse_index.index_grads(q, k, w, g), want_grads):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        assert _close(a.astype(jnp.float32), r.astype(jnp.float32),
                      1e-5 if name == "dw" else 2e-2), name


@pytest.mark.parametrize("k", [1, 5, 64, 200])
def test_the_kth_largest_is_exact(k):
    """The bisection against a sort, ties, signed zeros, negatives and
    -inf entries among the values; -inf where fewer than k are finite."""
    x = jax.random.normal(jax.random.PRNGKey(k), (7, 128))
    x = x.at[0, :40].set(0.5).at[1, ::2].set(-0.0).at[1, 1::4].set(0.0)
    x = x.at[2, 3:].set(-jnp.inf).at[3].set(-jnp.abs(x[3]))
    got = sparse_index.kth_largest(x, k)
    if k > 128:
        assert bool(jnp.all(got == -jnp.inf))
        return
    want = -jnp.sort(-x, axis=-1)[:, k - 1]
    assert bool(jnp.all(got == want)), (got, want)
    assert bool(jnp.all(sparse_index.kth_largest(x, 129) == -jnp.inf))


def test_the_plan_counts_what_the_cell_runs():
    plan = sparse_index.sparse_index_plan(16384, 16, 64)
    assert (plan.tile, plan.grid, plan.tiles) == (512, 32, 528)
    assert plan.fwd_products == 528 * 16 and plan.bwd_products == 3 * 8448
    assert plan.fwd_flops == 528 * 16 * 2 * 512 * 512 * 64
    assert plan.vmem_bytes <= 32 * 2 ** 20
    # both plain passes in four bands of 4,096 queries: 5/8 of the square
    assert plan.select_pairs == plan.target_pairs == 5 * 16384 ** 2 // 8
    with pytest.raises(ValueError, match="multiples of 128"):
        sparse_index.sparse_index_plan(1000, 16, 64)


@pytest.mark.parametrize("seq, chunk, want", [
    (16384, 512, (4096, 8192, 12288, 16384)),       # the cell's select
    (16384, 256, (4096, 8192, 12288, 16384)),       # and its target
    (32768, 512, (8192, 16384, 24576, 32768)),      # never over four
    (4096, 512, (2048, 4096)),
    (2048, 512, (2048,)), (128, 128, (128,)),       # tier-1's, the cache's
    (6144, 512, (2048, 4096, 6144)),
    (5120, 512, (2560, 5120)),          # 2,560 = five chunks of 512
    (7680, 512, (2560, 5120, 7680)),
    (7168, 512, (3584, 7168)),          # 14 chunks: three bands cut one
    (4608, 512, (4608,)),               # nine chunks: two bands cut one
    (4608, 256, (2304, 4608))])         # eighteen of 256: they do not
def test_the_bands_are_whole_chunks_from_the_shape(seq, chunk, want):
    """G equal bands, band g's keys [0, (g + 1) T / G): the most bands, up
    to four, of at least 2,048 queries whose edges cut no chunk; a count
    that would is passed over for the next one under it, down to one band
    of the whole row. The plan counts the pairs they hold, (G + 1) / 2G of
    the square."""
    got = sparse_index.key_bands(seq, chunk)
    assert got == want
    band = seq // len(got)
    assert band % chunk == 0 and got[-1] == seq
    assert len(got) == 1 or (band >= 2048 and len(got) <= 4)
    plan = sparse_index.sparse_index_plan(seq, 16, 64)
    for pairs, (c, extents) in (
            (plan.select_pairs, sparse_index._select_bands(seq)),
            (plan.target_pairs, sparse_index._target_bands(seq))):
        g = len(extents)
        assert seq // g % c == 0
        assert pairs == seq * seq * (g + 1) // (2 * g)


BANDED_SEQ = 1024


@pytest.fixture(scope="module")
def banded_scores():
    """I [2, 1024, 1024] as `index_scores` leaves it, with what a trimmed
    count could get wrong: rows of ties at tau across a band's edge, rows
    with fewer finite entries than any topk under the diagonal, a row of
    equal scores, and signed zeros."""
    causal = jnp.tril(jnp.ones((BANDED_SEQ, BANDED_SEQ), bool))
    x = jax.random.normal(jax.random.PRNGKey(61), (2, BANDED_SEQ, BANDED_SEQ))
    x = x.at[0, 700, 100:650].set(0.25).at[0, 701].set(1.5)
    x = x.at[1, 300, 40:].set(-jnp.inf).at[1, 900, ::3].set(-jnp.inf)
    x = x.at[1, 600, ::2].set(-0.0).at[1, 600, 1::4].set(0.0)
    return jnp.where(causal, x, -jnp.inf)


def _with_bands_of(monkeypatch, band, fn, *args):
    """fn(*args) traced with the fewest queries of a band at `band` and
    chunks of 128 queries, and with up to eight bands: the shape then
    falls to 1,024 / band bands (a test's only way to several bands under
    4,096 positions; the program reads the shape alone)."""
    monkeypatch.setattr(sparse_index, "_BAND", band)
    monkeypatch.setattr(sparse_index, "_BANDS", 8)
    monkeypatch.setattr(sparse_index, "_QUERY_CHUNK", 128)
    monkeypatch.setattr(sparse_index, "_TARGET_CHUNK", 128)
    return jax.jit(fn)(*args)


@pytest.mark.parametrize("band", [128, 256, 512])
@pytest.mark.parametrize("topk", [1, 200, 256, 700, 2000])
def test_the_banded_selection_is_the_whole_rows_bit_for_bit(
        monkeypatch, banded_scores, band, topk):
    """`select` over 8, 4 and 2 bands of key prefixes against one band of
    the whole row: the same selection and the same tau, every bit, at a
    topk under a band's extent, equal to it, between two bands' (the first
    bands give -inf at once, or count rows with fewer finite entries than
    topk), and over the sequence (every causal key)."""
    def pick(x):
        return sparse_index.select(x, topk)
    want_sel, want_tau = _with_bands_of(monkeypatch, 2048, pick, banded_scores)
    assert sparse_index._select_bands(BANDED_SEQ) == (128, (BANDED_SEQ,))
    sel, tau = _with_bands_of(monkeypatch, band, pick, banded_scores)
    assert len(sparse_index._select_bands(BANDED_SEQ)[1]) == BANDED_SEQ // band
    assert sel.dtype == jnp.int8 and tau.dtype == jnp.float32
    assert bool(jnp.all(sel == want_sel))
    assert np.array_equal(np.asarray(tau).view(np.int32),
                          np.asarray(want_tau).view(np.int32))
    seen = jnp.sum(sel, axis=-1)
    rows = jnp.arange(BANDED_SEQ)
    if topk >= BANDED_SEQ:
        assert bool(jnp.all(tau == -jnp.inf))
    else:
        assert bool(jnp.all((tau == -jnp.inf)[:, :topk - 1]))
        assert bool(jnp.all(seen[0, :700] == jnp.minimum(rows + 1, topk)[:700]))
    if topk == 200:
        assert float(tau[0, 700]) == 0.25 and int(seen[0, 700]) > 550   # a tie
        # 40 finite entries: -inf, and `scores >= -inf` is every causal key
        assert float(tau[1, 300]) == -jnp.inf and int(seen[1, 300]) == 301


@pytest.mark.parametrize("band", [128, 256, 512])
def test_the_banded_target_is_the_whole_rows(monkeypatch, banded_scores,
                                             band):
    """`index_target` over 8, 4 and 2 bands against one: L_I and dI to the
    order of a float32 sum (a trimmed key adds an exact zero), dI exactly
    zero above the diagonal and outside the selection, its rows summing to
    nothing (softmax less p, both of mass one over S_t)."""
    b, h, kvh, hd, topk = 2, 4, 2, 16, 200
    ks = jax.random.split(jax.random.PRNGKey(band), 2)
    q = jax.random.normal(ks[0], (b, h, BANDED_SEQ, hd))
    k = jax.random.normal(ks[1], (b, kvh, BANDED_SEQ, hd))
    causal = jnp.tril(jnp.ones((BANDED_SEQ, BANDED_SEQ), bool))
    scores = jnp.where(causal & ~jnp.isfinite(banded_scores), -3.0,
                       banded_scores)      # I is finite under the diagonal
    selected = sparse_index.select(scores, topk)[0]
    sm_scale = hd ** -0.5
    s = jnp.einsum("bjgqd,bjkd->bjgqk",
                   q.reshape(b, kvh, h // kvh, BANDED_SEQ, hd), k) * sm_scale
    lse = jax.nn.logsumexp(
        jnp.where(selected[:, None, None] != 0, s, -jnp.inf),
        axis=-1).reshape(b, h, 1, BANDED_SEQ)

    def target(*args):
        return sparse_index.index_target(*args, sm_scale)
    args = (scores, selected, q, k, lse)
    want_loss, want = _with_bands_of(monkeypatch, 2048, target, *args)
    loss, got = _with_bands_of(monkeypatch, band, target, *args)
    assert len(sparse_index._target_bands(BANDED_SEQ)[1]) == BANDED_SEQ // band
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(want_loss) > 0.1
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * float(want_loss)
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-6 * float(jnp.max(jnp.abs(want)))
    assert bool(jnp.all(jnp.where(selected != 0, 0.0, got) == 0.0))
    assert bool(jnp.all(jnp.triu(got, 1) == 0.0))
    assert float(jnp.max(jnp.abs(jnp.sum(got, axis=-1)))) < 1e-8


def test_two_bands_from_the_shape_alone_are_the_whole_rows():
    """No constant moved: 4,096 positions fall to two bands of 2,048 by
    themselves, and `select` there (a topk over the first band's extent,
    which gives -inf at once, and one under it) is `kth_largest` of the
    whole row."""
    seq = 4096
    assert sparse_index._select_bands(seq) == (512, (2048, 4096))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    x = jax.random.normal(jax.random.PRNGKey(4096), (1, seq, seq))
    x = jnp.where(causal, x, -jnp.inf)
    for topk in (2500, 64):
        want = jax.jit(lambda s: sparse_index.kth_largest(s, topk))(x)
        sel, tau = jax.jit(lambda s: sparse_index.select(s, topk))(x)
        assert np.array_equal(np.asarray(tau).view(np.int32),
                              np.asarray(want).view(np.int32))
        assert bool(jnp.all((sel != 0) == (causal & (x >= want[..., None]))))


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def _cell_config():
    with open(os.path.join(
            ROOT, "chipbench/configs/keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def test_counts_are_the_hand_computed_ones():
    config = _cell_config()
    cfg = family.build(config)
    shapes = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    layer = shapes["layers"][0]
    assert count({n: layer[n] for n in ("wq", "wkv", "wo")}) == 18_874_368
    assert count({n: layer[n] for n in INDEXER_LEAVES}) == 2_261_120
    assert count(layer["router"]) == 262_144
    assert count({n: layer[n] for n in ("expert_gate_up", "expert_down")}) \
        == 16 * 4_718_592
    assert count(layer) == 96_899_456
    assert count(shapes["embed"]) == count(shapes["head"]) == 38_895_616
    assert count(shapes) == 659_190_016
    # pairs: every causal one scored, min(t + 1, 2048) attended
    assert family.causal_pairs(16384) == 134_225_920
    assert family.selected_pairs(16384, 2048) == 31_458_304
    assert attention.attention_plan(
        16384, 128, selected=2048).required_pairs == 31_458_304
    for given in (cfg, config):     # the object's and the file's alike
        per_token = family.train_flops_per_token(given, 16384)
        assert per_token == pytest.approx(2.1e9, rel=0.03)
        parts = family._forward_parts(family._dims(given), 16384)
        step = {n: 3 * 16384 * v for n, v in parts.items()}
        assert step["projections"] == pytest.approx(1.86e12, rel=0.01)
        assert step["index_projections"] == pytest.approx(2.2e11, rel=0.02)
        assert step["index_scores"] == pytest.approx(8.2e11, rel=0.01)
        assert step["attention"] == pytest.approx(1.55e12, rel=0.01)
        assert step["target"] / 3 == pytest.approx(2.6e11, rel=0.01)
        assert step["experts"] == pytest.approx(4.9e11, rel=0.01)
        assert step["head"] == pytest.approx(3.8e12, rel=0.01)
        assert family.attention_kernel_flops(given, 1, 16384) \
            == 6 * 6 * 2 * 31_458_304 * 4096
        assert family.sparse_index_flops(given, 1, 16384) \
            == 6 * 3 * 2 * 134_225_920 * 1024
        assert family.held_rows_balanced(given, 16384) == 16384
        assert family.expert_matmul_flops(given, 16384) \
            == 6 * 9 * 2 * 16384 * 2048 * 768
    # the sparse mixer whole is four fifths of a layer, what it adds half
    layer_ops = sum(v for n, v in parts.items() if n != "head") \
        - 2 / 3 * parts["target"]
    sparse = parts["index_projections"] + parts["index_scores"] \
        + parts["attention"] + parts["target"] / 3
    assert sparse / layer_ops == pytest.approx(0.55, abs=0.03)
    assert (sparse + parts["projections"]) / layer_ops == pytest.approx(
        0.9, abs=0.03)


def test_the_configuration_file_keeps_every_catalog_key():
    config = _cell_config()
    catalog = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=262144, max_window_layers=48,
        mlp_only_layers=[], model_type="KeyeVL2", moe_intermediate_size=768,
        norm_topk_prob=True, num_attention_heads=32, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
        num_local_experts=128, rms_norm_eps=1e-06,
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        rope_theta=10000000,
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 2048},
        sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    reduced = {"num_hidden_layers": 6, "num_experts": 16,
               "num_local_experts": 16, "vocab_size": 18992}
    assert config["reduced"] == list(reduced)
    for key, value in catalog.items():
        assert config[key] == reduced.get(key, value), key
    assert set(config["reduced_from"]) == set(reduced)
    sizes = config["deployment_sizes"]
    assert (sizes["chips_sharing_a_layer"], sizes["num_experts"],
            sizes["vocab_size"], sizes["num_hidden_layers"]) == (
        8, 128, 151936, 48)
    for said in ("indexer_equations", "indexer_training", "indexer_chunks",
                 "rotary_on_text", "router_aux_loss_coef",
                 "index_loss_weight", "optimizer", "dtype"):
        assert said in config["assumed"], said
    assert f"{family.LEARNING_RATE:.0e}".replace("e-0", "e-") \
        in config["assumed"]["optimizer"]
    cfg = family.build(config)
    assert (cfg.n_layers, cfg.held, cfg.n_experts, cfg.vocab_size,
            cfg.index_topk, cfg.index_heads, cfg.index_head_dim) == (
        6, (0, 16), 128, 18992, 2048, 16, 64)


# ---------------------------------------------------------------------------
# every planted fault, at the smallest size that shows it
# ---------------------------------------------------------------------------
SMALL = dataclasses.replace(CFG, index_topk=24)
MOE_FAULTS = ("weights_not_renormalised", "sigmoid_router")


@pytest.fixture(scope="module")
def one_layer():
    """A sparse-attention branch's and an expert layer's seeded inputs (64
    tokens, a query naming 24; norms off one and a bias off zero, so that
    leaving one out shows) and what the reference gives for them: the
    outputs (and L_I) and the gradients of a seeded weighted sum of the
    output plus L_I by the rows and every weight."""
    lay = keye_vl2_init(jax.random.PRNGKey(7), SMALL)["layers"][0]
    for i, name in enumerate(("ln1", "q_head_norm", "k_head_norm",
                              "index_k_norm", "index_k_norm_b")):
        lay[name] = lay[name] + 0.3 * jax.random.normal(
            jax.random.PRNGKey(i), lay[name].shape)
    lay = {name: t if t.ndim == 1 else t * 8 for name, t in lay.items()}
    kx, kw = jax.random.split(jax.random.PRNGKey(8))
    x = jax.random.normal(kx, (1, 64, SMALL.d_model))
    out_w = jax.random.normal(kw, x.shape)

    def mixer(x, lay):
        out, l_i, _ = family._sparse_attention(
            family._rms_norm(x, lay["ln1"], SMALL.norm_eps), lay, SMALL)
        return jnp.sum(out * out_w) + l_i

    def experts(x, lay):
        out, _, _ = family._plain_experts(
            x[0], lay["router"], lay["expert_gate_up"], lay["expert_down"],
            k=SMALL.experts_per_token, first=SMALL.held[0])
        return jnp.sum(out * out_w[0])

    want = {"mixer": jax.jit(jax.value_and_grad(mixer, (0, 1)))(x, lay),
            "experts": jax.jit(jax.value_and_grad(experts, (0, 1)))(x, lay)}
    return x, lay, out_w, want


def _off_reference(x, lay, out_w, want, part):
    """The largest relative distance of the program's weighted sum or of
    any of its gradients from the reference's; 1 where L_I's own gradient
    reaches what is not the indexer's (beside the output's it is small,
    and it should not be there at all)."""
    dec = SMALL.decoder()

    def mixer(x, lay, of_out=1.0):
        out, _, stats = decoder.sparse_attention(x, lay, dec)
        return of_out * jnp.sum(out * out_w) + stats["index_loss"]

    def experts(x, lay):
        return jnp.sum(dec.mlp[0](x, lay)[0] * out_w)

    def distance(got, want):
        return max(float(jnp.max(jnp.abs(g - w)) / (
            jnp.max(jnp.abs(w)) + 1e-30)) for g, w in zip(
                jax.tree.leaves(got), jax.tree.leaves(want)))

    if part == "experts":
        return distance(jax.jit(jax.value_and_grad(experts, (0, 1)))(x, lay),
                        want[part])
    got, (dx, of_index_loss) = jax.jit(lambda x, lay: (
        jax.value_and_grad(mixer, (0, 1))(x, lay),
        jax.grad(mixer, (0, 1))(x, lay, 0.0)))(x, lay)
    leaked = bool(jnp.any(dx != 0)) or any(
        bool(jnp.any(g != 0)) for name, g in of_index_loss.items()
        if name not in INDEXER_LEAVES)
    return max(distance(got, want[part]), float(leaked))


@pytest.mark.parametrize("part", ["mixer", "experts"])
def test_a_layers_two_branches_are_the_references(one_layer, part):
    assert _off_reference(*one_layer, part) < 1e-4


@pytest.mark.parametrize("fault", sorted(family.STRUCTURAL_FAULTS))
def test_a_planted_fault_shows(one_layer, fault):
    """Each of the family's twelve, planted through the module's own names,
    moves an output or a gradient far outside the tests' 1e-4."""
    name, _ = family.STRUCTURAL_FAULTS[fault]
    real = getattr(decoder, name)
    with family.planted(fault):
        assert getattr(decoder, name) is not real
        off = _off_reference(
            *one_layer, "experts" if fault in MOE_FAULTS else "mixer")
    assert getattr(decoder, name) is real
    assert off > 3e-3, (fault, off)


def test_the_faults_are_the_issues_twelve():
    assert set(family.STRUCTURAL_FAULTS) == {
        "topk_1024", "selection_not_causal", "relu_left_out",
        "key_norm_left_out", "index_scale_left_out", "input_not_detached",
        "target_not_detached", "target_from_head_0",
        "selection_ignored_in_dkv", "early_rows_tau_zero",
        "weights_not_renormalised", "sigmoid_router"}
    assert family.PRECISION_FAULTS == {}
    # reader and counts name the same passes: the reader sums the Mosaic
    # rows whose name holds its SCOPE, which the family's two do and no
    # other kernel's scope does
    from chipbench.layer_metrics import sparse_index_ms_per_step as reader
    from ray_tpu.util import profiling
    assert family.SPARSE_INDEX_SCOPES == ("sparse_index_fwd",
                                          "sparse_index_bwd")
    assert {s for s in profiling.DEVICE_SCOPES if reader.SCOPE in s} == {
        *family.SPARSE_INDEX_SCOPES, "sparse_index_proj"}


# ---------------------------------------------------------------------------
# a call without a selection lowers to what it lowered to
# ---------------------------------------------------------------------------
def _text_without_locations(lowered) -> str:
    """A lowered program's text less its source locations, each Mosaic
    kernel's serialized body parsed and printed in its place (the bytes
    carry line numbers; the printed module does not)."""
    import base64

    from jax._src.lib.mlir import ir
    out = []
    for line in lowered.as_text().splitlines():
        if line.startswith("#loc") or not line.strip():
            continue
        line = re.sub(r"\s*loc\(.*\)$", "", line)
        config = re.search(r'backend_config = "((?:[^"\\]|\\.)*)"', line)
        if "@tpu_custom_call" in line and config:
            body = base64.b64decode(json.loads(config.group(1).replace(
                "\\22", '"'))["custom_call_config"]["body"])
            context = ir.Context()
            context.allow_unregistered_dialects = True  # Mosaic's own dialect
            with context:
                out.append(str(ir.Module.parse(body)))
            line = line.replace(config.group(0), "backend_config")
        out.append(line)
    return "\n".join(out)


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_flash_attention_without_a_selection_lowers_as_before(monkeypatch):
    """`selected=None` is no operand: the forward and the backward of a
    causal call, and of a windowed one, lower for the TPU to the text the
    parent of PR 60 gave (its digest, taken from that tree with this
    function), the three kernels' bodies, signatures and grids with it."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((2, 2, 512, 64), jnp.bfloat16)

    def lowered(window):
        def loss(q, k, v):
            return jnp.sum(attention.flash_attention(
                q, k, v, True, None, window).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, q, q).lower(lowering_platforms=("tpu",))

    texts = {window: _text_without_locations(lowered(window))
             for window in (None, 128)}
    for text in texts.values():
        assert text.count("tpu_custom_call") >= 3 and "xi8>" not in text
    assert {w: _digest(t) for w, t in texts.items()} == PARENTS_DIGESTS
    # with a selection: one more operand in each of the three, int8
    sel = jax.ShapeDtypeStruct((2, 512, 512), jnp.int8)

    def selected_loss(q, k, v, sel):
        return jnp.sum(attention.flash_attention(
            q, k, v, True, None, None, sel).astype(jnp.float32))
    text = jax.jit(jax.grad(selected_loss, argnums=(0, 1, 2))).trace(
        q, q, q, sel).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3 and all("512x512xi8" in c for c in calls)


# The digests of `_text_without_locations` of the two programs above on the
# tree PR 60 started from (b9e07f7).
PARENTS_DIGESTS = {None: "7a33b1117dc47bfe", 128: "0900adc64245b774"}


def test_a_stack_without_a_selection_lowers_as_before():
    """An attention block's lowering carries no trace of the fourteenth
    kind: no selection, no indexer's scope, no int8 operand, and the same
    scopes as the thirteen had (tests/test_xing4.py holds `_block`'s text
    to the parent's line for line)."""
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny()
    dec = cfg.decoder()
    layer = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))["layers"][0]
    x = jax.ShapeDtypeStruct((2, 128, cfg.d_model), cfg.dtype)
    text = jax.jit(lambda x, layer: decoder._block(
        x, layer, None, None, dec=dec, kind=dec.kinds[0],
        mlp=dec.mlp[0])[:2]).lower(x, layer).as_text()
    assert "sparse" not in text and "xi8>" not in text
