"""keye_vl2 family (ray_tpu.models.keye_vl2): config builder, operation and
byte counts, and a plain float32 reference of the layer equations of
Keye-VL-2.0-30B-A3B's language decoder (Kwai-Keye/Keye-VL-2.0-30B-A3B
config.json, model_type KeyeVL2: Qwen3-MoE's layer with DeepSeek sparse
attention, whose lightning indexer, top-k selection and KL loss are
DeepSeek-V3.2-Exp's report's). The model's own modeling file is not on this
machine; what the config's keys do not say is in the configuration's
`assumed`.

The equations (d = 2048, RMSNorm eps 1e-6, no bias but the indexer's key
norm's; T tokens of one sequence, positions from 0; every layer alike):

    x_0 = E[token];  layer l:  x <- x + A(rmsnorm(x; w1));
                               x <- x + F(rmsnorm(x; w2))
    logits = rmsnorm(x_L; w_f) Head                        (Head untied)
    A          q = y W_q (32 x 128); k | v = y W_kv (4 x 128 each); q, k <-
               rmsnorm over a head's 128 columns (one [128] weight each),
               then rotary over all 128 columns (rotate_half pairs (i, i +
               64), f_i = 1e7^(-2i/128));
               the indexer, on y' = stop_gradient(y): q_I = y' W_qI (16 x
               64); k_I = layernorm(y' W_kI; gain, bias, eps 1e-6) (64);
               w = y' W_w / sqrt(16 x 64) (16); rotary over all 64 columns
               of q_I and k_I at the same base;
               I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t;
               tau_t the 2,048th largest of I[t, :t + 1] (-inf while t <
               2,048); S_t = {s <= t : I[t, s] >= tau_t};
               o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, h // 8]
               / sqrt(128)) v[s, h // 8];  out = concat_h(o) W_o
    L_I (a layer)   p[t, s] = mean_h a[t, h, s], a the probabilities above,
               stop-gradient;  L_I = 1/T sum_t sum_{s in S_t} p[t, s] (log
               p[t, s] - log softmax_{S_t}(I[t, .])[s])
    F          r = softmax(z W_r) over all 128, float32; e_1..8 the top
               eight; w_j = r[e_j] / sum_j r[e_j];
               out = sum over the HELD e_j of w_j W2[e_j] (silu(W1[e_j] z)
                     * W3[e_j] z), width 768; no shared expert
    loss = CE(logits, next token) + 0.001 x 128 sum_e f_e P_e + sum_l L_I
           (f_e the share of ALL layers' T x 8 assignments on e, times 8;
           P_e the mean of r_e over all layers' tokens)

One chip's share: the file's `num_experts` experts from the first on are
held; what the absent ones would add is left out, here as in the program.
The vocabulary is the file's slice.

Which experts those are is a placement (`placed`): before the step is built
the family routes the cell's own ring of batches through the seeded model
layer by layer, counts every expert's assignments, gives the deployment's
eight chips 16 experts each so that every chip's count is an eighth
(ray_tpu.parallel.moe.place_experts), and relabels the router's columns so
that this chip's experts are the first 16. A layer is placed on what the
layer before it, already placed, hands on. Weights are a seed's, so a
relabelling is another draw. A seeded biasless softmax router needs it: at
Keye-VL-2.0's widths the residual stream of a seeded start sends most
tokens of a layer to the same few experts (read on the chip: one expert up
to sixteen times the mean load, the first sixteen experts' rows 0.04 to 2.48
of a balanced share; PERF.md section 6, PR 60), and this router has no bias
to answer with.

The reference scores EVERY causal pair in query blocks, finds tau by a
sort, runs attention as a plain masked softmax over per-head keys and
values and every held expert for every token masked by the reference's
own routing: no tile, no bisection, no flash kernel, no sort of tokens, no
grouped matmul, no cache, and no code shared with ray_tpu. It reads the
program's parameter tree (`wkv` W_k | W_v side by side, `index_wq` W_qI,
`index_wk` W_kI, `index_k_norm` | `_b` its norm, `index_ww` W_w,
`expert_gate_up` an expert's W1 | W3 side by side). The count functions
take the program's config object or the configuration file's dict and
import no jax: per-layer readers call them in run.py's parent process,
which must never initialise a backend."""

from __future__ import annotations

import contextlib
import functools
import importlib.machinery
import importlib.util
import math

# A tree from before the family says so as the cell is looked up, in
# run.py's own process, before a cluster or a chip is touched
# (families/granite_hybrid.py has why it is looked for this way).
if importlib.machinery.PathFinder.find_spec(
        "ray_tpu.models.keye_vl2", importlib.util.find_spec(
            "ray_tpu.models").submodule_search_locations) is None:
    raise ImportError("this tree's program has no ray_tpu.models.keye_vl2: "
                      "it cannot run a keye_vl2 configuration")

from .lfm2_moe import _blocks, _rms_norm, _rotate_half, _silu  # noqa: E402
from .xing4 import _all_of, _rel  # noqa: E402

# The Pallas kernels a lowered train step of this family must call:
# ops/attention.py's three, ops/sparse_index.py's two,
# ops/grouped_matmul.py's two.
MOSAIC_KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel",
                  "_index_fwd_kernel", "_index_bwd_kernel",
                  "_gmm_kernel", "_tgmm_kernel")

# The scopes whose Mosaic rows layer_metrics/sparse_index_ms_per_step.py
# sums, and exactly the passes `sparse_index_flops` / `_bytes` count.
SPARSE_INDEX_SCOPES = ("sparse_index_fwd", "sparse_index_bwd")

_QUERY_BLOCK = 256
_LOSS_ROWS = 2048
_INDEX_NORM_EPS = 1e-6
_CATALOG = dict(
    attention_bias=False, decoder_sparse_step=1, hidden_act="silu",
    mlp_only_layers=[], norm_topk_prob=True, tie_word_embeddings=False,
    use_sliding_window=False, sliding_window=None)


# cfg -> (ring batches, global batch, sequence) of the cell the placement
# is made on: `build` notes what the configuration's file says.
_PLACED_ON = {}


def build(config: dict, **overrides):
    """The program's KeyeVL2Config at the file's sizes."""
    from ray_tpu.models.keye_vl2 import KeyeVL2Config

    for key, want in _CATALOG.items():
        if config[key] != want:
            raise ValueError(f"models/keye_vl2.py has {key} = {want!r} "
                             f"only, not {config[key]!r}")
    a, sizes, sa = (config["assumed"], config["deployment_sizes"],
                    config["sa_config"])
    if sizes["first_expert_held"] % config["num_experts"]:
        raise ValueError("a chip's share is a whole one: `placed` makes the "
                         "held experts those of chip first / held")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("ops/sparse_index.py scores against ONE key head")
    if config["rope_scaling"]["rope_type"] != "default":
        raise ValueError("models/keye_vl2.py turns plain rotary pairs: on "
                         "text the three mrope sections are one position")
    kw = dict(vocab_size=config["vocab_size"],
              d_model=config["hidden_size"],
              n_layers=config["num_hidden_layers"],
              n_heads=config["num_attention_heads"],
              n_kv_heads=config["num_key_value_heads"],
              head_dim=config["head_dim"],
              index_heads=sa["indexer_num_heads"],
              index_head_dim=sa["indexer_head_dim"],
              index_topk=sa["topk"],
              n_experts=sizes["num_experts"],
              experts_held=(sizes["first_expert_held"],
                            config["num_experts"]),
              experts_per_token=config["num_experts_per_tok"],
              d_expert=config["moe_intermediate_size"],
              router_aux_loss_coef=a["router_aux_loss_coef"],
              index_loss_weight=a["index_loss_weight"],
              rope_theta=float(config["rope_theta"]),
              norm_eps=config["rms_norm_eps"],
              init_std=a["initializer_range"],
              max_seq_len=config["max_position_embeddings"])
    kw.update(overrides)
    cfg = KeyeVL2Config(**kw)
    on = a["placement"]
    _PLACED_ON[cfg] = (on["ring_batches"], on["global_batch"], on["seq"])
    return cfg


# The cell's second limit, on what this configuration brought: the largest
# of kernel_errors' relative errors (each the root mean square of got -
# want over that of want). Read on the v5e at the published sizes (my chip
# runs, PR 60: _scratch/pr60/readings2.py, which calls kernel_errors as
# limit_readings.py does; seeds 11 and 2147483900, and seed 0 in every run's
# hold_kernels): the program 0.0321 (seed 0), 0.0345, 0.0347; this file's
# forms with every input and value in bfloat16, the nearest precision
# below, 0.0472 and 0.0439. The worst value is the same in all five: the
# gradient of W_kI through the layer (then W_qI's, 0.023 | 0.030): L_I's
# gradient by the scores sums to zero a row, so the bfloat16 the backward
# kernel's products and dq_I, dk_I are rounded to shows at thirty times its
# size; every other value reads under 0.0076 | 0.0143 (attention's q norm).
# The limit is the geometric mean of the program's largest and the lower
# precision's smallest, 1.125 times of room either side: ISSUE 60 asked
# 1.2, and the two precisions lie 1.27 apart on this value. Each of the
# twelve structural faults below reads 0.121 or more (seed 11: the
# indexer's input not detached 0.121, topk 1,024 0.373, the selection
# ignored in dK/dV 0.505, norm_topk_prob left out 0.710, a sigmoid router
# 0.892, the others 1.58 to 674).
KERNEL_LIMIT = 0.039


def hold_kernels(cfg):
    """Refuse a program whose indexer kernels, selection, attention under a
    selection, indexer loss or held expert layer under the softmax router
    is further from this file's float32 forms than KERNEL_LIMIT: the loss
    at initialisation, which drivers/train.py compares, hardly sees a
    layer's structure (PERF.md section 4), so the cell holds what this
    configuration brought to a limit of its own before it hands the program
    over."""
    from .. import harness

    errors = kernel_errors(cfg)
    _cases.cache_clear()        # its arrays are the chip's, and the step's now
    worst = max(errors, key=errors.get)
    harness.require(
        errors[worst] <= KERNEL_LIMIT,
        f"the program is off the float32 reference by {errors[worst]:.3g} "
        f"in {worst} (limit {KERNEL_LIMIT}): {errors}")


# The family's learning rate: AdamW, weight decay 0.01, constant. ISSUE
# 60's rule asks for the largest of 1e-4, 1e-5 and 1e-6 at which every
# layer's held rows stay within 5% of a balanced 16,384 in every one of 120
# steps on six seeds, one pass a layer throughout. NONE of the three holds
# it, read on the chip with the experts placed (chipbench/step_counters.py,
# every step's counters fetched; my chip runs, PR 60; the configuration's
# assumed.optimizer and PERF.md section 4 have the tables): at 1e-6 step 0
# reads 0.972-1.002 of balanced in every layer on six seeds, the first step
# outside 5% is the 14th to the 76th, the first 45 steps (a window's) stay
# within 3.3-8.0% on five seeds and reach 1.22 in one layer on the sixth,
# and by the 120th a layer reads up to 1.79; 1e-5 and 1e-4 (unplaced) reach
# 5.8 and 6.8. The smallest of the rule's three is kept. A seeded biasless
# softmax router at these widths sends most tokens of a layer to the same
# few experts (one expert up to sixteen times the mean load), so a router
# that moves 1e-6 a step already moves many tokens at once, and it has no
# bias to answer with; the cut feeds the drift (what the 112 absent experts
# would add is left out, so the loss's gradient prefers the held ones). At
# this rate the bfloat16 leaves barely move and the float32 ones (router,
# norms) do: a warm-up's first steps.
LEARNING_RATE = 1e-6


def train_program(cfg, mesh=None, rules=None):
    """(init_params, init_state, step, loss) of the program under test, the
    layers held to KERNEL_LIMIT first where the kernels are the chip's
    (elsewhere tier-1 holds them to the reference at 1e-4). `loss` is the
    step's own sum L."""
    import jax
    import optax

    from ray_tpu.models import keye_vl2 as program

    if jax.default_backend() == "tpu":
        hold_kernels(cfg)
    init_state, step = program.make_keye_vl2_train_step(
        cfg, optimizer=optax.adamw(LEARNING_RATE, weight_decay=0.01),
        mesh=mesh, rules=rules)

    def placed_state(key):
        state = init_state(key)
        return {**state, "params": placed(state["params"], key, cfg)}

    return (lambda key: placed(program.keye_vl2_init(key, cfg), key, cfg),
            placed_state, step,
            lambda params, batch: program.keye_vl2_loss(params, batch, cfg))


# ---------------------------------------------------------------------------
# which experts this chip holds
# ---------------------------------------------------------------------------
def _seed_of(key) -> int:
    """The seed jax.random.PRNGKey(seed) was made from (drivers/train.py
    hands the family the key, and draws its ring from the seed)."""
    import jax
    import numpy as np
    hi, lo = (int(w) for w in np.asarray(
        jax.random.key_data(key)).reshape(-1)[-2:])
    return (hi << 32) | lo


def ring_of(seed: int, vocab: int, ring_batches: int, batch: int, seq: int):
    """The cell's ring of batches as drivers/train.py draws it."""
    import numpy as np
    return np.random.default_rng([seed, 1]).integers(
        0, vocab, (ring_batches, batch, seq), dtype=np.int32)


def place_layers(params, ring, cfg):
    """The walk over the layers: -> (one order a layer, order[new] = old,
    int32 [n_experts]; a layer's counts [ring batches, n_experts] as they
    were before it was placed). Layer l is routed on what layers 0..l - 1,
    already placed and cut to this chip's share, hand on; every chip's
    count is an eighth on every batch of the ring
    (ray_tpu.parallel.moe.place_experts)."""
    import jax
    import numpy as np

    from ray_tpu.models import decoder
    from ray_tpu.parallel.moe import place_experts, placement_order

    dec = cfg.decoder()._replace(remat=None)
    layers = params["layers"]
    chips = cfg.n_experts // cfg.held[1]

    @functools.cache
    def block_for(key):
        block = decoder._block_of(dec, *key)
        return jax.jit(lambda x, layer: block(
            x, layer, None, None, decoder.Shared())[:2])

    xs = [params["embed"][np.asarray(ids)] for ids in ring]
    orders, counted = [], []
    for key, layer in zip(decoder._block_keys(dec, layers), layers):
        run = block_for(key)
        counts = np.stack([np.asarray(run(x, layer)[1]["expert_tokens"])
                           for x in xs])
        order = placement_order(place_experts(counts, chips))
        orders.append(order)
        counted.append(counts)
        layer = {**layer, "router": layer["router"][:, order]}
        xs = [run(x, layer)[0] for x in xs]
    return orders, counted


@functools.lru_cache(maxsize=4)
def _orders(cfg, seed: int):
    import jax

    from ray_tpu.models import keye_vl2 as program

    params = program.keye_vl2_init(jax.random.PRNGKey(seed), cfg)
    ring = ring_of(seed, cfg.vocab_size, *_PLACED_ON[cfg])
    return tuple(place_layers(params, ring, cfg)[0])


def relabelled(params, orders):
    """`params` with each layer's router's columns in its order: expert
    `new` of the result is expert order[new] of `params`."""
    return {**params, "layers": [
        {**layer, "router": layer["router"][:, order]}
        for layer, order in zip(params["layers"], orders)]}


def placed(params, key, cfg):
    """`params` (the seeded model of `key`) with this chip's experts made
    the first it holds, layer by layer: the placement is made once a
    (configuration, seed), on the ring the configuration's file names. A
    config that `build` did not make (a test's own) is left as it is, and
    so is a tree that is being traced."""
    import jax
    traced = any(isinstance(t, jax.core.Tracer)
                 for t in (key, *jax.tree.leaves(params)[:1]))
    if cfg not in _PLACED_ON or traced:
        return params       # (traced for its shapes: a relabelling has none)
    return relabelled(params, _orders(cfg, _seed_of(key)))


# ---------------------------------------------------------------------------
# faults to plant: the control of the cell's two limits
# ---------------------------------------------------------------------------
def _topk_halved(mixer, x, layer, dec, cache=None, start_pos=None):
    """A query names 1,024 keys, not 2,048."""
    return mixer(x, layer, dec._replace(sparse_topk=dec.sparse_topk // 2),
                 cache, start_pos)


def _selection_not_causal(select, scores, topk):
    """tau_t is the topk-th largest of the WHOLE row, the keys after t
    scored as their mirror images are: fewer than topk of a query's own
    past clear it."""
    import jax.numpy as jnp
    return select(jnp.where(jnp.isfinite(scores), scores,
                            jnp.swapaxes(scores, 1, 2)), topk)


def _relu_left_out(scores, q, k, w):
    """I = sum_j w_j (q_j . k): one product of the weighted sum of the
    heads' queries."""
    import jax.numpy as jnp
    f32 = jnp.float32
    mixed = jnp.einsum("bth,bhtd->btd", w.astype(f32), q.astype(f32))
    plain = jnp.einsum("btd,bsd->bts", mixed, k.astype(f32))
    return jnp.where(jnp.isfinite(scores(q, k, w)), plain, -jnp.inf)


def _key_norm_left_out(norm, x, weight, bias, eps):
    """k_I = y' W_kI as it is (the model's one LayerNorm is the
    indexer's)."""
    return x


def _index_scale_left_out(heads, y, layer, dec, positions):
    """w = y' W_w, the 16^(-1/2) 64^(-1/2) left out: every score of a row
    grows alike, so the selection stays and L_I and the indexer's
    gradients move."""
    q, k, w = heads(y, layer, dec, positions)
    return q, k, w * math.sqrt(q.shape[1] * q.shape[3])


def _input_not_detached(detached, y):
    """The indexer reads y itself: L_I's gradient runs on into the block's
    input and everything before it."""
    return y


def _target_not_detached(loss, q_index, k_index, w, scores, selected, q, k,
                         lse, sm_scale):
    """p carries its gradient: L_I reaches attention's q and k. The value
    is the rule's own."""
    import jax

    from ray_tpu.ops.sparse_index import index_target
    through_p = index_target(scores, selected, q, k, lse, sm_scale)[0]
    return loss(q_index, k_index, w, scores, selected, q, k, lse, sm_scale) \
        + through_p - jax.lax.stop_gradient(through_p)


def _target_from_head_0(loss, q_index, k_index, w, scores, selected, q, k,
                        lse, sm_scale):
    """p[t, s] = a[t, 0, s]: head 0's probabilities alone."""
    return loss(q_index, k_index, w, scores, selected, q[:, :1], k[:, :1],
                lse[:, :1], sm_scale)


def _selection_ignored_in_dkv(attend, q, k, v, sm_scale, selected):
    """dK and dV as the dense causal backward gives them with the selected
    forward's lse: every causal pair's exp(s - lse), selected or not. The
    forward and dQ are the real ones."""
    import jax
    import jax.numpy as jnp

    stop, f32 = jax.lax.stop_gradient, jnp.float32
    out, lse = attend(q, stop(k), stop(v), sm_scale, selected)
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    seq = q.shape[2]
    block = _blocks(seq, _QUERY_BLOCK)

    def dense(args):
        qb, lb, first = args            # [b, h, block, hd], [b, h, block]
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k,
                       preferred_element_type=f32) * scale
        seen = jnp.arange(seq)[None, :] <= (
            first + jnp.arange(block))[:, None]
        p = jnp.where(seen, jnp.exp(s - lb[..., None]), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)

    def blocks(t):
        return jnp.moveaxis(t.reshape(t.shape[:2] + (seq // block, block)
                                      + t.shape[3:]), 2, 0)
    unselected = jnp.moveaxis(jax.lax.map(dense, (
        blocks(stop(q)), blocks(stop(lse)[:, :, 0]),
        jnp.arange(0, seq, block))), 0, 2).reshape(out.shape)
    return out + unselected - stop(unselected), lse


def _early_rows_tau_zero(select, scores, topk):
    """tau_t = 0 while t < topk: a query with fewer than topk keys before
    it drops those that score under zero."""
    import jax.numpy as jnp
    selected, tau = select(scores, topk)
    early = (jnp.arange(scores.shape[1]) < topk)[None, :, None]
    return jnp.where(early & (scores < 0), 0, selected).astype(
        selected.dtype), tau


def _weights_not_renormalised(layer, x, router_w, router_bias, w_up, w_down,
                              *shared, **sizes):
    """w_j = r[e_j] as the softmax gives it: norm_topk_prob false. The
    plain form in the program's own precision; the counters are the real
    layer's."""
    out = _plain_experts(x, router_w, w_up, w_down,
                         k=sizes["experts_per_token"], first=sizes["first"],
                         fault="weights_not_renormalised")[0]
    real = layer(x, router_w, router_bias, w_up, w_down, *shared, **sizes)[1]
    return out.astype(x.dtype), real


def _sigmoid_scores(layer, x, router_w, router_bias, *weights, **sizes):
    """s = sigmoid(z W_r) and a zero bias: DeepSeek-V3's scores."""
    import jax.numpy as jnp
    out, stats = layer(x, router_w, jnp.zeros(router_w.shape[-1]), *weights,
                       **{**sizes, "softmax": False})
    stats.pop("router_bias")
    return out, stats


# What limit_readings.py plants in the program, one at a time, each a fault
# of structure in what this configuration brought: (the name on
# ray_tpu.models.decoder that stands for the faulty one meanwhile, the
# faulty one given the real one first).
STRUCTURAL_FAULTS = {
    "topk_1024": ("sparse_attention", _topk_halved),
    "selection_not_causal": ("select", _selection_not_causal),
    "relu_left_out": ("index_scores", _relu_left_out),
    "key_norm_left_out": ("layer_norm", _key_norm_left_out),
    "index_scale_left_out": ("_index_heads", _index_scale_left_out),
    "input_not_detached": ("_detached", _input_not_detached),
    "target_not_detached": ("indexer_loss", _target_not_detached),
    "target_from_head_0": ("indexer_loss", _target_from_head_0),
    "selection_ignored_in_dkv": ("attention_and_lse",
                                 _selection_ignored_in_dkv),
    "early_rows_tau_zero": ("select", _early_rows_tau_zero),
    "weights_not_renormalised": ("held_moe_layer",
                                 _weights_not_renormalised),
    "sigmoid_router": ("held_moe_layer", _sigmoid_scores),
}
PRECISION_FAULTS = {}


@contextlib.contextmanager
def planted(fault: str):
    """The program with `fault` in every layer: models.decoder calls the
    indexer's passes, the selection, attention and the expert layer through
    its own names, one of which stands for the faulty one meanwhile. Trace
    the program inside; a function jitted before keeps what it traced."""
    from ray_tpu.models import decoder

    name, faulty = STRUCTURAL_FAULTS[fault]
    real = getattr(decoder, name)
    setattr(decoder, name, functools.partial(faulty, real))
    try:
        yield
    finally:
        setattr(decoder, name, real)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes alone (no jax)
# ---------------------------------------------------------------------------
def _dims(cfg) -> dict:
    """Sizes from the program's KeyeVL2Config or the configuration's dict.
    `held` experts of `e` the router spans."""
    if isinstance(cfg, dict):
        sa = cfg["sa_config"]
        return dict(d=cfg["hidden_size"], v=cfg["vocab_size"],
                    layers=cfg["num_hidden_layers"],
                    h=cfg["num_attention_heads"],
                    kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                    ih=sa["indexer_num_heads"], id=sa["indexer_head_dim"],
                    topk=sa["topk"],
                    e=cfg["deployment_sizes"]["num_experts"],
                    held=cfg["num_experts"], k=cfg["num_experts_per_tok"],
                    f=cfg["moe_intermediate_size"])
    return dict(d=cfg.d_model, v=cfg.vocab_size, layers=cfg.n_layers,
                h=cfg.n_heads, kv=cfg.n_kv_heads, hd=cfg.head_dim,
                ih=cfg.index_heads, id=cfg.index_head_dim,
                topk=cfg.index_topk, e=cfg.n_experts, held=cfg.held[1],
                k=cfg.experts_per_token, f=cfg.d_expert)


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one sequence with the key at or before the
    query."""
    return seq * (seq + 1) // 2


def selected_pairs(seq: int, topk: int) -> int:
    """Pairs a sequence's attention runs over under the selection:
    sum_t min(t + 1, topk) (31,458,304 at 16,384 and 2,048; a tie at tau
    adds its keys, which no count knows)."""
    reach = min(seq, topk)
    return reach * (reach + 1) // 2 + (seq - reach) * reach


def _held_rows(s: dict, tokens: int) -> float:
    """Rows a layer's held experts see under a balanced router."""
    return tokens * s["k"] * s["held"] / s["e"]


def held_rows_balanced(cfg, tokens: int) -> float:
    """The rows a layer's held experts see a step of `tokens` under a
    balanced router: what the counts below take the routed work to be, and
    what the step's `expert_rows_held` is read against
    (chipbench/step_counters.py)."""
    return _held_rows(_dims(cfg), tokens)


def _forward_parts(s: dict, seq: int) -> dict:
    """REQUIRED matmul operations of one token's forward pass at context
    `seq`, by part: a layer's, and the head's."""
    d, q_d, kv_d = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    index_d = s["ih"] * s["id"]
    return dict(
        projections=2 * d * (q_d + 2 * kv_d) + 2 * q_d * d,
        index_projections=2 * d * (index_d + s["id"] + s["ih"]),
        # every causal pair: a product a head
        index_scores=2 * index_d * causal_pairs(seq) / seq,
        # the selected pairs: QK^T and PV a head
        attention=2 * 2 * q_d * selected_pairs(seq, s["topk"]) / seq,
        # the heads' probabilities again over the selected pairs: forward only
        target=2 * q_d * selected_pairs(seq, s["topk"]) / seq,
        experts=2 * d * s["e"] + _held_rows(s, 1) * 3 * 2 * d * s["f"],
        head=2 * d * s["v"])


def forward_flops_per_token(cfg, seq: int) -> float:
    """Required operations one token needs in the forward pass at context
    `seq`: the four attention projections; the indexer's three and its
    scores over every causal pair; attention over the SELECTED pairs; the
    indexer's target over them; the router over all e outputs and the
    balanced share of the routed work; the head once. What the masked
    kernels compute of unselected pairs is not required and not counted."""
    s = _dims(cfg)
    p = _forward_parts(s, seq)
    return s["layers"] * sum(p[n] for n in p if n != "head") + p["head"]


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward plus backward (twice the forward); the indexer's target has
    no backward pass (its gradient by the scores is a difference of two
    values the forward has); recomputation (remat, the kernels' tiles made
    again in their backward) is not counted."""
    s = _dims(cfg)
    return 3.0 * forward_flops_per_token(cfg, seq) \
        - 2.0 * s["layers"] * _forward_parts(s, seq)["target"]


def attention_kernel_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the attention kernels in one train step:
    forward 2 matmuls, backward 4, each 2 x heads x head_dim a SELECTED
    pair: what the selection is worth, whatever the kernels execute."""
    s = _dims(cfg)
    return (s["layers"] * (2 + 4) * 2.0 * batch
            * selected_pairs(seq, s["topk"]) * s["h"] * s["hd"])


def attention_kernel_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those kernels: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv, k and v
    counted at their 4 heads, not their copies across a group. bf16. No
    form of the selection is counted: a mask is one way to hand it over."""
    s = _dims(cfg)
    q = batch * seq * s["h"] * s["hd"] * 2
    kv = batch * seq * s["kv"] * s["hd"] * 2
    return s["layers"] * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def sparse_index_flops(cfg, batch: int, seq: int) -> float:
    """Required operations of the indexer's two kernels in one train step
    (SPARSE_INDEX_SCOPES): a product a head and causal pair forward, two
    backward (dq_I and dk_I; the scores the backward kernel makes again are
    not required)."""
    s = _dims(cfg)
    return (s["layers"] * 3 * 2.0 * batch * causal_pairs(seq)
            * s["ih"] * s["id"])


def sparse_index_bytes(cfg, batch: int, seq: int) -> float:
    """Least HBM traffic of those two: forward reads q_I, k_I (bf16), w
    (float32) and writes I, float32 over the causal pairs; backward reads
    them and dI and writes the three gradients."""
    s = _dims(cfg)
    operands = seq * ((s["ih"] * s["id"] + s["id"]) * 2 + s["ih"] * 4)
    return s["layers"] * batch * (3.0 * operands + 2 * 4 * causal_pairs(seq))


def expert_matmul_flops(cfg, tokens: int) -> float:
    """Required operations of the grouped matmuls in one train step for a
    BALANCED router: the held experts' rows (tokens x k x held / e a layer)
    go through three matmuls forward (gate and up are one grouped matmul of
    twice the width) and six backward, 2 * rows * d * f each."""
    s = _dims(cfg)
    return (s["layers"] * (3 + 6) * 2.0 * _held_rows(s, tokens)
            * s["d"] * s["f"])


def expert_matmul_bytes(cfg, tokens: int) -> float:
    """Least HBM traffic of those nine matmuls a layer: each touches its
    rows [rows, d], the held experts' tensor [held, d, f] and its other
    rows [rows, f] once. bf16."""
    s = _dims(cfg)
    one = (_held_rows(s, tokens) * (s["d"] + s["f"])
           + s["held"] * s["d"] * s["f"])
    return s["layers"] * (3 + 6) * 2.0 * one


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------
def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def index_inputs(y, lay, cfg):
    """The indexer's (q_I [b, s, H, D], k_I [b, s, D], w [b, s, H]) of the
    detached normed input y."""
    import jax
    b, s, _ = y.shape
    H, D = cfg.index_heads, cfg.index_head_dim
    y = jax.lax.stop_gradient(y)
    q = _rotate_half((y @ lay["index_wq"]).reshape(b, s, H, D),
                     cfg.rope_theta)
    k = _rotate_half(_layer_norm(y @ lay["index_wk"], lay["index_k_norm"],
                                 lay["index_k_norm_b"], _INDEX_NORM_EPS),
                     cfg.rope_theta)
    return q, k, (y @ lay["index_ww"]) / math.sqrt(H * D)


def index_scores(q, k, w, first=0, rows=None):
    """I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]) of queries `first` to
    `first + rows - 1` against every key, -inf where s > t: [b, rows, s]."""
    import jax
    import jax.numpy as jnp
    s = k.shape[1]
    rows = s if rows is None else rows
    qb, wb = (jax.lax.dynamic_slice_in_dim(t, first, rows, axis=1)
              for t in (q, w))
    prod = jnp.einsum("bqjd,bkd->bqjk", qb, k)
    scores = jnp.einsum("bqj,bqjk->bqk", wb, jnp.maximum(prod, 0))
    seen = jnp.arange(s)[None, :] <= (first + jnp.arange(rows))[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def selection(scores, topk: int):
    """S_t of a block of rows of I (-inf where not causal): I[t, s] >=
    tau_t, tau_t the topk-th largest of the row by a sort, -inf where
    fewer are causal (then every causal key); a tie at tau_t keeps both."""
    import jax.numpy as jnp
    if topk >= scores.shape[-1]:
        return jnp.isfinite(scores)
    tau = -jnp.sort(-scores, axis=-1)[..., topk - 1:topk]
    return jnp.isfinite(scores) & (scores >= tau)


def _sparse_attention(y, lay, cfg, given=None):
    """y [b, s, d] -> (the branch's output [b, s, d], L_I, the selection
    [b, s, s] bool): query blocks against all keys. `given`: a selection
    to attend and score under in the place of the reference's own."""
    import jax
    import jax.numpy as jnp

    b, s, _ = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (y @ lay["wq"]).reshape(b, s, kvh, h // kvh, hd)
    k, v = jnp.split(y @ lay["wkv"], 2, axis=-1)
    k, v = k.reshape(b, s, kvh, hd), v.reshape(b, s, kvh, hd)
    q = _rotate_half(_rms_norm(q, lay["q_head_norm"], cfg.norm_eps),
                     cfg.rope_theta)
    k = _rotate_half(_rms_norm(k, lay["k_head_norm"], cfg.norm_eps),
                     cfg.rope_theta)
    q_i, k_i, w = index_inputs(y, lay, cfg)
    block = _blocks(s, _QUERY_BLOCK)

    def one_block(args):
        qb, first, chosen = args               # [b, block, kvh, group, hd]
        scores = index_scores(q_i, k_i, w, first, block)
        seen = selection(jax.lax.stop_gradient(scores), cfg.index_topk) \
            if given is None else chosen
        sc = jnp.einsum("bqjgd,bkjd->bjgqk", qb, k) / math.sqrt(hd)
        a = jax.nn.softmax(
            jnp.where(seen[:, None, None], sc, -jnp.inf), -1)
        out = jnp.einsum("bjgqk,bkjd->bqjgd", a.astype(v.dtype), v)
        p = jax.lax.stop_gradient(jnp.mean(a, axis=(1, 2)))    # [b, block, s]
        log_i = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(p > 0, p * (
            jnp.log(jnp.where(p > 0, p, 1.0))
            - jnp.where(seen, log_i, 0.0)), 0.0))
        return out, kl, seen

    firsts = jnp.arange(0, s, block)
    chosen = jnp.zeros((s // block, b, block, s), bool) if given is None \
        else given.reshape(b, s // block, block, s).swapaxes(0, 1)
    out, kl, seen = jax.lax.map(one_block, (
        q.reshape(b, s // block, block, kvh, h // kvh, hd).swapaxes(0, 1),
        firsts, chosen))
    out = out.swapaxes(0, 1).reshape(b, s, h * hd) @ lay["wo"]
    return out, jnp.sum(kl) / (b * s), seen.swapaxes(0, 1).reshape(b, s, s)


def _plain_experts(y, router, gate_up, down, *, k: int, first: int,
                   chosen=None, fault=None):
    """y [T, d] -> (the held experts' part [T, d], the chosen experts
    [T, k], the router's probabilities [T, E]). Every held expert runs on
    every token and is weighted by the routing's mask; `chosen` given, the
    routing is that one and not the reference's own."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(
        (y.astype(router.dtype) @ router).astype(jnp.float32), -1).astype(
            router.dtype)
    if chosen is None:
        chosen = jax.lax.top_k(probs, k)[1]
    w = jnp.take_along_axis(probs, chosen, -1)
    if fault != "weights_not_renormalised":
        w = w / jnp.sum(w, -1, keepdims=True)
    held = gate_up.shape[0]
    # [T, held]: a held expert's weight where it is among the k, else 0.
    weight = jnp.sum(
        jax.nn.one_hot(chosen - first, held, dtype=w.dtype) * w[..., None], 1)

    def one_expert(acc, xs):
        gu, dn, w_e = xs
        w1, w3 = jnp.split(gu, 2, axis=-1)
        out = (_silu(y @ w1) * (y @ w3)) @ dn
        return acc + w_e[:, None].astype(acc.dtype) * out, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          (gate_up, down, weight.T))
    return out, chosen, probs


def _hidden(params, tokens, cfg, dtype=None):
    """(final-norm rows [b, s, d], the head [d, V], the balance loss before
    its coefficient, each layer's L_I [layers], each layer's selection),
    every parameter and so every value in `dtype` (float32 unless given)."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda t: t.astype(dtype or jnp.float32), params)
    x = p["embed"][tokens]
    b, s, d = x.shape
    eps, E = cfg.norm_eps, cfg.n_experts
    counts = jnp.zeros((E,), jnp.float32)
    prob_sum = jnp.zeros((E,), jnp.float32)
    index_losses, selections = [], []
    for lay in p["layers"]:
        mixed, l_i, seen = _sparse_attention(
            _rms_norm(x, lay["ln1"], eps), lay, cfg)
        index_losses.append(l_i.astype(jnp.float32))
        selections.append(seen)
        x = (x + mixed).astype(p["embed"].dtype)
        y = _rms_norm(x, lay["ln2"], eps).reshape(b * s, d)
        out, chosen, probs = _plain_experts(
            y, lay["router"], lay["expert_gate_up"], lay["expert_down"],
            k=cfg.experts_per_token, first=cfg.held[0])
        counts = counts + jnp.sum(jax.nn.one_hot(chosen, E), (0, 1))
        prob_sum = prob_sum + jnp.sum(probs.astype(jnp.float32), 0)
        x = (x + out.reshape(b, s, d)).astype(p["embed"].dtype)
    rows = b * s * cfg.n_layers
    balance = E * jnp.sum(counts / rows * prob_sum / rows)
    return (_rms_norm(x, p["lnf"], eps), p["head"], balance,
            jnp.stack(index_losses), selections)


def reference_logits(params, tokens, cfg):
    """Full forward in float32: tokens [b, s] -> logits [b, s, vocab].
    Call under jax.default_matmul_precision("highest")."""
    x, head, *_ = _hidden(params, tokens, cfg)
    return x @ head


def reference_parts(params, tokens, targets, cfg, dtype=None) -> dict:
    """The training loss L and what it is the sum of: `loss`, `loss_ce`,
    `balance_loss` (before its coefficient), `index_loss` [layers] and each
    layer's `selections` [b, s, s] bool."""
    import jax
    import jax.numpy as jnp

    x, head, balance, index_loss, selections = _hidden(params, tokens, cfg,
                                                       dtype)
    rows = x.reshape(-1, x.shape[-1])
    block = _blocks(rows.shape[0], _LOSS_ROWS)

    def one_block(args):
        xb, tb = args
        logp = jax.nn.log_softmax((xb @ head).astype(jnp.float32), -1)
        return jnp.sum(jnp.take_along_axis(logp, tb[:, None], -1))

    total = jax.lax.map(one_block, (rows.reshape(-1, block, rows.shape[-1]),
                                    targets.reshape(-1, block)))
    ce = -jnp.sum(total) / targets.size
    loss = ce + cfg.router_aux_loss_coef * balance.astype(jnp.float32) \
        + cfg.index_loss_weight * jnp.sum(index_loss)
    return dict(loss=loss, loss_ce=ce, balance_loss=balance,
                index_loss=index_loss, selections=selections)


def reference_loss(params, tokens, targets, cfg, dtype=None):
    """L = CE + router_aux_loss_coef x balance + index_loss_weight x sum of
    the layers' L_I, in float32, the logits a block of rows at a time.
    `dtype` is for setting the comparison's limit only: the same reference
    with every parameter and value in a lower precision (bfloat16) has to
    come out as not correct (PERF.md)."""
    return reference_parts(params, tokens, targets, cfg, dtype)["loss"]


# ---------------------------------------------------------------------------
# what this configuration brought, against the forms above
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=2)
def _cases(cfg, seed: int) -> dict:
    """kernel_errors' seeded inputs, once a (configuration, seed): the
    program, the all-bfloat16 forms and every planted fault are read
    against the same values."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    d, E, k = cfg.d_model, cfg.n_experts, cfg.experts_per_token
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    H, D, f, held = (cfg.index_heads, cfg.index_head_dim, cfg.d_expert,
                     cfg.held[1])
    # rows: half again what a query may name, so that the later queries
    # select and the earlier ones see every key; in whole tiles
    L = max(256, -(-3 * cfg.index_topk // 2 // 128) * 128)
    T = 2048
    normal = jax.random.normal
    ki = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), 1), 16)
    dt = cfg.dtype

    def matrix(kk, shape, fan_in):
        return (normal(kk, shape) * fan_in ** -0.5).astype(dt)

    layer = {
        "ln1": 1.0 + 0.1 * normal(ki[0], (d,)),
        "wq": matrix(ki[1], (d, h * hd), d),
        "wkv": matrix(ki[2], (d, 2 * kvh * hd), d),
        "q_head_norm": 1.0 + 0.1 * normal(ki[3], (hd,)),
        "k_head_norm": 1.0 + 0.1 * normal(ki[4], (hd,)),
        "wo": matrix(ki[5], (h * hd, d), h * hd),
        "index_wq": matrix(ki[6], (d, H * D), d),
        "index_wk": matrix(ki[7], (d, D), d),
        "index_k_norm": 1.0 + 0.1 * normal(ki[8], (D,)),
        "index_k_norm_b": 0.1 * normal(ki[9], (D,)),
        "index_ww": matrix(ki[10], (d, H), d)}
    km = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), 2), 6)
    experts = (normal(km[1], (d, E)) * d ** -0.5,
               matrix(km[2], (held, d, 2 * f), d),
               matrix(km[3], (held, f, d), f))
    rows = normal(km[0], (T, d)).astype(dt)
    # a row whose k-th and next probability lie within 1e-6 is made a zero
    # row: its routing a rounding could turn
    with jax.default_matmul_precision("highest"):
        best, _ = jax.lax.top_k(jax.nn.softmax(
            rows.astype(f32) @ experts[0], -1), k + 1)
    rows = jnp.where((best[:, k - 1] - best[:, k] < 1e-6)[:, None], 0, rows)
    return dict(
        L=L, T=T, layer=layer,
        x=normal(ki[11], (1, L, d)).astype(dt),
        out_w=normal(ki[12], (1, L, d)),
        d_scores=normal(ki[13], (1, L, L)) / L,
        attn=tuple(normal(jax.random.fold_in(ki[14], i),
                          (1, n, L, hd)).astype(dt)
                   for i, n in enumerate((h, kvh, kvh))),
        attn_w=normal(ki[15], (1, h, L, hd)),
        experts=experts, rows=rows, moe_w=normal(km[4], (T, d)))


def kernel_errors(cfg, seed: int = 0, low: bool = False) -> dict:
    """What the program runs as models.decoder calls it (on a TPU its
    kernels), against this file's float32 forms at the configuration's
    sizes, each value's root mean square of got - want over that of want:

    * `index_*`: the index scores I over L = 3/2 topk rows from seeded
      q_I, k_I and w (the decoder's own three projections of a seeded
      input), and the three gradients of a seeded weighted sum of I, from
      `index_grads` (the backward kernel);
    * `select_unexplained`: the share of a query's keys on which the
      program's selection of ITS scores and this file's (a sort) of ITS
      scores from the same q_I, k_I and w differ although the score is
      further than 1e-4 of the row's largest |I| from tau_t: zero unless
      the selection is wrong;
    * `attn_*`: attention under the program's selection, which the form
      here is handed: the output and the gradients of a seeded weighted
      sum of it by q, k and v through the three kernels;
    * `layer_*`: the whole sparse-attention branch on L rows, the form
      here handed the selection the program's own pieces make: its output,
      L_I, and the gradients of a seeded weighted sum of the output plus
      L_I by the rows and by all eleven of the layer's weights (the
      cross entropy's side reaches no indexer weight and L_I's side
      nothing else: a path that should not be there shows as a gradient
      that is off);
    * `moe_*`: the held share of the expert layer at 16 of 128 under the
      softmax router on 2,048 seeded rows under THIS file's routing: the
      output and the gradients of a seeded weighted sum by the rows, the
      router and both expert tensors.

    With `low`, what is compared is this file's forms themselves with
    every input and value in bfloat16: the second reading KERNEL_LIMIT
    lies under."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decoder

    f32, bf16 = jnp.float32, jnp.bfloat16
    c = _cases(cfg, seed)
    lay, x, L = c["layer"], c["x"], c["L"]
    dec = cfg.decoder()
    topk = cfg.index_topk
    names = sorted(lay)
    errors = {}

    def cast(tree, dtype):
        return jax.tree.map(lambda t: t.astype(dtype), tree)

    # -- the program's pieces, as models.decoder calls them ------------------
    def pieces(x, lay):
        y = decoder._norm(x, lay, "ln1", dec.norm_eps)
        q_i, k_i, w = decoder._index_heads(decoder._detached(y), lay, dec,
                                           None)
        scores = decoder.index_scores(q_i, k_i, w)
        return q_i, k_i, w, scores, decoder.select(scores, topk)[0]

    q_i, k_i, w, scores, chosen = jax.jit(pieces)(x, lay)
    chosen = chosen != 0

    # -- the index scores and their gradient rule ----------------------------
    def plain_scores(dtype):
        def fn(q, k, w_):
            full = index_scores(q.astype(dtype).swapaxes(1, 2),
                                k.astype(dtype), w_.astype(dtype))
            return (jnp.where(jnp.isfinite(full), full, 0.0),)
        return fn

    given = (q_i.astype(f32), k_i.astype(f32), w)
    causal = jnp.tril(jnp.ones((L, L), f32))[None]
    with jax.default_matmul_precision("highest"):
        want = _all_of(plain_scores(f32), 3)((c["d_scores"] * causal,),
                                             *given)
        got = _all_of(plain_scores(bf16), 3)(
            (c["d_scores"] * causal,), *given) if low else None
    if not low:
        from ray_tpu.ops.sparse_index import index_grads
        got = (jnp.where(causal > 0, scores, 0.0),
               *jax.jit(index_grads)(q_i, k_i, w, c["d_scores"] * causal))
    errors.update(zip(("index_scores", "index_dq", "index_dk", "index_dw"),
                      _rel(got, want)))

    # -- the selection --------------------------------------------------------
    def causal_only(t):
        return jnp.where(causal > 0, t, -jnp.inf)

    base = selection(causal_only(want[0]), topk)
    tau = jnp.min(jnp.where(base, want[0], jnp.inf), axis=-1, keepdims=True)
    reach = 1e-4 * jnp.max(jnp.abs(want[0]), axis=-1, keepdims=True)
    against = selection(causal_only(got[0]), topk) if low else chosen
    unexplained = (against != base) & (jnp.abs(want[0] - tau) > reach)
    errors["select_unexplained"] = float(
        jnp.sum(unexplained) / (L * min(topk, L)))

    # -- attention under a selection, through the three kernels --------------
    q, k, v = c["attn"]
    group = q.shape[1] // k.shape[1]

    def attn_program(q, k, v):
        return (decoder.attention_and_lse(
            q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1), None,
            chosen.astype(jnp.int8))[0],)

    def attn_plain(dtype):
        def fn(q, k, v):
            q, k, v = (t.astype(dtype) for t in (q, k, v))
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, 1)) \
                / math.sqrt(q.shape[-1])
            a = jax.nn.softmax(jnp.where(chosen[:, None], sc, -jnp.inf), -1)
            return (jnp.einsum("bhqk,bhkd->bhqd", a.astype(dtype),
                               jnp.repeat(v, group, 1)),)
        return fn

    exact = cast((q, k, v), f32)
    with jax.default_matmul_precision("highest"):
        want = _all_of(attn_plain(f32), 3)((c["attn_w"],), *exact)
        got = _all_of(attn_plain(bf16), 3)((c["attn_w"],), *exact) \
            if low else None
    if not low:
        got = _all_of(attn_program, 3)((c["attn_w"],), q, k, v)
    errors.update(zip(("attn_out", "attn_dq", "attn_dk", "attn_dv"),
                      _rel(got, want)))

    # -- the whole branch -----------------------------------------------------
    def layer_program(x, *weights):
        out, _, stats = decoder.sparse_attention(
            x, dict(zip(names, weights)), dec)
        return out, stats["index_loss"]

    def layer_plain(dtype):
        def fn(x, *weights):
            held = cast(dict(zip(names, weights)), dtype)
            out, l_i, _ = _sparse_attention(
                _rms_norm(x.astype(dtype), held["ln1"], cfg.norm_eps), held,
                cfg, given=chosen)
            return out, l_i
        return fn

    weights = tuple(lay[n] for n in names)
    sums = (c["out_w"], jnp.ones(()))
    exact = cast((x, *weights), f32)
    n = 1 + len(names)
    with jax.default_matmul_precision("highest"):
        want = _all_of(layer_plain(f32), n)(sums, *exact)
        got = _all_of(layer_plain(bf16), n)(sums, *exact) if low else None
    if not low:
        got = _all_of(layer_program, n)(sums, x, *weights)
    errors.update(zip(("layer_out", "layer_index_loss", "layer_dx",
                       *(f"layer_d{name}" for name in names)),
                      _rel(got, want)))

    # Two paths that must not be there: L_I's gradient on anything but the
    # indexer's five leaves, the output's on any of those. Each the largest
    # such gradient over the largest the same sum rightly has; no form is
    # needed (both are zero in this file's, at any precision).
    own = [i + 1 for i, name in enumerate(names) if name.startswith("index_")]

    def largest(grads, which):
        return max(float(jnp.max(jnp.abs(grads[i]))) for i in which)

    if low:
        errors.update(layer_index_loss_leak=0.0, layer_output_leak=0.0)
    else:
        rest = [i for i in range(n) if i not in own]
        of_loss = _all_of(layer_program, n)(
            (0.0 * c["out_w"], jnp.ones(())), x, *weights)[2:]
        of_out = _all_of(layer_program, n)(
            (c["out_w"], jnp.zeros(())), x, *weights)[2:]
        errors.update(
            layer_index_loss_leak=largest(of_loss, rest)
            / largest(of_loss, own),
            layer_output_leak=largest(of_out, own) / largest(of_out, rest))

    # -- the held share of the expert layer ----------------------------------
    router, gate_up, down = c["experts"]
    sizes = dict(experts_per_token=cfg.experts_per_token, first=cfg.held[0],
                 routed_scale=1.0, weight_eps=0.0, gated=True, softmax=True)

    def moe_program(x, router, gate_up, down):
        return (decoder.held_moe_layer(x, router, None, gate_up, down,
                                       **sizes)[0],)

    def moe_plain(dtype):
        def fn(x, router, gate_up, down, chosen):
            x, router, gate_up, down = cast((x, router, gate_up, down), dtype)
            return (_plain_experts(x, router, gate_up, down,
                                   k=cfg.experts_per_token,
                                   first=cfg.held[0], chosen=chosen)[0],)
        return fn

    rows = c["rows"]
    exact = cast((rows, router, gate_up, down), f32)
    with jax.default_matmul_precision("highest"):
        # this file's routing, which the program's own router has to arrive
        # at (at the full precision it routes in)
        routed = jax.lax.top_k(jax.nn.softmax(exact[0] @ router, -1),
                               cfg.experts_per_token)[1]
        want = _all_of(moe_plain(f32), 4)((c["moe_w"],), *exact, routed)
        got = _all_of(moe_plain(bf16), 4)((c["moe_w"],), *exact, routed) \
            if low else None
    if not low:
        got = _all_of(moe_program, 4)((c["moe_w"],), rows, router, gate_up,
                                      down)
    errors.update(zip(("moe_out", "moe_dx", "moe_drouter", "moe_dgate_up",
                       "moe_ddown"), _rel(got, want)))
    return errors
