"""Names on the profiler's clock for the chip path, and the profile itself.

On TPU the profiler of record is jax.profiler: one trace holds what the
device ran ("/device:TPU:n": XLA operations and jitted programs) and what
the host was doing meanwhile ("/host:CPU": TraceAnnotation spans). This
module is the one route by which the chip path (ops/, models/, llm/,
train/, data/) puts names into that trace:

  * `annotate(name)`: a host span. Free of jax until jax is imported, and
    a few hundred nanoseconds when no profiler runs, so sites need no gate.
    Request spans that cross processes are util/tracing.py's; a hot loop
    never goes through those (two uuid4s, a lock and a buffer per span).
  * `HOST_SPANS` / `DEVICE_SCOPES`: every name the program emits, once.
    Tests check each against its site; PERF.md and docs/OBSERVABILITY.md
    quote the table.
  * `capture(logdir)`: a profile of this process, Python tracer off.
  * `profile_actor(actor, seconds)`: the same, taken by another process's
    worker for an operator (`ray_tpu profile`): only the process that
    owns a chip can trace it.
  * `read_device_events(xplane)`, `step_events`, `by_scope(events, steps,
    compiled_text)`: a trace's device time by DEVICE_SCOPES' names, a row
    a scope path and pass (`ray_tpu profile --by-scope`,
    chipbench/scope_profile.py). Offline arithmetic: nothing of it runs
    in a step.

One clock: a read-back trace counts every event's `start_ns`, host and
device, from its own `profile_start_time`, which is unix nanoseconds
(`profile_start_unix_ns`); their sum is `time.time_ns()` at a span's entry
to within microseconds (PERF.md has the chip's reading). So
util/tracing.py's request spans from other processes, on `time.time()`,
lie on the device trace's clock with no bridge but that one addition.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import glob
import math
import os
import re
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

# Host spans: name -> (site, the per-layer metric it is for).
HOST_SPANS: Dict[str, tuple] = {
    "ray_tpu.engine.admit": (
        "llm/continuous.py _admit: one pending request taken into a "
        "free slot", "engine_step_ms"),
    "ray_tpu.engine.prefill": (
        "llm/continuous.py _admit: dispatch of insert_prefill and the "
        "fetch of its last-position logits", "ttft"),
    "ray_tpu.engine.decode": (
        "llm/continuous.py _loop: dispatch of decode_batch",
        "engine_step_ms"),
    "ray_tpu.engine.fetch": (
        "llm/continuous.py _loop: np.asarray(logits), the wait for the "
        "device and the copy to the host", "engine_step_ms"),
    "ray_tpu.engine.sample": (
        "llm/continuous.py _loop/_admit: host-side sampling and the "
        "push to each request's stream", "engine_step_ms"),
    "ray_tpu.engine.idle": (
        "llm/continuous.py _loop: no slot occupied, waiting for a "
        "submit", "batch_occupancy"),
    "ray_tpu.serve.handle": (
        "serve/_private/replica.py handle_request(_streaming): the "
        "user's handler", "generator_late_p99_ms"),
    "ray_tpu.init": (
        "api.py init: the cluster of this driver comes up (node, log "
        "monitor, prestarted workers); its own root, the run's file "
        "carries the driver's latest", "cluster_start_s"),
    "ray_tpu.train.fit": (
        "train/trainer.py DataParallelTrainer.fit: called -> Result "
        "returned; the root of a run's spans",
        "worker_start_s"),
    "ray_tpu.train.start_group": (
        "train/v2/controller.py TrainController.run, round "
        "_start_worker_group: feasibility, dataset split, actors "
        "created, setup answered, run submitted", "worker_start_s"),
    "ray_tpu.train.worker_setup": (
        "train/worker_group.py TrainWorker.setup: the session and the "
        "backend's on_start (a gang's rendezvous)", "worker_start_s"),
    "ray_tpu.train.backend_start": (
        "train/worker_group.py TrainWorker.run, before the user's loop: "
        "import jax and the first jax.local_devices(), in a worker that "
        "was handed chips", "backend_start_s"),
    "ray_tpu.train.loop": (
        "train/worker_group.py TrainWorker.run: the user's loop, entry "
        "-> return", "setup_exec_s"),
    "ray_tpu.train.first_report": (
        "train/session.py _Session.report: a zero-length mark at the "
        "run's first train.report",
        "none (an operator's time to first report)"),
    "ray_tpu.train.report": (
        "train/session.py report, in the training loop's thread",
        "step_ms_p50"),
    "ray_tpu.train.poll": (
        "train/worker_group.py TrainWorker.poll: the controller's "
        "result poll draining the reports, on the worker's second "
        "thread", "train_device_idle_share"),
    "ray_tpu.feed.fetch_block": (
        "data/streaming.py iter_blocks: api.get of one block",
        "fetch_ms_per_batch"),
    "ray_tpu.feed.assemble": (
        "data/streaming.py batches_from_blocks: concat, slice and "
        "format of one batch", "fetch_ms_per_batch"),
    "ray_tpu.feed.device_put": (
        "data/streaming.py jax_device_feed: jax.device_put of one batch",
        "h2d_ms_per_batch"),
    "ray_tpu.data.map_batch": (
        "data/dataset.py _BatchMapper.apply (the map_batches actor): "
        "the user's function on one batch", "compute_ms_per_batch"),
}

# Device scopes (jax.named_scope, trace-time only): name -> site. A scope
# reaches the HLO instruction's name, which is what the device plane
# shows: the forward kernel is `flash_attention_fwd.N`, alone or under
# shard_map, while its kernel_name stays `_fwd_kernel`.
DEVICE_SCOPES: Dict[str, str] = {
    "flash_attention_fwd": "ops/attention.py _flash_forward, the "
                           "_fwd_kernel pallas_call",
    "flash_attention_dq": "ops/attention.py _flash_backward, the "
                          "_dq_kernel pallas_call",
    "flash_attention_dkv": "ops/attention.py _flash_backward, the "
                           "_dkv_kernel pallas_call",
    **{scope + "_window": f"ops/attention.py, the `{scope}` call under a "
                          "window: the same kernel over the band (a "
                          "reader that matches the kernel's name as a "
                          "substring reads both rows)"
       for scope in ("flash_attention_fwd", "flash_attention_dq",
                     "flash_attention_dkv")},
    "grouped_matmul_fwd": "ops/grouped_matmul.py _gmm, the _gmm_kernel "
                          "pallas_call: an expert layer's gate, up and "
                          "down matmuls",
    "grouped_matmul_dlhs": "ops/grouped_matmul.py _gmm with transpose_rhs, "
                           "the _gmm_kernel pallas_call: the gradient by "
                           "the rows",
    "grouped_matmul_drhs": "ops/grouped_matmul.py _tgmm, the _tgmm_kernel "
                           "pallas_call: the gradient by the experts' "
                           "weights",
    "ssm_scan_fwd": "ops/ssm_scan.py _scan_forward_call, the _ssm_fwd_kernel "
                    "pallas_call: a Mamba-2 layer's chunked selective "
                    "scan, y and one state a chunk; and round it _scan_fwd "
                    "(the chunk sums, the operands' layouts)",
    "ssm_scan_bwd": "ops/ssm_scan.py _scan_backward_call, the _ssm_bwd_kernel "
                    "pallas_call: every gradient of the scan, chunks last "
                    "to first; and round it _scan_bwd, the whole rule (the "
                    "chunk sums' transpose, the gradients' layouts)",
    "selective_scan_fwd": "ops/selective_scan.py _scan_forward_call, the "
                          "_selective_fwd_kernel pallas_call: a Mamba-1 "
                          "layer's selective scan, m and one state a chunk; "
                          "and round it _scan_fwd (the operands padded and "
                          "tiled in float32)",
    "selective_scan_bwd": "ops/selective_scan.py _scan_backward_call, the "
                          "_selective_bwd_kernel pallas_call: a chunk's "
                          "states again, then every gradient of the scan, "
                          "chunks last to first; and round it _scan_bwd, the "
                          "whole rule (the float32 tiles, B's and C's sums)",
    "gated_delta_fwd": "ops/gated_delta.py _forward_call, the "
                       "_gd_fwd_kernel pallas_call: a linear-attention "
                       "layer's chunked gated delta rule, o and the state "
                       "entering each chunk; and round it _rule_fwd (the "
                       "chunk sums of g)",
    "gated_delta_bwd": "ops/gated_delta.py _backward_call, the "
                       "_gd_bwd_kernel pallas_call: a chunk's inverse, W, U "
                       "and V' again, then every gradient of the rule, "
                       "chunks last to first; and round it _rule_bwd, the "
                       "whole rule",
    "kda_fwd": "ops/kda.py _forward_call, the _kda_fwd_kernel pallas_call: a "
               "KDA layer's chunked delta rule with a decay a key channel "
               "(chunks of 64 in sub-blocks of 16 rows, a reference row "
               "each; the running sums of g made in the kernel), o and "
               "the state entering each chunk; and round it _rule_fwd "
               "(beta by head group)",
    "kda_bwd": "ops/kda.py _backward_call, the _kda_bwd_kernel pallas_call: "
               "a chunk's decayed tiles, W, U and V' again from the kept "
               "state and T - I, then every gradient of the rule, chunks "
               "last to first, the gradient by g summed to each chunk's "
               "end in the kernel; and round it _rule_bwd, the whole rule",
    "short_conv_fwd": "ops/short_conv.py _forward_call, the "
                      "_conv_fwd_kernel pallas_call: a gated short "
                      "convolution's y = C * conv(B * x) from the "
                      "projection's thirds, in one pass",
    "short_conv_bwd": "ops/short_conv.py _backward_call, the "
                      "_conv_bwd_kernel pallas_call: the convolution made "
                      "again, the gradients by B, C and x and the taps' "
                      "float32 partial sums; and round it _gated_conv_bwd, "
                      "the whole rule (the padding, the taps' sum)",
    "short_conv_proj": "models/decoder.py short_conv: the input "
                       "projection to B | C | x and the output projection "
                       "round the convolution's kernels",
    "ssm_conv": "models/decoder.py mamba2, mamba1, gated_delta and kda: the "
                "causal depthwise convolution (over x | B | C; Mamba-1: "
                "over x; the delta rules: over q | k | v, no bias) and its "
                "silu; ops/layers.py _conv_silu_bwd runs under it too, as "
                "every rule does under the scopes round its call",
    "delta_qk_norm": "models/decoder.py gated_delta: q and k divided by "
                     "their L2 norm a head, q scaled by 1 / sqrt(key "
                     "width)",
    "delta_gate_norm": "models/decoder.py gated_delta: the RMSNorm a head "
                       "of the rule's output, then silu(gate) times it",
    "kda_qk_norm": "models/decoder.py kda: q and k divided by their L2 "
                   "norm a head, q scaled by 1 / sqrt(key width)",
    "kda_gate": "models/decoder.py kda: W_f to the float32 log-decay g = "
                "bound * sigmoid(exp(A_log) (y W_f + dt_bias)), one number "
                "a key channel, and beta = sigmoid(y W_beta)",
    "kda_gate_norm": "models/decoder.py kda: the RMSNorm a head of the "
                     "rule's output, then sigmoid(y W_g) times it, one "
                     "gate a head",
    "attention_gate": "models/decoder.py attention where the layer holds "
                      "`attn_gate`: sigmoid(y W_g), one gate a channel, "
                      "times the heads' outputs before W_o",
    "mla_gate": "models/decoder.py latent_attention where the layer holds "
                "`head_gate`: sigmoid(y W_g), one gate a head, times the "
                "heads' outputs before W_o",
    "gmu": "models/decoder.py gmu: a gated memory unit's gate projection, "
           "silu, the product with the handed-on scan output and the "
           "output projection",
    "diff_attention_combine": "models/decoder.py differential_maps: a "
                              "pair's first map less lambda times its "
                              "second, the sub-norm and the scale",
    "ssm_gate_norm": "models/decoder.py mamba2: y * silu(z) and the "
                     "RMSNorm over the inner width, one a group of B and C",
    "moe_route": "parallel/moe.py dropless_moe_layer and held_moe_layer: "
                 "float32 router, top-k, the sort of the assignments by "
                 "expert (a held share: the absent experts' last), the "
                 "per-expert counts and (in the experts' rule) the gather "
                 "of the rows and the weights in sorted order",
    "moe_combine": "parallel/moe.py _experts and _held_experts: the "
                   "experts' weighted rows back in token order and their "
                   "sum (a held share: a pass's rows added to their "
                   "tokens', by k gathers of T rows or one scatter-add as "
                   "held_rows_plan's `gathered` says)",
    "moe_dx": "parallel/moe.py _held_experts_bwd: the transpose of a held "
              "share's dispatch, a pass's gradient rows added to their "
              "tokens' in float32 by the same form as moe_combine",
    "moe_shared": "parallel/moe.py held_moe_layer: the shared expert every "
                  "token passes where the model has one, two plain matmuls "
                  "with relu^2 between or, gated, silu(gate) * up from one "
                  "fused first matrix",
    "layers": "models/decoder.py decoder_hidden, the layer stack of "
              "every decoder family (gpt, llama, moe, hybrid, sambay, "
              "olmo_hybrid, nemotron_h, lfm2_moe, xing4, glm4_moe_lite, "
              "keye_vl2, bailing_hybrid), "
              "each layer run by "
              "the row of decoder.MIXERS "
              "that its config's `kinds` names, in the train step and "
              "under prefill / decode alike",
    "loss": "ops/loss.py cross_entropy, every family's loss after its "
            "backbone: the scan over chunks of rows, forward and "
            "gradient in one pass (_sum_ll_bwd, which scales it, runs "
            "under the same scope: it is round the rule's call)",
    "mtp": "models/decoder.py decoder_hidden, everything of a "
           "multi-token-prediction module behind the stack (a training "
           "forward that is given the next tokens): the scopes below and, "
           "inside it, its block's own as any layer's "
           "(`latent_attention_mixer`, `channel_mixer` and theirs)",
    "mtp_embed": "models/decoder.py decoder_hidden and prediction_module: "
                 "the second lookup of the main table, at the next tokens, "
                 "and its norm (`enorm`); its backward is the table's "
                 "second scatter-add",
    "mtp_project": "models/decoder.py prediction_module: the norm of the "
                   "last block's output (`hnorm`), the concatenation "
                   "[embedding ; hidden] and its product with W_eh",
    "mtp_norm": "models/decoder.py prediction_module: the module's own "
                "final norm (the head's matmuls are `mtp_loss`'s)",
    "mtp_loss": "models/glm4_moe_lite.py joint_loss, round a step's second "
                "cross entropy (whose own `loss` lies inside it, so the "
                "path is `mtp_loss/loss`): the module's rows against the "
                "tokens two on through the MAIN head, the last position "
                "masked; `loss` alone is the stack's own",
    "optimizer_update": "models/_training.py train_step, optimizer "
                        "update and apply",
    # The boundaries of a block, and the hand-written backward rules that
    # no kernel's name covers: what `by_scope` files a step's time under.
    **{kind + "_mixer": "models/decoder.py _block: the sequence-mixer "
                        f"branch of a `{kind}` block (decoder.MIXER_SCOPES; "
                        "a kind `*_only` or `*_nope` runs under its "
                        "mixer's name), from "
                        "the norm the mixer reads to the residual add"
       for kind in ("attention", "mamba2", "mamba1", "gated_delta", "gmu",
                    "diff_windowed", "diff_full", "diff_cross", "short_conv",
                    "latent_attention", "sparse_attention", "kda",
                    "windowed_attention")},
    "sparse_index_proj": "models/decoder.py _index_heads: a lightning "
                         "indexer's three projections of the detached "
                         "normed input, its key norm and the rotary of q_I "
                         "and k_I",
    "sparse_index_fwd": "ops/sparse_index.py _fwd_call, the "
                        "_index_fwd_kernel pallas_call: the index scores I "
                        "[T, T] float32 over every causal pair, a product a "
                        "head and tile (elsewhere the plain form under the "
                        "same scope)",
    "sparse_index_bwd": "ops/sparse_index.py _bwd_call, the "
                        "_index_bwd_kernel pallas_call: dq_I, dk_I and dw "
                        "from dI, the heads' products made again a tile; it "
                        "runs in the FORWARD pass, inside `indexer_loss`'s "
                        "forward rule (elsewhere the plain form's gradient "
                        "under the same scope)",
    "sparse_select": "ops/sparse_index.py select: tau a row by bisection "
                     "over the float32 order, 32 counts of the key prefix "
                     "of the query chunk's band (key_bands: up to four "
                     "bands, the keys up to the band's last query), a loop "
                     "a band, and the selection [T, T] int8 in one pass "
                     "over I (with a cache, models/decoder.py "
                     "sparse_attention's plain form: the whole row)",
    "sparse_target": "ops/sparse_index.py index_target: the heads' mean "
                     "attention probability over the selected keys from q, "
                     "k and lse again, L_I and dI, a query chunk against "
                     "its band's key prefix (key_bands), a loop a band, a "
                     "chunk's rows of dI written over the rows of I it read",
    "mla_project": "models/decoder.py latent_attention: the products from "
                   "the block's input to q (through its normed latent where "
                   "the layer holds one) and to the latent | shared key, "
                   "the latents' norms and the rotary of q's and the key's "
                   "rope columns",
    "mla_expand": "models/decoder.py latent_attention: per-head keys and "
                  "values from the normed latent by W_kvb and K's assembly "
                  "from its no-rope part and the one rotated key under "
                  "every head: the part a rematerialised block makes again",
    "hc_coefficients": "models/decoder.py _streams_read, inside a branch's "
                       "scope: a hyper-connected branch's H_pre, H_post and "
                       "H_res from the streams (their norm, phi's product, "
                       "the sigmoids, the exp and the Sinkhorn rounds) and "
                       "its two counters",
    "hc_read": "models/decoder.py _streams_read: what a hyper-connected "
               "branch reads, the streams' mix by H_pre",
    "hc_write": "models/decoder.py _streams_read: what a hyper-connected "
                "branch returns to the streams, H_res X + H_post (x) y",
    "channel_mixer": "models/decoder.py _block: the channel-mixer branch "
                     "of a block, dense, routed or held experts alike "
                     "(`ln2`, the layer's `mlp`, `post_feedforward`, the "
                     "residual add); the moe_* scopes lie inside it",
    "embed": "models/decoder.py decoder_hidden: the embedding's lookup "
             "and scale; its backward is the table's scatter-add",
    "final_norm": "models/decoder.py decoder_hidden: the last norm and "
                  "the logit scale (the head's matmuls are `loss`'s)",
    "flash_attention_bwd": "ops/attention.py _flash_bwd, the whole rule: "
                           "delta = rowsum(dO * O), the operands' layouts "
                           "and, inside it, the dq and dkv kernels' scopes",
    "grouped_matmul_bwd": "ops/grouped_matmul.py grouped_matmul_grads, "
                          "both gradients of one grouped matmul: the work "
                          "lists and, inside it, the dlhs and drhs "
                          "kernels' scopes",
    "moe_experts_bwd": "parallel/moe.py _experts_bwd and "
                       "_held_experts_bwd, the whole rule: the rows "
                       "gathered again, the activation's slope, the "
                       "weights' gradients summed over the passes; "
                       "moe_dx and grouped_matmul_bwd lie inside it",
    "prefill": "models/generate.py prefill / insert_prefill, round "
               "models/decoder.py's stack with a cache",
    "decode": "models/generate.py decode_step / decode_batch, round the "
              "same stack one token a row",
}


def kernel_calls(compiled_text: str) -> Dict[str, int]:
    """The Mosaic kernel calls of a compiled program, counted by scope:
    a static counter, read from `jitted.lower(...).compile().as_text()`
    and not from a run. A call is an instruction whose target is
    `tpu_custom_call`; it counts under the DEVICE_SCOPES name its own name
    holds (`%grouped_matmul_fwd.12`, `%jvp_flash_attention_fwd_.3`), or
    under its own name with the number dropped where it holds none, so
    the values sum to the program's kernel calls. A call in a loop's body
    counts once.

    What it is for: a forward kernel that runs twice a step. A kernel's
    forward runs once for each of its backward passes unless a
    rematerialised block makes it again, so in a train step
    `calls["grouped_matmul_fwd"] - calls["grouped_matmul_dlhs"]` and
    `calls["flash_attention_fwd"] - calls["flash_attention_dq"]` are the
    forward calls the step repeats: 6 + 2 in OLMoE's two-layer step while
    its blocks kept nothing, 0 since they keep what
    models/decoder.py KEPT_UNDER_REMAT names (PERF.md §6, PR 28). The
    scan kernels count the same way: `calls["ssm_scan_fwd"] -
    calls["ssm_scan_bwd"]`, 0 in granite-4.0-h-micro's step of nine
    Mamba-2 layers (9 and 9, not 18 and 9), and `calls["gated_delta_fwd"]
    - calls["gated_delta_bwd"]`, 0 in Olmo-Hybrid-7B's period of three
    linear-attention layers."""
    calls: Dict[str, int] = {}
    for line in compiled_text.splitlines():
        name, eq, rest = line.strip().partition(" = ")
        if not eq or 'custom_call_target="tpu_custom_call"' not in rest:
            continue
        name = name.removeprefix("ROOT ").lstrip("%")
        scope = max((s for s in DEVICE_SCOPES if s in name), key=len,
                    default=name.rstrip(".0123456789"))
        calls[scope] = calls.get(scope, 0) + 1
    return calls


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
_ARRAY = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_DTYPE_BYTES))
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_CALLEE = re.compile(r"calls=%?([\w.-]+)")
_OPERAND = re.compile(r"[^()]*?%([\w.-]+)")     # an instruction's first
_ASYNC_FUSION = re.compile(r'custom_call_target="AsyncCollective(Start|Done)"')
# What a collective can run under: whatever the schedule gives the
# TensorCore to do meanwhile.
_WORK = ("fusion", "custom-call", "while", "convolution", "dot")


def _array_bytes(shape: str) -> list:
    """The bytes of each array in a shape as HLO prints it (a tuple's
    shape has several)."""
    return [_DTYPE_BYTES[dtype] * math.prod(int(n) for n in dims.split(",")
                                            if n)
            for dtype, dims in _ARRAY.findall(shape)]


def _computations(compiled_text: str) -> Dict[str, list]:
    """computation -> [(instruction, its shape, opcode, operands and
    attributes)] of an HLO module's text, in the order printed: for a
    scheduled module (`is_scheduled=true`) the order it runs in."""
    computations, body = {}, None
    for line in compiled_text.splitlines():
        if line.startswith("}"):
            body = None
        elif not line.startswith(" "):
            head = re.match(r"(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$", line)
            body = computations.setdefault(head.group(1), []) if head else None
        elif body is not None:
            name, eq, rest = line.strip().partition(" = ")
            op = _OPCODE.search(rest) if eq else None
            if op:
                body.append((name.removeprefix("ROOT ").lstrip("%"),
                             rest[:op.start()], op.group(1), rest[op.end():]))
    return computations


def scatter_calls(compiled_text: str) -> Dict[str, int]:
    """The scatter instructions of a compiled program, counted by the
    array each scatters into as HLO prints it without its layout
    (`f32[32768,2048]`): a static counter like `kernel_calls`, read from
    `jitted.lower(...).compile().as_text()` and not from a run. A scatter
    inside a fusion counts, one in a loop's body counts once.

    What it is for: which way a held share's rows go back to their tokens
    (parallel/moe.py `HeldRowsPlan.gathered`). A layer of `held_moe_layer`
    that scatters them holds two scatters into float32 [T, d] a step, the
    forward's combine and the backward's transpose of the dispatch, and
    one that gathers them holds none: LFM2-8B-A1B's step of four expert
    layers 8 into `f32[32768,2048]` before PR 49 and 0 since,
    Nemotron-3-Nano's 8 into `f32[16384,2688]` before and since (PERF.md
    section 6, PR 49). What else a step scatters stays: the embedding's
    gradient into the table, the router's k weights' gradients into [T *
    E] scores, the grouped matmuls' tile counts into `s32[...]`."""
    calls: Dict[str, int] = {}
    for body in _computations(compiled_text).values():
        for _, shape, op, _ in body:
            if op == "scatter":
                into = _ARRAY.search(shape).group(0)
                calls[into] = calls.get(into, 0) + 1
    return calls


def collective_calls(compiled_text: str) -> Dict[str, Any]:
    """The collectives of a compiled program and where they stand in its
    schedule: a static counter like `kernel_calls`, read from
    `jitted.lower(...).compile().as_text()` and not from a run.

    `collectives` has one entry a collective, in schedule order: `name`,
    `kind` (one of COLLECTIVES), `operands` (each operand's shape as
    printed, without its layout), `bytes` (theirs together), `async` (a
    `-start` / `-done` pair, or XLA:TPU's `async-collective-start` /
    `-done` fusions round the collective), `between` (the fusions, custom
    calls, loops, convolutions and dots scheduled between its start and
    its done: what it can run under; 0 for a blocking one) and `kernels`
    (the Mosaic calls among them, by instruction name). A collective in a
    loop's body counts once.

    From them two numbers for a data-parallel train step:
    `gradient_reduce_bytes`, the bytes the program's all-reduces take in
    (gpt2-small under dp=4: 324 MB while the tied table's lookup and head
    halves crossed the chips apart, 247 MB, the parameters' own bytes,
    since they are added first), and `async_share`, the share of those
    bytes whose reduce is asynchronous with work in between (0.0 where
    every gradient is reduced by a blocking instruction and the
    TensorCore waits for each). On the chip
    `collective_exposed_ms_per_step` reads the result (PERF.md §3)."""
    computations = _computations(compiled_text)
    # XLA:TPU's async collective: two fusions whose fused computations
    # hold the collective itself and an AsyncCollectiveStart / -Done, and
    # between them `async_collective_fusion`s: compute that carries the
    # collective on, its instruction standing in each of them again.
    fused = {m.group(1) for body in computations.values()
             for _, _, op, rest in body
             if op == "fusion" and (m := _CALLEE.search(rest))}
    wrapped = {}              # such a fused computation -> (its part, kind)
    for comp in fused & computations.keys():
        body = computations[comp]
        kind = next((op for _, _, op, _ in body if op in COLLECTIVES), None)
        if kind:
            wrapped[comp] = (next((m.group(1) for *_, rest in body if (
                m := _ASYNC_FUSION.search(rest))), "Carry"), kind)

    def async_half(op, rest):
        callee = _CALLEE.search(rest) if op == "fusion" else None
        return wrapped.get(callee.group(1) if callee else None, (None, None))

    found = []
    for comp, body in computations.items():
        if comp in wrapped:
            continue
        shapes = {name: shape for name, shape, _, _ in body}
        element_of = {}       # a start's tuple element -> the start
        started = {}          # a start -> (its entry, its place)
        for at, (name, _, op, rest) in enumerate(body):
            args = re.findall(r"%([\w.-]+)", rest.split(")", 1)[0])
            half, inner = async_half(op, rest)
            belongs = next((s for s in (element_of.get(a, a) for a in args)
                            if s in started), None)
            if belongs and (op == "get-tuple-element" or half == "Carry"):
                element_of[name] = belongs
            kind = inner if half == "Start" else next(
                (c for c in COLLECTIVES if op in (c, c + "-start")), None)
            if kind:
                operands = [re.sub(r"\{[^{}]*\}", "", shapes.get(a, "")).strip()
                            for a in args]
                entry = {"name": name, "kind": kind, "operands": operands,
                         "bytes": sum(sum(_array_bytes(shape))
                                      for shape in operands),
                         "async": False, "between": 0, "kernels": []}
                found.append(entry)
                if op != kind:
                    started[name] = (entry, at)
            elif belongs and (half == "Done" or op.endswith("-done")):
                entry, began = started.pop(belongs)
                work = [(n, r) for n, _, o, r in body[began + 1:at]
                        if o in _WORK
                        and async_half(o, r)[0] in (None, "Carry")]
                entry.update({"async": True, "between": len(work), "kernels": [
                    n for n, r in work if '"tpu_custom_call"' in r]})
    reduces = [c for c in found if c["kind"] == "all-reduce"]
    total = sum(c["bytes"] for c in reduces)
    hidden = sum(c["bytes"] for c in reduces if c["async"] and c["between"])
    return {"collectives": found, "gradient_reduce_bytes": total,
            "async_share": hidden / total if total else 0.0}


# Instructions that name an array and write none: views and selections of
# what another instruction made.
_NO_WRITE = ("get-tuple-element", "tuple", "bitcast", "reshape", "parameter",
             "constant")
_ENTRY = re.compile(r"^ENTRY %?([\w.-]+)", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_writes(compiled_text: str, scope: str) -> Dict[str, Any]:
    """What a scope's instructions write: a static counter like
    `kernel_calls`, read from `jitted.lower(...).compile().as_text()` and
    not from a run. For work that is no kernel but XLA's own fusions under
    a `jax.named_scope`, the bytes the compiler chose to put through HBM
    are the first thing to read, before a chip run.

    `writes` has one entry for each instruction of the entry computation
    whose `op_name` holds `scope` and that makes an array of its own (a
    fusion, a convert, a reduce, a copy; not the `get-tuple-element`s,
    bitcasts and reshapes that name a part or a view of one): `name`,
    `opcode`, and `results`, the bytes of each array it writes (a
    multi-output fusion has several). `instructions` is their count and
    `bytes` the sum. An instruction inside a loop's body or a fused
    computation is not an instruction of the entry computation: a fusion
    counts once, by what leaves it.

    What it is for: `ssm_conv`, the causal depthwise convolution and its
    silu (ops/layers.py `causal_conv1d_silu`), in granite-4.0-h-micro's
    step of nine Mamba-2 layers at [1, 16384, 4352]: 108 instructions and
    11.55 GB a step while autodiff derived its backward pass (one fusion a
    layer wrote the cotangent times each tap as four 143 MB arrays), 90
    and 6.42 GB since the rule is written by hand (PERF.md §6, PR 34)."""
    entry = _ENTRY.search(compiled_text)
    body = _computations(compiled_text)[entry.group(1)] if entry else []
    writes = []
    for name, shape, op, rest in body:
        held = _OP_NAME.search(rest)
        if op in _NO_WRITE or not held or not re.search(
                r"\b%s\b" % re.escape(scope), held.group(1)):
            continue
        writes.append({"name": name, "opcode": op,
                       "results": _array_bytes(shape)})
    return {"instructions": len(writes), "writes": writes,
            "bytes": sum(sum(w["results"]) for w in writes)}


# jax's wrappers round a component of an `op_name` path: `transpose(jvp(
# layers))` is the scope `layers` under two of them. `jit(f)` and `pjit(f)`
# hold a function's name, which is no scope.
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")
_FUNCTION_WRAPPERS = ("jit", "pjit")
# Scopes that say which program or stack an operation is in, not which
# branch of a block: time under these alone is not covered.
_NOT_A_BOUNDARY = ("layers", "prefill", "decode")
_PASSES = ("forward", "remade", "backward")


def scope_path(op_name: str) -> tuple:
    """(the DEVICE_SCOPES names of an `op_name` path, outermost first; the
    pass). jax's wrappers are unwrapped (`jvp(...)`, `transpose(...)`,
    `vmap(...)`; the plain components `checkpoint`, `rematted_computation`,
    `while/body`, `cond/branch_*`, `shard_map`, `custom_vjp_call`,
    `closed_call` and every primitive's name are no scopes and fall out).
    The pass is `remade` under a rematerialised block's second run (a
    `rematted_computation` component), else `backward` under a
    `transpose(...)`, else `forward`. Under `jax.checkpoint` the block's
    own path starts again after the call's (`transpose(jvp(layers))/
    jvp(layers)/checkpoint/...`): a path whose beginning repeats at once
    counts it once, so `layers/layers/x` is `layers/x`, while a kernel's
    scope inside its rule's (`x/ssm_scan_bwd/ssm_scan_bwd`) stays two."""
    names, transposed, remade = [], False, False
    for part in op_name.split(";")[0].split("/"):
        inner = True
        while (m := _WRAPPED.match(part)):
            transposed |= m.group(1) == "transpose"
            inner &= m.group(1) not in _FUNCTION_WRAPPERS
            part = m.group(2)
        remade |= part == "rematted_computation"
        if inner and part in DEVICE_SCOPES:
            names.append(part)
    for n in range(len(names) // 2, 0, -1):
        if names[:n] == names[n:2 * n]:
            del names[:n]
            break
    return tuple(names), ("remade" if remade else
                          "backward" if transposed else "forward")


def _instruction(event_name: str) -> tuple:
    """(instruction name, opcode, result shape without layouts) of a device
    event named, as the v5e names them, by its whole HLO instruction."""
    name, eq, rest = event_name.partition(" = ")
    op = _OPCODE.search(rest) if eq else None
    if op is None:
        return name.lstrip("%"), name.lstrip("%"), ""
    return (name.lstrip("%"), op.group(1),
            re.sub(r"\{[^{}]*\}", "", rest[:op.start()]).strip())


def _self_times(events) -> list:
    """Each event's duration less what events inside it cover: at every
    instant the time is the latest-started event's that is still open, so
    a `while` does not count its body twice and the self times sum to the
    union of the intervals whatever overlaps."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][2], -events[i][3]))
    own, open_, now = [0.0] * len(events), [], 0.0
    for i in [*order, None]:
        start = float("inf") if i is None else events[i][2]
        while open_:
            top = open_[-1]
            end = events[top][2] + events[top][3]
            if end > start:
                own[top] += max(0.0, start - now)
                break
            own[top] += max(0.0, end - now)
            now = max(now, end)
            open_.pop()
        now = max(now, start) if open_ else start
        open_.append(i)
    return own


def mixed_fusions(compiled_text: str) -> Dict[str, list]:
    """fusion instruction -> the scope paths its fused computation holds,
    for the fusions that hold more than one: XLA fused work of two scopes
    and the row can stand under one name only. Instructions with no
    `op_name` (parameters, constants) and those under no scope of ours (a
    cast of the weights under `jit(train_step)` alone) say nothing: one
    scope's work with such an instruction beside it is not flagged."""
    return _mixed(_computations(compiled_text))


def _mixed(computations: Dict[str, list]) -> Dict[str, list]:
    found = {}
    for body in computations.values():
        for name, _, op, rest in body:
            callee = _CALLEE.search(rest) if op == "fusion" else None
            inside = {"/".join(scope_path(m.group(1))[0])
                      for *_, r in computations.get(
                          callee.group(1) if callee else "", ())
                      if (m := _OP_NAME.search(r))} - {""}
            if len(inside) > 1:
                found[name] = sorted(inside)
    return found


def by_scope(events, steps: int,
             compiled_text: Optional[str] = None) -> Dict[str, Any]:
    """A device trace's time by the program's own names. `events` are one
    plane's "XLA Ops" events (name, tf_op, start_ns, duration_ns), as
    `read_device_events` gives them; `steps` the runs of the step's program
    they span (`step_events` counts them); `compiled_text` the step's
    `compile().as_text()` where the caller has it. Plain arithmetic, no
    file format. Times in ms a step.

        steps, busy_ms_per_step    busy: the union of the events' intervals
        coverage                   share of busy time under a scope that
                                   names a branch of a block or finer
                                   (anything but `layers` alone)
        scopes   {path: {forward_ms, remade_ms, backward_ms, calls,
                  mixed_ms}}       `path` the event's DEVICE_SCOPES names
                                   joined by "/", outermost first
                                   (`scope_path`); `calls` events a step
        unscoped [{opcode, shape, ms, calls, after}]: events with no
                                   `op_name` or none of whose components
                                   is a known name, by opcode and result
                                   shape; the twenty largest, then one row
                                   "(rest)". `after` (with `compiled_text`)
                                   is the path of the instruction that made
                                   the row's first operand, where most of
                                   its time has one: whose output XLA's own
                                   `copy` lays out again

    Every event counts its SELF time (`_self_times`): the rows, scoped and
    unscoped, sum to busy exactly, which is asserted. An event with no
    `tf_op` takes its instruction's `op_name` from `compiled_text`. A
    fusion that `mixed_fusions` flags counts under the scope its own
    `op_name` gives and again under that scope's `mixed_ms`: how much of a
    row may be another scope's work, with no time split by guesswork."""
    events = [e for e in events if e[3] > 0]
    own = _self_times(events)
    busy = 0.0
    covered_to = float("-inf")
    for _, _, start, duration in sorted(events, key=lambda e: e[2]):
        if start + duration > covered_to:
            busy += start + duration - max(start, covered_to)
            covered_to = start + duration
    names, fed_by, mixed = {}, {}, {}
    if compiled_text:
        computations = _computations(compiled_text)
        mixed = _mixed(computations)
        for body in computations.values():
            for name, _, _, rest in body:
                if (m := _OP_NAME.search(rest)):
                    names[name] = m.group(1)
                if (m := _OPERAND.match(rest)):
                    fed_by[name] = m.group(1)

    def after(instruction):
        """The path of what made the first operand, a few hops back."""
        for _ in range(4):
            instruction = fed_by.get(instruction)
            path = scope_path(names.get(instruction, ""))[0]
            if path or instruction is None:
                return "/".join(path)
        return ""
    per = 1e-6 / max(1, steps)
    scopes, unscoped, covered = {}, {}, 0.0
    for (name, tf_op, _, _), mine in zip(events, own):
        instruction, opcode, shape = _instruction(name)
        path, which = scope_path(tf_op or names.get(instruction, ""))
        if not path:
            row = unscoped.setdefault((opcode, shape), [0.0, 0, {}])
            row[0] += mine
            row[1] += 1
            if fed_by:
                made = after(instruction)
                row[2][made] = row[2].get(made, 0.0) + mine
            continue
        row = scopes.setdefault("/".join(path), {
            **{p + "_ms": 0.0 for p in _PASSES}, "calls": 0, "mixed_ms": 0.0})
        row[which + "_ms"] += mine * per
        row["calls"] += 1
        if instruction in mixed:
            row["mixed_ms"] += mine * per
        if set(path) - set(_NOT_A_BOUNDARY):
            covered += mine
    total = sum(own)
    assert abs(total - busy) <= 1e-6 * max(busy, 1.0), (total, busy)
    for row in scopes.values():
        row["calls"] /= max(1, steps)
    ranked = sorted(unscoped.items(), key=lambda kv: -kv[1][0])
    listed = [{"opcode": op, "shape": shape, "ms": ns * per,
               "calls": n / max(1, steps),
               "after": max(made, key=made.get, default="")}
              for (op, shape), (ns, n, made) in ranked[:20]]
    if ranked[20:]:
        listed.append({"opcode": "(rest)", "shape": "",
                       "ms": sum(row[0] for _, row in ranked[20:]) * per,
                       "calls": sum(row[1] for _, row in ranked[20:])
                       / max(1, steps), "after": ""})
    return {"steps": steps, "busy_ms_per_step": busy * per,
            "coverage": covered / busy if busy else 0.0,
            "scopes": dict(sorted(scopes.items())), "unscoped": listed}


def _varint(buf, at: int) -> tuple:
    """(the varint at buf[at:], the index behind it)."""
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf, at: int, end: int):
    """(field number, value) of the protobuf message buf[at:end]: an int
    for a varint or fixed field, (start, end) for a length-delimited one.
    The wire format alone, so that reading a trace needs no protobuf
    package and no TensorFlow beside jax."""
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value = (at, at + size)
            at += size
        else:
            size = 8 if kind == 1 else 4
            value = int.from_bytes(buf[at:at + size], "little")
            at += size
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def read_device_events(xplane_path: str) -> Dict[str, Any]:
    """{"plane", "planes", "events", "modules"} of an `.xplane.pb`: the
    first device plane's "XLA Ops" events as (name, tf_op, start_ns,
    duration_ns) and its "XLA Modules" events as (name, start_ns,
    duration_ns); `planes` names every device plane of the file.
    The only function here that knows the file's format (xplane.proto's
    XSpace > XPlane > XLine > XEvent, each event naming an XEventMetadata
    of its plane).

    On the v5e an operation's name is its whole HLO instruction and its
    metadata carries a stat `tf_op` (or `hlo_op`), the instruction's
    `op_name` with `:` and a type behind it: the path `by_scope` reads.
    jax.profiler.ProfileData shows an event's own stats and not its
    metadata's, which is why this reads the bytes (tests/test_by_scope.py
    holds that on a recorded trace: once it fails, read through
    ProfileData and delete the parser). A trace taken on another backend
    holds no "/device:TPU:n" plane and gives no events."""
    with open(xplane_path, "rb") as f:
        buf = f.read()
    found = {}
    for number, span in _fields(buf, 0, len(buf)):
        if number == 1:                                   # XSpace.planes
            name = next((_text(buf, v) for n, v in _fields(buf, *span)
                         if n == 2), "")
            if name.startswith("/device:TPU:"):
                found[name] = span
    out = {"plane": None, "planes": sorted(found), "events": [],
           "modules": []}
    if not found:
        return out
    chosen = out["plane"] = out["planes"][0]
    lines, metadata, stat_names = [], {}, {}
    for number, span in _fields(buf, *found[chosen]):
        if number == 3:
            lines.append(span)
        elif number in (4, 5):                            # a map's entry
            entry = dict(_fields(buf, *span))
            (metadata if number == 4 else stat_names)[entry.get(1, 0)] = \
                entry.get(2)

    def stat_name(stat_id):
        span = stat_names.get(stat_id)
        return next((_text(buf, v) for n, v in _fields(buf, *span)
                     if n == 2), "") if span else ""

    @functools.cache
    def described(metadata_id):
        """(name, tf_op) of an XEventMetadata."""
        span, name, tf_op = metadata.get(metadata_id), "", ""
        for number, value in _fields(buf, *span) if span else ():
            if number == 2:
                name = _text(buf, value)
            elif number == 5:                             # its stats
                stat = dict(_fields(buf, *value))
                if stat_name(stat.get(1)) in ("tf_op", "hlo_op") \
                        and not tf_op:
                    tf_op = _text(buf, stat[5]) if 5 in stat \
                        else stat_name(stat.get(7))
        return name, tf_op.rsplit(":", 1)[0]

    for span in lines:
        line = list(_fields(buf, *span))
        name = next((_text(buf, v) for n, v in line if n == 2), "")
        if name not in ("XLA Ops", "XLA Modules"):
            continue
        zero_ns = next((v for n, v in line if n == 3), 0)
        for number, value in line:
            if number != 4:
                continue
            event = dict(_fields(buf, *value))
            start = zero_ns + event.get(2, 0) / 1e3
            duration = event.get(3, 0) / 1e3
            what, tf_op = described(event.get(1, 0))
            if name == "XLA Ops":
                out["events"].append((what, tf_op, start, duration))
            else:
                out["modules"].append((what, start, duration))
    return out


def step_events(events, modules) -> tuple:
    """(the events that ran inside a whole run of the step's program, the
    runs counted): the step's program is the "XLA Modules" name with the
    most device time. A capture that starts or ends inside a run keeps
    the run clipped to what it saw, so a run is whole where it lasts nine
    tenths of the median run and its events span nine tenths of it (the
    device is busy all through a step). What ran under another program
    (an initialisation, an evaluation between steps) is left out. With no
    modules (a hand-made list) everything is one run."""
    if not modules:
        return list(events), 1
    seconds = {}
    for name, _, duration in modules:
        seconds[name] = seconds.get(name, 0.0) + duration
    step = max(seconds, key=seconds.get)
    runs = sorted((start, start + duration)
                  for name, start, duration in modules if name == step)
    held, at = [[] for _ in runs], 0
    for event in sorted(events, key=lambda e: e[2]):
        while at < len(runs) and runs[at][1] <= event[2]:
            at += 1
        if at < len(runs) and runs[at][0] <= event[2]:
            held[at].append(event)
    lengths = sorted(end - start for start, end in runs)
    least = 0.9 * lengths[len(lengths) // 2]
    whole = [inside for (start, end), inside in zip(runs, held)
             if inside and end - start >= least
             and max(e[2] + e[3] for e in inside) - inside[0][2]
             >= 0.9 * (end - start)]
    return [e for inside in whole for e in inside], len(whole)


def format_by_scope(table: Dict[str, Any]) -> str:
    """`by_scope`'s result as the lines `ray_tpu profile --by-scope`
    prints: a row a scope path, largest first, then the unscoped rows."""
    busy = table["busy_ms_per_step"] or 1.0
    lines = [f"{table['steps']} step(s), {table['busy_ms_per_step']:.3f} ms "
             f"busy a step, {100 * table['coverage']:.2f}% under a block's "
             f"branch or finer",
             f"{'forward':>10} {'remade':>10} {'backward':>10} {'share':>7} "
             f"{'mixed':>9} {'calls':>8}  scope (ms a step)"]
    total = lambda r: sum(r[p + "_ms"] for p in _PASSES)   # noqa: E731
    for path, r in sorted(table["scopes"].items(),
                          key=lambda kv: -total(kv[1])):
        lines.append(
            f"{r['forward_ms']:10.3f} {r['remade_ms']:10.3f} "
            f"{r['backward_ms']:10.3f} {100 * total(r) / busy:6.2f}% "
            f"{r['mixed_ms']:9.3f} {r['calls']:8.1f}  {path}")
    for r in table["unscoped"]:
        lines.append(f"{r['ms']:32.3f} {100 * r['ms'] / busy:6.2f}% "
                     f"{'':9} {r['calls']:8.1f}  unscoped: {r['opcode']} "
                     f"{r['shape']}"
                     + (f" (after {r['after']})" if r["after"] else ""))
    return "\n".join(lines)


# The worker-level actor method behind profile_actor: any actor's worker
# answers it on a thread of its own (_private/worker_proc.py).
PROFILE_METHOD = "__ray_tpu_profile__"

_NO_SPAN = contextlib.nullcontext()
_trace_annotation = None


def annotate(name: str):
    """Host span on the profiler's clock: `with annotate(name): ...`.
    Names come from HOST_SPANS. Never imports jax: where the process has
    not (a driver, the benchmark's parent) there is no profiler to write
    to and the span is nothing."""
    global _trace_annotation
    if _trace_annotation is None:
        # getattr twice: another thread may be in the middle of importing
        # jax, and a module half imported has no `profiler` yet.
        span = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                       "TraceAnnotation", None)
        if span is None:
            return _NO_SPAN
        _trace_annotation = span
    return _trace_annotation(name)


# jax.monitoring's time spans of a program's build, by the phase each is.
_BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
# ... and its events inside the last: what the persistent cache said.
_CACHE_ANSWERS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class CompileLog:
    """Every program a process builds, one entry a phase:

        {"fun", "phase": "trace" | "lower" | "compile", "start", "end",
         "cache": "hit" | "miss" | None}

    `fun` is jax's name for the jitted function, `start` / `end` are
    `time.time()` (the clock of util/tracing.py's spans and, through
    `profile_start_unix_ns`, of a device trace). `trace` is Python
    running the function into a jaxpr and `lower` the jaxpr into
    StableHLO: no cache saves either. `compile` is XLA building the
    executable or the persistent cache answering: `cache` says which
    (`"hit"`: the key was found, read, deserialised and loaded; `"miss"`:
    XLA compiled and the entry was written; None: the cache was not
    asked, or XLA compiled too quickly for jax to keep the program,
    `jax_persistent_cache_min_compile_time_secs`).

    A function traced while another is traced or lowered (jax.numpy's
    own jitted functions, a kernel's body: 3,669 of them in one 8-layer
    train step) is part of the outer entry and has none of its own.
    Where threads interleave, or a program was compiled meanwhile, an
    inner interval can stay: sum over the union of the intervals.

    Always on once listening: an entry costs a dict and an append, and
    there are three a program built, none a step. What a thread logs
    between two of its `compile` entries is its open run: any of it may
    yet turn out to lie inside a trace that has not ended (5,083 entries
    in one train step). A run stands, in the log for good, at its
    thread's next `compile` or once its thread has gone. The log keeps
    the newest `keep` entries that stand and as many again of each open
    run, so a run that outgrows `keep` gives up its own oldest entry,
    never an earlier program's; `dropped` counts what stood and went,
    and what a run gave up once it stands (the outer entry that swallows
    a run takes the given-up ones with it uncounted). `programs_built`
    counts every `compile` entry ever, dropped ones too."""

    def __init__(self, keep: int = 4096):
        self._keep = keep
        # entries that stand, in the order their runs stood
        self._entries: collections.deque = collections.deque()
        # thread -> its `trace` / `lower` entries since its last `compile`:
        # disjoint, and each may yet turn out to lie inside a later one
        self._runs: Dict[int, collections.deque] = {}
        # thread -> [entries its run gave up, the first one's start]
        self._given_up: Dict[int, list] = {}
        self._lock = threading.Lock()
        self._cache_said: Dict[int, str] = {}   # thread -> hit | miss
        self._listening = False
        self.dropped = 0
        self.programs_built = 0

    def _stand(self, thread: int, *more):
        """A thread's open run (and `more`) can be swallowed no longer."""
        self._entries.extend(self._runs.pop(thread, ()))
        self._entries.extend(more)
        self.dropped += self._given_up.pop(thread, (0, 0.0))[0]
        while len(self._entries) > self._keep:
            self._entries.popleft()
            self.dropped += 1

    def on_time_span(self, event: str, start: float, end: float, **kw):
        phase = _BUILD_PHASES.get(event)
        if phase is None:
            return
        me = threading.get_ident()
        entry = {"fun": kw.get("fun_name"), "phase": phase,
                 "start": start, "end": end, "cache": None}
        with self._lock:
            if phase == "compile":
                # The answer belongs to the compile entry of the same
                # thread that closes next: this one. No trace runs on
                # while its program compiles, so the run stands.
                entry["cache"] = self._cache_said.pop(me, None)
                self.programs_built += 1
                self._stand(me, entry)
                return
            run = self._runs.setdefault(me, collections.deque())
            # What began inside this one is part of it.
            while run and run[-1]["start"] >= start:
                run.pop()
            gone = self._given_up.get(me)
            if gone and gone[1] >= start:
                del self._given_up[me]
            run.append(entry)
            if len(run) > self._keep:
                gone = self._given_up.setdefault(me, [0, run[0]["start"]])
                gone[0] += 1
                run.popleft()

    def on_event(self, event: str, **kw):
        answer = _CACHE_ANSWERS.get(event)
        if answer is not None:
            with self._lock:
                self._cache_said[threading.get_ident()] = answer

    def entries(self) -> List[dict]:
        with self._lock:
            alive = {t.ident for t in threading.enumerate()}
            for thread in [t for t in self._runs if t not in alive]:
                self._stand(thread)
            held = [*self._entries, *(e for run in self._runs.values()
                                      for e in run)]
        return [dict(entry) for entry in sorted(held, key=lambda e: e["end"])]

    def listen(self) -> bool:
        """Register with jax.monitoring, once; False where this process
        has not imported jax (nothing is imported here: importing jax is
        the caller's decision, and starts no runtime)."""
        if not self._listening:
            # getattr: another thread may be half way through importing jax.
            monitoring = sys.modules.get("jax.monitoring")
            spans = getattr(monitoring,
                            "register_event_time_span_listener", None)
            events = getattr(monitoring, "register_event_listener", None)
            if spans is None or events is None:
                return False
            with self._lock:
                if not self._listening:
                    self._listening = True
                    spans(self.on_time_span)
                    events(self.on_event)
        return True


# The one log of this process (jax.monitoring's listeners are the
# process's): TrainWorker.run starts it before the user's loop,
# telemetry.flush_device_gauges at a worker's metrics push.
COMPILES = CompileLog()


def compile_log() -> List[dict]:
    """The programs this process has built since it began listening (it
    begins here if it has not), in the order their phases ended:
    `CompileLog`'s entries.
    `COMPILES.dropped` and `COMPILES.programs_built` hold the counts."""
    COMPILES.listen()
    return COMPILES.entries()


def default_logdir() -> str:
    """Session-scoped trace dir (driver) or a /tmp fallback."""
    from .._private import state
    rt = state.current_or_none()
    base = getattr(rt, "session_dir", None) if rt is not None else None
    if base is None:
        base = os.path.join(tempfile.gettempdir(), "ray_tpu_profiles")
    return os.path.join(base, "profiles")


@dataclasses.dataclass
class Capture:
    """What `capture` hands its block; filled in when the block ends."""
    logdir: str
    start_unix_ns: int = 0          # just before the profiler started
    xplane: Optional[str] = None    # the .xplane.pb, once stopped
    stop_s: Optional[float] = None  # how long stop_trace took


@contextlib.contextmanager
def capture(logdir: Optional[str] = None):
    """Profile this process's device and host for the enclosed block.

        with profiling.capture("/tmp/tb") as cap:
            state, _ = train_step(state, batch)
            jax.block_until_ready(state)
        cap.xplane   # .../plugins/profile/<time>/<host>.xplane.pb

    The Python tracer is off and the host tracer at 1: TraceAnnotation
    spans and little else. With both up, traces of this program were
    24-212 MB and took minutes to stop (PERF.md, PR 22). One capture
    per process at a time: a second start raises."""
    import jax

    logdir = logdir or default_logdir()
    os.makedirs(logdir, exist_ok=True)
    before = set(_xplanes(logdir))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    cap = Capture(logdir, time.time_ns())
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield cap
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        cap.stop_s = time.perf_counter() - t0
        new = sorted(set(_xplanes(logdir)) - before)
        cap.xplane = new[-1] if new else None


def profile_start_unix_ns(xplane: str) -> int:
    """Unix nanoseconds of a trace's zero: add an event's `start_ns` (as
    jax.profiler.ProfileData gives it) to place it on time.time_ns()'s
    clock. Reads the whole file."""
    import jax

    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if plane.name == "Task Environment":
            for stat in plane.stats:
                if stat[0] == "profile_start_time":
                    return int(stat[1])
    raise ValueError(f"{xplane} holds no profile_start_time")


def _xplanes(logdir: str):
    return glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def capture_for(seconds: float) -> Dict[str, Any]:
    """Profile this process for `seconds` and return the trace's bytes:
    what a worker runs for PROFILE_METHOD, on a thread of its own, so
    the actor keeps serving meanwhile."""
    with tempfile.TemporaryDirectory(prefix="ray_tpu_profile_") as tmp:
        with capture(tmp) as cap:
            time.sleep(max(0.0, float(seconds)))
        if cap.xplane is None:
            raise RuntimeError("the profiler wrote no trace")
        with open(cap.xplane, "rb") as f:
            data = f.read()
    return {"xplane": data, "pid": os.getpid(),
            "start_unix_ns": cap.start_unix_ns, "stop_s": cap.stop_s}


def remote_capture(actor, seconds: float) -> Dict[str, Any]:
    """`capture_for(seconds)` in the process that hosts `actor`, by its
    worker, on a thread that is none of the actor's request threads:
    its own methods keep running, and an actor whose threads are all
    taken (a TrainWorker inside its loop) still answers."""
    from .. import api

    return api.get(actor._actor_method_call(
        PROFILE_METHOD, (float(seconds),), {}, {}))


def profile_actor(actor, seconds: float,
                  logdir: Optional[str] = None) -> Dict[str, Any]:
    """Have the process that hosts `actor` (a Serve replica, a
    TrainWorker: anything that owns a chip) profile itself for
    `seconds`, and write the trace under `logdir` here. Returns
    {"path", "bytes", "pid", "start_unix_ns", "stop_s"}."""
    out = remote_capture(actor, seconds)
    data = out.pop("xplane")
    logdir = logdir or default_logdir()
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(
        logdir, f"pid{out['pid']}_{out['start_unix_ns']}.xplane.pb")
    with open(path, "wb") as f:
        f.write(data)
    out.update(path=path, bytes=len(data))
    return out
