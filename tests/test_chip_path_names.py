"""The chip path's names on the profiler's clock (util/profiling.py).

Every name in HOST_SPANS is emitted by its site into a `capture`, the
lowered train step carries DEVICE_SCOPES and still the kernels'
`kernel_name`s, the engine's always-on counters add up, a profile can be
taken by the process that hosts an actor while the actor keeps serving,
and the LLM reply's `timing` is additive. CPU backend: the host plane is
what is checked here, the device plane only on the chip (PERF.md)."""

import asyncio
import dataclasses
import glob
import os
import re
import subprocess
import sys
import threading
import time

import cloudpickle
import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models import (GPTConfig, HybridConfig, Lfm2MoeConfig,
                            LlamaConfig, MoEConfig, gpt_init,
                            make_hybrid_train_step, make_lfm2_moe_train_step,
                            make_llama_train_step, make_moe_train_step,
                            make_nemotron_h_train_step,
                            make_olmo_hybrid_train_step,
                            make_sambay_train_step, make_train_step)
from ray_tpu.models.nemotron_h import NemotronHConfig
from ray_tpu.models.olmo_hybrid import OlmoHybridConfig
from ray_tpu.models.sambay import SambaYConfig
from ray_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                          make_glm4_moe_lite_train_step)
from ray_tpu.models.keye_vl2 import KeyeVL2Config, make_keye_vl2_train_step
from ray_tpu.models.bailing_hybrid import (BailingHybridConfig,
                                           make_bailing_hybrid_train_step)
from ray_tpu.models.xing4 import Xing4Config, make_xing4_train_step
from ray_tpu.util import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_PATH = ("ops", "models", "parallel", "llm", "train", "data")


def ray_tpu_spans(xplane: str) -> dict:
    """{name: [(start_ns, duration_ns), ...]} of the `ray_tpu.*` spans on
    the host plane, plus "$python": the Python tracer's events."""
    out = {}
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                key = e.name if e.name.startswith("ray_tpu.") else (
                    "$python" if e.name.startswith("$") else None)
                if key:
                    out.setdefault(key, []).append(
                        (e.start_ns, e.duration_ns))
    return out


@pytest.fixture(scope="module")
def small_setup():
    cfg = GPTConfig(vocab_size=272, d_model=64, n_heads=4, n_layers=2,
                    d_ff=128, max_seq_len=256)
    return cfg, gpt_init(jax.random.PRNGKey(7), cfg)


def _run_engine(cfg, params, prompts, n_tokens):
    """A whole engine life: requests in, streams out, loop ended."""
    from ray_tpu.llm.continuous import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(cfg=cfg, params=params, max_batch=2)
    t0 = time.perf_counter()
    streams = [eng.submit(p, n_tokens, 0.0) for p in prompts]
    texts = ["".join(s) for s in streams]
    deadline = time.time() + 10
    while not eng.phase_n["idle"] and time.time() < deadline:
        time.sleep(0.01)    # the loop goes idle once the slots empty
    eng.close()
    eng._thread.join(timeout=10)
    assert not eng._thread.is_alive()
    return eng, streams, texts, time.perf_counter() - t0


class _Doubler:
    def __call__(self, batch):
        return {"id": batch["id"] * 2}


def _one_report_loop():
    from ray_tpu import train
    train.report({"loss": 1.0})


@pytest.fixture(scope="module")
def spans_seen(small_setup, tmp_path_factory):
    """One capture in this process over the cluster's start, a tiny
    engine run, a tiny iter_jax_batches, a one-worker JaxTrainer job (its
    `fit` and `start_group` run here), a TrainWorker's whole life run in
    this process as if it held a chip (its four run spans, a report and
    the poll) and a replica's handler; one taken by a map_batches actor's
    worker of itself. The cluster is this fixture's: `ray_tpu.init` has
    to happen under the capture."""
    from ray_tpu import data as rd
    from ray_tpu.data.dataset import _MapBatchesActorPool
    from ray_tpu.serve._private.replica import Replica
    from ray_tpu.train import (JaxBackendConfig, JaxTrainer, RunConfig,
                               ScalingConfig, session)
    from ray_tpu.train.worker_group import TrainWorker

    cfg, params = small_setup
    logdir = str(tmp_path_factory.mktemp("capture"))
    ray_tpu.shutdown()
    with profiling.capture(logdir) as cap:
        ray_tpu.init(num_cpus=4)
        _run_engine(cfg, params, ["hello", "late one", "third"], 4)
        rows = sum(int(b["id"].shape[0]) for b in
                   rd.range(48).iter_jax_batches(batch_size=16))
        fitted = JaxTrainer(
            _one_report_loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="names", storage_path=str(
                tmp_path_factory.mktemp("fit")))).fit()
        worker = TrainWorker._cls()
        with pytest.MonkeyPatch.context() as patch:
            patch.setenv("TPU_VISIBLE_CHIPS", "0")
            try:
                worker.setup(session.TrainContext(), JaxBackendConfig(),
                             None)
                worker.run(_one_report_loop, None)
                polled = worker.poll()
            finally:
                session._set_session(None)
        replica = Replica(cloudpickle.dumps(lambda x: x + 1), (), {}, "d")
        handled = asyncio.run(replica.handle_request("__call__", (1,), {}))
    assert fitted.error is None and fitted.metrics == {"loss": 1.0}
    reports = polled["reports"]
    assert [s["name"].rsplit(".", 1)[1] for s in polled["timeline"]["spans"]
            ] == ["worker_setup", "backend_start", "loop", "first_report"]
    assert rows == 48 and handled == 2
    assert [r["metrics"] for r in reports] == [{"loss": 1.0}]

    pool = _MapBatchesActorPool(_Doubler, 1, 1, {}, (), {})
    actor = pool.actors[0]
    blk = {"id": jnp.arange(8).__array__()}
    ray_tpu.get(actor.apply.remote(blk, 4, "numpy", (), {}))
    got = {}
    t = threading.Thread(target=lambda: got.update(
        profiling.profile_actor(actor, 1.0, logdir)))
    t.start()
    outs = []
    while t.is_alive():
        outs.append(ray_tpu.get(actor.apply.remote(blk, 4, "numpy", (), {})))
    t.join()
    assert outs and all(list(o["id"]) == list(blk["id"] * 2) for o in outs)
    yield {"local": cap, "local_spans": ray_tpu_spans(cap.xplane),
           "remote": got, "remote_spans": ray_tpu_spans(got["path"]),
           "served_during_profile": len(outs)}
    ray_tpu.shutdown()


@pytest.mark.parametrize("name", sorted(profiling.HOST_SPANS))
def test_host_span_is_emitted_by_its_site(spans_seen, name):
    where = "remote_spans" if name == "ray_tpu.data.map_batch" \
        else "local_spans"
    assert name in spans_seen[where], sorted(spans_seen[where])


def test_capture_writes_one_xplane_with_the_python_tracer_off(spans_seen):
    cap = spans_seen["local"]
    assert glob.glob(os.path.join(cap.logdir, "**", "*.xplane.pb"),
                     recursive=True).count(cap.xplane) == 1
    assert cap.stop_s is not None and os.path.getsize(cap.xplane) > 0
    assert "$python" not in spans_seen["local_spans"]
    # Spans count from the trace's own unix zero (PERF.md: the chip's).
    zero = profiling.profile_start_unix_ns(cap.xplane)
    first = zero + min(s for s, _ in spans_seen["local_spans"]
                       ["ray_tpu.engine.decode"])
    assert cap.start_unix_ns <= zero < first < time.time_ns()
    assert first - cap.start_unix_ns < 120e9


def test_remote_profile_is_readable_and_the_actor_kept_serving(spans_seen):
    got = spans_seen["remote"]
    assert got["pid"] != os.getpid() and got["bytes"] > 0
    assert got["bytes"] == os.path.getsize(got["path"])
    assert spans_seen["served_during_profile"] >= 1
    assert len(spans_seen["remote_spans"]["ray_tpu.data.map_batch"]) >= 2


def _literals(pattern: str, under=("",)):
    found = {}
    for sub in under:
        for path in glob.glob(os.path.join(ROOT, "ray_tpu", sub, "**",
                                           "*.py"), recursive=True):
            with open(path) as f:
                for m in re.finditer(pattern, f.read()):
                    found.setdefault(m.group(1), set()).add(
                        os.path.relpath(path, ROOT))
    return found


def test_the_table_lists_exactly_the_names_the_program_emits():
    from ray_tpu.llm.continuous import PHASES
    # `annotate` itself, or a run's span or mark (util/tracing.py Run),
    # which enters one
    emitted = set(_literals(
        r'(?:\bannotate|[Rr]un(?:\(\))?\.(?:span|mark))\(\s*"([^"]+)"'))
    emitted.discard("ray_tpu.engine.")       # + one of PHASES
    emitted |= {"ray_tpu.engine." + p for p in PHASES}
    assert emitted == set(profiling.HOST_SPANS)
    assert all(re.fullmatch(r"ray_tpu(\.[a-z]+)?\.[a-z_]+", n)
               for n in profiling.HOST_SPANS)
    scopes = _literals(r'named_scope\(\s*"([^"]+)"', CHIP_PATH)
    # ... and the one scope that is no literal: a block's sequence-mixer
    # branch, named from its kind's key in decoder.MIXERS
    from ray_tpu.models import decoder
    assert _literals(r"named_scope\(\s*([^\"\s][^)]*)\)", CHIP_PATH) == {
        "MIXER_SCOPES[kind]": {"ray_tpu/models/decoder.py"}}
    assert set(decoder.MIXER_SCOPES) == {
        kind for kind, row in decoder.MIXERS.items() if row.apply}
    assert len(set(decoder.MIXER_SCOPES.values())) == 13
    assert set(scopes) | set(decoder.MIXER_SCOPES.values()) \
        == set(profiling.DEVICE_SCOPES)
    assert not set(scopes) & set(decoder.MIXER_SCOPES.values())
    # the boundaries of a block are opened in _block and decoder_hidden
    # and nowhere else
    for scope in ("channel_mixer", "embed", "final_norm"):
        assert scopes[scope] == {"ray_tpu/models/decoder.py"}


# Lines as XLA:TPU prints them (tests/test_compile_v5e_olmoe.py reads a
# whole compiled step; these are cut from one, and from PR 25's, whose
# forward kernel stood under `jvp(...)`).
_CALL = ('custom-call(%a, %b), custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={bf16[8,128]{1,0}}')
_COMPILED = "\n".join([
    "HloModule jit_train_step, is_scheduled=true",
    "  %grouped_matmul_fwd.12 = bf16[131072,1024]{1,0:T(8,128)(2,1)} " + _CALL,
    "  %grouped_matmul_fwd.13 = bf16[131072,1024]{1,0:T(8,128)(2,1)} " + _CALL,
    "  %jvp_grouped_matmul_fwd_.2 = bf16[131072,2048]{1,0} " + _CALL,
    "  %grouped_matmul_dlhs.4 = bf16[131072,2048]{1,0} " + _CALL,
    "  %flash_attention_fwd.4 = (bf16[64,4096,128]{2,1,0}, "
    "f32[64,4096,128]{2,1,0}) " + _CALL,
    "  ROOT %flash_attention_dkv.1 = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) "
    + _CALL,
    "  %ssm_scan_fwd.3 = (bf16[1,16384,4096]{2,1,0}, "
    "f32[1,64,32,128,128]{4,3,2,1,0}) " + _CALL,
    "  %ssm_scan_bwd.3 = (bf16[1,16384,4096]{2,1,0}, f32[8,128]{1,0}) "
    + _CALL,
    "  %a_kernel_of_no_scope.7 = f32[8,128]{1,0} " + _CALL,
    '  %fusion.6 = bf16[8,128]{1,0} fusion(%a), kind=kLoop, calls=%fused',
    '  %custom-call.3 = f32[8]{0} custom-call(%a), '
    'custom_call_target="Sharding"',
])


def test_kernel_calls_counts_mosaic_calls_by_scope():
    calls = profiling.kernel_calls(_COMPILED)
    assert calls == {"grouped_matmul_fwd": 3, "grouped_matmul_dlhs": 1,
                     "flash_attention_fwd": 1, "flash_attention_dkv": 1,
                     "ssm_scan_fwd": 1, "ssm_scan_bwd": 1,
                     "a_kernel_of_no_scope": 1}
    assert sum(calls.values()) == _COMPILED.count('"tpu_custom_call"')
    assert profiling.kernel_calls("") == {}


# Scatters as XLA:TPU prints them, cut from LFM2-8B-A1B's step before PR 49
# (tests/test_compile_v5e_lfm2moe.py reads the whole step since): a layer's
# rows scattered into its tokens' inside a fusion of a loop's body, the
# embedding's gradient in the entry, a grouped matmul's tile counts.
_SCATTERS = """HloModule jit_train_step, is_scheduled=true

%fused_computation.12 (param_0.1: f32[32768,2048], param_1.1: s32[73728,1], param_2.1: f32[73728,2048]) -> f32[32768,2048] {
  %param_0.1 = f32[32768,2048]{1,0:T(8,128)} parameter(0)
  %param_1.1 = s32[73728,1]{0,1:T(1,128)} parameter(1)
  %param_2.1 = f32[73728,2048]{1,0:T(8,128)} parameter(2)
  ROOT %scatter-add.348 = f32[32768,2048]{1,0:T(8,128)} scatter(%param_0.1, %param_1.1, %param_2.1), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_16.38, metadata={op_name="jit(train_step)/jvp(layers)/while/body/moe_combine/scatter-add"}
}

%fused_computation.13 (param_0.2: s32[159], param_1.2: s32[16,1], param_2.2: s32[16]) -> s32[159] {
  %param_0.2 = s32[159]{0:T(256)S(1)} parameter(0)
  %param_1.2 = s32[16,1]{0,1:T(1,128)} parameter(1)
  %param_2.2 = s32[16]{0:T(256)} parameter(2)
  ROOT %scatter-add.347 = s32[159]{0:T(256)S(1)} scatter(%param_0.2, %param_1.2, %param_2.2), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_16.39
}

%body.5 (p: (f32[32768,2048], s32[73728,1], f32[73728,2048])) -> f32[32768,2048] {
  %p = (f32[32768,2048]{1,0}, s32[73728,1]{0,1}, f32[73728,2048]{1,0}) parameter(0)
  %acc = f32[32768,2048]{1,0:T(8,128)} get-tuple-element(%p), index=0
  %at = s32[73728,1]{0,1:T(1,128)} get-tuple-element(%p), index=1
  %rows = f32[73728,2048]{1,0:T(8,128)} get-tuple-element(%p), index=2
  ROOT %fusion.90 = f32[32768,2048]{1,0:T(8,128)} fusion(%acc, %at, %rows), kind=kCustom, calls=%fused_computation.12
}

ENTRY %main.9 (table: bf16[32768,2048], ids: s32[32768,1], g: bf16[32768,2048]) -> bf16[32768,2048] {
  %table = bf16[32768,2048]{1,0:T(8,128)(2,1)} parameter(0)
  %ids = s32[32768,1]{0,1:T(1,128)} parameter(1)
  %g = bf16[32768,2048]{1,0:T(8,128)(2,1)} parameter(2)
  ROOT %scatter-add.326 = bf16[32768,2048]{1,0:T(8,128)(2,1)} scatter(%table, %ids, %g), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%region_1.2, metadata={op_name="jit(train_step)/transpose(jvp(jit(_take)))/scatter-add"}
}
"""


def test_scatter_calls_counts_scatters_by_what_they_scatter_into():
    assert profiling.scatter_calls(_SCATTERS) == {
        "f32[32768,2048]": 1, "s32[159]": 1, "bf16[32768,2048]": 1}
    assert profiling.scatter_calls(_COMPILED) == {}
    assert profiling.scatter_calls("") == {}


# Three scheduled programs as XLA:TPU prints them, cut to what
# collective_calls reads (tests/test_compile_v5e_loss.py reads the whole
# dp=4 step): the combiner's blocking tuple; a start/done pair; and an
# async collective fusion, whose collective stands in the start's and the
# done's fused computation and again in the fusion that carries it on.
_BLOCKING = """HloModule jit_train_step, is_scheduled=true

%add.clone (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[]{:T(256)} parameter(0)
  %y = bf16[]{:T(256)} parameter(1)
  ROOT %add.3 = bf16[]{:T(256)} add(%x, %y)
}

ENTRY %main.1_spmd (param.1: bf16[768,3072]) -> (bf16[768,3072], bf16[3072,768]) {
  %param.1 = bf16[768,3072]{1,0:T(8,128)(2,1)} parameter(0)
  %fusion.7 = bf16[768,3072]{1,0:T(8,128)(2,1)} fusion(%param.1), kind=kOutput, calls=%fused_computation.7
  %fusion.8 = bf16[3072,768]{1,0:T(8,128)(2,1)} fusion(%param.1), kind=kOutput, calls=%fused_computation.8
  %scale.2 = f32[768]{0:T(1024)} fusion(%param.1), kind=kLoop, calls=%fused_computation.9
  %all-reduce.78 = (bf16[768,3072]{1,0:T(8,128)(2,1)}, bf16[3072,768]{1,0:T(8,128)(2,1)}, f32[768]{0:T(1024)}) all-reduce(%fusion.7, %fusion.8, %scale.2), channel_id=2, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.clone
  %flash_attention_dq.21 = bf16[8,12,1024,64]{3,2,1,0} custom-call(%param.1), custom_call_target="tpu_custom_call"
  ROOT %tuple.1 = (bf16[768,3072]{1,0}, bf16[3072,768]{1,0}) tuple(%all-reduce.78)
}
"""

_START_DONE = """HloModule jit_step, is_scheduled=true

ENTRY %main.2 (p: f32[1024,1024], q: f32[8]) -> f32[1024,1024] {
  %p = f32[1024,1024]{1,0} parameter(0)
  %q = f32[8]{0} parameter(1)
  %all-reduce-start.1 = f32[1024,1024]{1,0} all-reduce-start(%p), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
  %fusion.3 = f32[1024,1024]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.3
  %flash_attention_dkv.4 = f32[8]{0} custom-call(%q), custom_call_target="tpu_custom_call"
  %bitcast.5 = f32[8]{0} bitcast(%flash_attention_dkv.4)
  %all-reduce-done.1 = f32[1024,1024]{1,0} all-reduce-done(%all-reduce-start.1)
  %all-gather.2 = f32[32]{0} all-gather(%q), dimensions={0}
  ROOT %add.9 = f32[1024,1024]{1,0} add(%all-reduce-done.1, %fusion.3)
}
"""

_ASYNC_FUSION = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1595 (param_0.1: bf16[768,768]) -> (bf16[768,768], bf16[768,768], u32[]) {
  %param_0.1 = bf16[768,768]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %all-reduce.277 = bf16[768,768]{1,0:T(8,128)(2,1)} all-reduce(%param_0.1), channel_id=8, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.2.clone
  ROOT %custom-call.9 = (bf16[768,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,768]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) custom-call(%all-reduce.277), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.1194 (param_0.2: bf16[768,768], param_1.2: bf16[768,768], param_2.2: u32[], param_3.2: bf16[8,1024,768]) -> (bf16[768,768], bf16[768,768], bf16[768,768], u32[]) {
  %param_0.2 = bf16[768,768]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.2 = bf16[768,768]{1,0:T(8,128)(2,1)} parameter(1)
  %param_2.2 = u32[]{:S(2)} parameter(2)
  %param_3.2 = bf16[8,1024,768]{2,1,0:T(8,128)(2,1)} parameter(3)
  %convolution.399 = bf16[768,768]{1,0:T(8,128)(2,1)} convolution(%param_3.2, %param_3.2), dim_labels=0fb_0io->bf0
  %all-reduce.279 = bf16[768,768]{1,0:T(8,128)(2,1)} all-reduce(%param_0.2), channel_id=8, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.2.clone
  ROOT %tuple.530 = (bf16[768,768]{1,0}, bf16[768,768]{1,0}, bf16[768,768]{1,0}, u32[]{:S(2)}) tuple(%convolution.399, %param_0.2, %param_1.2, %param_2.2)
}

%fused_computation.1597 (param_0.3: bf16[768,768], param_1.3: bf16[768,768], param_2.3: u32[]) -> bf16[768,768] {
  %param_0.3 = bf16[768,768]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.3 = bf16[768,768]{1,0:T(8,128)(2,1)} parameter(1)
  %all-reduce.281 = bf16[768,768]{1,0:T(8,128)(2,1)} all-reduce(%param_0.3), channel_id=8, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.2.clone
  %param_2.3 = u32[]{:S(2)} parameter(2)
  ROOT %custom-call.11 = bf16[768,768]{1,0:T(8,128)(2,1)} custom-call(%param_0.3, %param_1.3, %all-reduce.281, %param_2.3), custom_call_target="AsyncCollectiveDone"
}

ENTRY %main.122_spmd (param.5: bf16[8,1024,768]) -> bf16[768,768] {
  %param.5 = bf16[8,1024,768]{2,1,0:T(8,128)(2,1)} parameter(0)
  %convolution_bitcast_fusion.11 = bf16[768,768]{1,0:T(8,128)(2,1)S(1)} fusion(%param.5), kind=kOutput, calls=%fused_computation.11
  %async-collective-start = (bf16[768,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,768]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) fusion(%convolution_bitcast_fusion.11), kind=kCustom, output_to_operand_aliasing={{0}: (0, {})}, calls=%fused_computation.1595
  %get-tuple-element.1146 = bf16[768,768]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%async-collective-start), index=0
  %get-tuple-element.1147 = bf16[768,768]{1,0:T(8,128)(2,1)} get-tuple-element(%async-collective-start), index=1
  %get-tuple-element.1148 = u32[]{:S(2)} get-tuple-element(%async-collective-start), index=2
  %fusion.1194 = (bf16[768,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,768]{1,0:T(8,128)(2,1)S(1)}, bf16[768,768]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) fusion(%get-tuple-element.1146, %get-tuple-element.1147, %get-tuple-element.1148, %param.5), kind=kOutput, calls=%async_collective_fusion.1194
  %get-tuple-element.1150 = bf16[768,768]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%fusion.1194), index=1
  %get-tuple-element.1151 = bf16[768,768]{1,0:T(8,128)(2,1)} get-tuple-element(%fusion.1194), index=2
  %get-tuple-element.1152 = u32[]{:S(2)} get-tuple-element(%fusion.1194), index=3
  %copy.4 = bf16[8,1024,768]{2,1,0:T(8,128)(2,1)} copy(%param.5)
  %async-collective-done = bf16[768,768]{1,0:T(8,128)(2,1)} fusion(%get-tuple-element.1150, %get-tuple-element.1151, %get-tuple-element.1152), kind=kCustom, output_to_operand_aliasing={{}: (1, {})}, calls=%fused_computation.1597
  %all-reduce.556 = bf16[3072,768]{1,0:T(8,128)(2,1)} all-reduce(%async-collective-done), channel_id=9, replica_groups=[1,4]<=[4], to_apply=%add.2.clone
  ROOT %copy.5 = bf16[768,768]{1,0:T(8,128)(2,1)} copy(%async-collective-done)
}
"""


def test_collective_calls_reads_a_blocking_tuple():
    got = profiling.collective_calls(_BLOCKING)
    assert got["collectives"] == [{
        "name": "all-reduce.78", "kind": "all-reduce",
        "operands": ["bf16[768,3072]", "bf16[3072,768]", "f32[768]"],
        "bytes": 2 * 2 * 768 * 3072 + 4 * 768,
        "async": False, "between": 0, "kernels": []}]
    assert got["gradient_reduce_bytes"] == 9_440_256
    assert got["async_share"] == 0.0
    assert profiling.collective_calls("") == {
        "collectives": [], "gradient_reduce_bytes": 0, "async_share": 0.0}


def test_collective_calls_reads_a_start_done_pair():
    got = profiling.collective_calls(_START_DONE)
    reduce, gather = got["collectives"]
    # A fusion and a kernel stand between the start and the done; the
    # bitcast is no work.
    assert (reduce["name"], reduce["kind"], reduce["async"],
            reduce["between"], reduce["kernels"]) == (
        "all-reduce-start.1", "all-reduce", True, 2,
        ["flash_attention_dkv.4"])
    assert reduce["bytes"] == 4 * 1024 * 1024
    assert (gather["kind"], gather["async"], gather["bytes"]) == (
        "all-gather", False, 32)
    # Only all-reduces are gradient reduces.
    assert got["gradient_reduce_bytes"] == 4 * 1024 * 1024
    assert got["async_share"] == 1.0


def test_collective_calls_reads_an_async_collective_fusion():
    got = profiling.collective_calls(_ASYNC_FUSION)
    fused, blocking = got["collectives"]
    # One collective, though its instruction is printed three times; the
    # fusion that carries it on is the work it runs under.
    assert (fused["name"], fused["kind"], fused["operands"], fused["async"],
            fused["between"], fused["kernels"]) == (
        "async-collective-start", "all-reduce", ["bf16[768,768]"], True, 1,
        [])
    assert (blocking["name"], blocking["async"]) == ("all-reduce.556", False)
    assert got["gradient_reduce_bytes"] == 2 * 2 * 768 * 768
    assert got["async_share"] == 0.5


def test_the_chip_path_opens_spans_through_profiling_only():
    other = _literals(r"(TraceAnnotation|tracing\.span|start_trace)\(",
                      CHIP_PATH)
    assert other == {}


def test_annotate_keeps_a_process_off_jax():
    code = ("import sys\n"
            "from ray_tpu.util import profiling\n"
            "with profiling.annotate('ray_tpu.feed.fetch_block'):\n"
            "    pass\n"
            "import ray_tpu.data.streaming, ray_tpu.train.session\n"
            "import ray_tpu.serve._private.replica\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   timeout=60)


ATTENTION_KERNELS = {"_fwd_kernel", "_dq_kernel", "_dkv_kernel"}
SCAN_KERNELS = {"_ssm_fwd_kernel", "_ssm_bwd_kernel"}
CONV_KERNELS = {"_conv_fwd_kernel", "_conv_bwd_kernel"}
INDEX_KERNELS = {"_index_fwd_kernel", "_index_bwd_kernel"}
KDA_KERNELS = {"_kda_fwd_kernel", "_kda_bwd_kernel"}


@pytest.mark.parametrize("make_step,cfg,batch,kernels", [
    (make_train_step,
     GPTConfig(vocab_size=512, d_model=128, n_heads=2, n_layers=1,
               d_ff=256, max_seq_len=256, remat=False), 2,
     ATTENTION_KERNELS),
    # gpt2s-train-1chip's step: 16 sequences of 1,024, 12 heads of 64.
    (make_train_step,
     dataclasses.replace(GPTConfig.gpt2_small(), remat=False), 16,
     ATTENTION_KERNELS),
    # The other two families run the same stack (models/decoder.py).
    (make_llama_train_step,
     LlamaConfig(vocab_size=512, d_model=256, n_heads=2, n_kv_heads=1,
                 n_layers=1, d_ff=256, max_seq_len=256), 2,
     ATTENTION_KERNELS),
    (make_moe_train_step,
     MoEConfig(vocab_size=512, d_model=128, n_heads=1, n_layers=1,
               n_experts=4, experts_per_token=2, d_expert=128,
               max_seq_len=256), 2,
     ATTENTION_KERNELS | {"_gmm_kernel", "_tgmm_kernel"}),
    # One Mamba-2 layer and one attention layer in one stack.
    (make_hybrid_train_step,
     HybridConfig(vocab_size=512, d_model=128, n_heads=2, n_kv_heads=1,
                  layer_types=("mamba", "attention"), d_ff=256,
                  mamba_n_heads=4, mamba_d_head=64, mamba_d_state=128,
                  mamba_chunk_size=128, max_seq_len=256), 2,
     ATTENTION_KERNELS | SCAN_KERNELS),
    # A dense layer under a gated short convolution, then an expert layer
    # under attention: LFM2's two sequence mixers and two channel mixers.
    (make_lfm2_moe_train_step,
     Lfm2MoeConfig(vocab_size=512, d_model=128, n_heads=2, n_kv_heads=1,
                   head_dim=64, layer_types=("conv", "full_attention"),
                   n_dense_layers=1, d_ff=256, n_experts=4,
                   experts_held=(1, 2), experts_per_token=2, d_expert=128,
                   bias_rounds=8, balance_tokens=0, max_seq_len=256), 2,
     ATTENTION_KERNELS | CONV_KERNELS | {"_gmm_kernel", "_tgmm_kernel"}),
    # The other three families at their tests' sizes, which no attention,
    # Mamba-2 or delta-rule kernel takes: the rules' scopes are round the
    # plain forms too. SambaY's eight kinds of layer; a delta-rule period;
    # Nemotron-H's one branch a layer and its held experts.
    (make_sambay_train_step, SambaYConfig.tiny(8), 2,
     {"_selective_fwd_kernel", "_selective_bwd_kernel"}),
    (make_olmo_hybrid_train_step, OlmoHybridConfig.tiny(), 2, set()),
    (make_nemotron_h_train_step, NemotronHConfig.tiny(), 2,
     {"_gmm_kernel", "_tgmm_kernel"}),
    # A dense layer, then an expert layer, both under latent attention at
    # q and k wider than v, every branch joined to two streams by
    # hyper-connections.
    (make_xing4_train_step,
     Xing4Config(vocab_size=512, d_model=128, n_heads=2, qk_nope_head_dim=64,
                 qk_rope_head_dim=64, v_head_dim=64, q_lora_rank=64,
                 kv_lora_rank=64, n_layers=2, n_dense_layers=1, d_ff=256,
                 n_experts=4, experts_held=(1, 2), experts_per_token=2,
                 d_expert=128, hc_mult=2, hc_sinkhorn_iters=2, bias_rounds=8,
                 balance_tokens=0, max_seq_len=256), 2,
     ATTENTION_KERNELS | {"_gmm_kernel", "_tgmm_kernel"}),
    # A dense layer, then an expert layer, both under latent attention at
    # q, k and v alike wide, on one residual stream, and a prediction module
    # behind them: one more such block and a second loss over the one head.
    (make_glm4_moe_lite_train_step,
     Glm4MoeLiteConfig(vocab_size=512, d_model=128, n_heads=2,
                       qk_nope_head_dim=64, qk_rope_head_dim=64,
                       v_head_dim=128, q_lora_rank=64, kv_lora_rank=64,
                       n_layers=2, n_dense_layers=1, d_ff=256, n_experts=4,
                       experts_held=(1, 2), experts_per_token=2, d_expert=128,
                       bias_rounds=8, balance_tokens=0, max_seq_len=256), 2,
     ATTENTION_KERNELS | {"_gmm_kernel", "_tgmm_kernel"}),
    # sparse attention: the indexer's two kernels beside attention's three,
    # a query naming 128 of 256 keys
    (make_keye_vl2_train_step,
     KeyeVL2Config(vocab_size=512, d_model=128, n_layers=2, n_heads=2,
                   n_kv_heads=1, head_dim=64, index_heads=2,
                   index_head_dim=64, index_topk=128, n_experts=4,
                   experts_held=(1, 2), experts_per_token=2, d_expert=128,
                   max_seq_len=256), 2,
     ATTENTION_KERNELS | INDEX_KERNELS | {"_gmm_kernel", "_tgmm_kernel"}),
    # a KDA layer (two heads of 128, the rule's two kernels) before a gated
    # latent layer, a dense layer then experts 1-2 of 8 in 4 groups
    (make_bailing_hybrid_train_step,
     BailingHybridConfig(vocab_size=512, d_model=128, n_heads=2,
                         kda_head_dim=128, qk_nope_head_dim=64,
                         qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=64,
                         n_layers=2, layer_group_size=2, n_dense_layers=1,
                         d_ff=256, n_experts=8, experts_held=(1, 2),
                         experts_per_token=2, n_group=4, topk_group=2,
                         d_expert=128, bias_rounds=8, balance_tokens=0,
                         max_seq_len=256), 2,
     ATTENTION_KERNELS | KDA_KERNELS | {"_gmm_kernel", "_tgmm_kernel"}),
], ids=["tiny", "gpt2-small", "llama", "moe", "hybrid", "lfm2-moe", "sambay",
        "olmo-hybrid", "nemotron-h", "xing4", "glm4-moe-lite", "keye-vl2",
        "bailing-hybrid"])
def test_lowered_train_step_carries_scopes_and_kernel_names(
        monkeypatch, make_step, cfg, batch, kernels):
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    init_state, step = make_step(cfg)
    state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)
    text = step.trace(state, (tok, tok)).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert set(re.findall(r'kernel_name = "([^"]+)"', text)) == kernels
    # The kernels' wrappers are jitted (one trace a step, not one a
    # layer), so their locations start at the scope: a kernel's own scope
    # is the innermost one round its call.
    for kernel in ("fwd", "dq", "dkv") if kernels >= ATTENTION_KERNELS else ():
        assert re.search(r'loc\("(?:[^"]*/)?flash_attention_%s/pallas_call"'
                         % kernel, text), kernel
    for found in re.findall(r'loc\("([^"]*)/pallas_call"', text):
        assert found.rsplit("/", 1)[-1] in {
            "flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv", "grouped_matmul_fwd",
            "grouped_matmul_dlhs", "grouped_matmul_drhs", "ssm_scan_fwd",
            "ssm_scan_bwd", "selective_scan_fwd", "selective_scan_bwd",
            "short_conv_fwd", "short_conv_bwd", "sparse_index_fwd",
            "sparse_index_bwd", "kda_fwd", "kda_bwd"}, found
    _every_branch_and_rule_sits_under_a_name(cfg, text)
    scopes = ["layers", "loss", "optimizer_update"]
    if kernels >= SCAN_KERNELS:
        for kernel in ("fwd", "bwd"):
            assert re.search(r'loc\("(?:[^"]*/)?ssm_scan_%s/pallas_call"'
                             % kernel, text), kernel
        scopes += ["ssm_conv", "ssm_gate_norm"]
    if kernels >= CONV_KERNELS:
        for kernel in ("fwd", "bwd"):
            assert re.search(r'loc\("(?:[^"]*/)?short_conv_%s/pallas_call"'
                             % kernel, text), kernel
        scopes += ["short_conv_proj", "moe_route", "moe_combine"]
    if cfg.decoder().hyper is not None:
        # a hyper-connected branch's three parts inside the branch's scope,
        # the latent mixer's two round its products
        for branch in ("latent_attention_mixer", "channel_mixer"):
            for part in ("hc_coefficients", "hc_read", "hc_write"):
                assert re.search(r'loc\("jit\(train_step\)/[^"]*\b%s/%s\b'
                                 % (branch, part), text), (branch, part)
        scopes += ["mla_project", "mla_expand", "moe_shared"]
    if getattr(cfg, "n_predict_layers", 0):
        # the module's parts inside `mtp`, its block's as any layer's, and
        # its loss under a name apart from the stack's
        for part in ("mtp_embed", "mtp_project", "mtp_norm",
                     "latent_attention_mixer/mla_project",
                     "channel_mixer/moe_route", "channel_mixer/moe_shared"):
            assert re.search(
                r'loc\("jit\(train_step\)/[^"]*\bmtp\)*/(?:[^"]*/)?%s\b' % part,
                text), part
        # the second loss stands beside the module, not inside it
        assert not re.search(r'loc\("[^"]*\bmtp\)*/[^"]*loss\b', text)
        scopes += ["mtp_loss", "mla_project", "mla_expand", "moe_shared"]
    if kernels >= INDEX_KERNELS:
        # the indexer's passes inside the branch's scope, each kernel's own
        # scope the innermost round its call; the backward kernel under the
        # target's rule, in the forward pass
        for part in ("sparse_index_proj", "sparse_select", "sparse_target"):
            assert re.search(
                r'loc\("jit\(train_step\)/[^"]*\bsparse_attention_mixer\)*/'
                r'(?:[^"]*/)?%s\b' % part, text), part
        for kernel in ("fwd", "bwd"):
            assert re.search(r'loc\("(?:[^"]*/)?sparse_index_%s/pallas_call"'
                             % kernel, text), kernel
        scopes += ["moe_route"]
    if kernels >= KDA_KERNELS:
        # the KDA mixer's parts inside the branch's scope, each kernel's own
        # scope the innermost round its call; the latent layer's gate
        # inside its own branch, the group choice inside `moe_route`
        for part in ("ssm_conv", "kda_qk_norm", "kda_gate", "kda_fwd",
                     "kda_bwd", "kda_gate_norm"):
            assert re.search(
                r'loc\("jit\(train_step\)/[^"]*\bkda_mixer\)*/'
                r'(?:[^"]*/)?%s\b' % part, text), part
        for kernel in ("fwd", "bwd"):
            assert re.search(r'loc\("(?:[^"]*/)?kda_%s/pallas_call"'
                             % kernel, text), kernel
        assert re.search(r'loc\("jit\(train_step\)/[^"]*\b'
                         r'latent_attention_mixer\)*/(?:[^"]*/)?mla_gate\b',
                         text)
        scopes += ["moe_route", "moe_shared", "mla_project", "mla_expand"]
    for scope in scopes:
        assert re.search(r'loc\("jit\(train_step\)/[^"]*\b%s\b' % scope,
                         text), scope


# kind of layer -> the hand-written backward rules its mixer runs, by the
# scope round each rule's whole work (ops/layers.py's runs under the
# `ssm_conv` round its call)
RULES = {
    "attention": ("flash_attention_bwd",),
    "attention_only": ("flash_attention_bwd",),
    "diff_windowed": ("flash_attention_bwd",),
    "diff_full": ("flash_attention_bwd",),
    "diff_cross": ("flash_attention_bwd",),
    "mamba2": ("ssm_scan_bwd", "ssm_conv"),
    "mamba2_only": ("ssm_scan_bwd", "ssm_conv"),
    "mamba1": ("selective_scan_bwd", "ssm_conv"),
    "gated_delta": ("gated_delta_bwd", "ssm_conv"),
    "short_conv": ("short_conv_bwd",), "gmu": (), "experts": (),
    "latent_attention": ("flash_attention_bwd",),
    "sparse_attention": ("flash_attention_bwd",),
    "kda": ("kda_bwd", "ssm_conv")}


def _every_branch_and_rule_sits_under_a_name(cfg, text):
    """In a lowered train step every branch of every block, the embedding,
    the last norm and each hand-written backward rule sit under a
    DEVICE_SCOPES name, forward, backward and (under remat) made again."""
    from ray_tpu.models import decoder

    seen = {}
    for op_name in re.findall(r'loc\("(jit\(train_step\)/[^"]*)"', text):
        names, which = profiling.scope_path(op_name)
        seen.setdefault(names, set()).add(which)
    passes = {"forward", "backward"} | ({"remade"} if cfg.remat else set())
    dec = cfg.decoder()
    assert set(dec.kinds) <= set(RULES)
    for kind in set(dec.kinds):
        row = decoder.MIXERS[kind]
        if row.apply is not None:
            mixer = ("layers", decoder.MIXER_SCOPES[kind])
            assert seen[mixer] == passes, (kind, seen.get(mixer))
            for rule in RULES[kind]:
                assert "backward" in seen[mixer + (rule,)], (kind, rule)
        if row.channel:
            assert seen[("layers", "channel_mixer")] == passes, kind
    if ("layers", "channel_mixer", "moe_route") in seen:
        rule = ("layers", "channel_mixer", "moe_experts_bwd")
        assert seen[rule] == {"backward"}
        assert seen[rule + ("grouped_matmul_bwd",)] == {"backward"}
    for scope in ("embed", "final_norm", "loss"):
        assert seen[(scope,)] >= {"forward", "backward"}, scope
    assert seen[("optimizer_update",)] == {"forward"}
    # under `layers` alone stands the rematerialised blocks' call and no
    # work; outside every name, what is no layer's (a counter, a cast of
    # the batch, the held experts' bias)
    assert seen.get(("layers",), set()) <= {"backward"}
    assert all(names[:1] in (("layers",), ("mtp",), ("mtp_loss",))
               for names in seen if len(names) > 1)
    if getattr(cfg, "n_predict_layers", 0):
        # a prediction module: its own lines and its block's two branches
        # forward, backward and made again, its loss beside the stack's
        kind, = set(dec.kinds)
        for part in ("mtp_embed", "mtp_project", "mtp_norm"):
            assert seen[("mtp", part)] >= {"forward", "backward"}, part
        for branch in (decoder.MIXER_SCOPES[kind], "channel_mixer"):
            assert seen[("mtp", branch)] == passes, branch
        assert "backward" in seen[("mtp", decoder.MIXER_SCOPES[kind],
                                   "flash_attention_bwd")]
        assert seen[("mtp_loss", "loss")] >= {"forward", "backward"}
        assert seen.get(("mtp",), set()) <= {"backward"}


def test_generate_steps_carry_their_scopes():
    from ray_tpu.models.generate import (init_cache, make_continuous_fns,
                                         make_generate_fns)
    cfg = GPTConfig(vocab_size=272, d_model=64, n_heads=4, n_layers=1,
                    d_ff=128, max_seq_len=64)
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 64))
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    one = jax.ShapeDtypeStruct((), jnp.int32)
    insert, decode_batch = make_continuous_fns(cfg, 64, 2)
    prefill, decode_step = make_generate_fns(cfg, 64)
    lowered = {
        "prefill": [
            insert.lower(params, jax.ShapeDtypeStruct((1, 64), jnp.int32),
                         cache, one, one),
            prefill.lower(params, jax.ShapeDtypeStruct((2, 8), jnp.int32),
                          cache)],
        "decode": [decode_batch.lower(params, vec, vec, cache),
                   decode_step.lower(params, vec, one, cache)]}
    for scope, programs in lowered.items():
        for low in programs:
            assert re.search(r'loc\("jit\([a-z_]+\)/%s/' % scope,
                             low.as_text(debug_info=True)), scope


def test_engine_counters_add_up(small_setup):
    cfg, params = small_setup
    prompts = ["a", "bb", "ccc", "dddd", "eeeee"]     # > max_batch=2
    eng, streams, texts, wall = _run_engine(cfg, params, prompts, 6)
    c = eng.counters()
    assert set(c["phase_s"]) == set(c["phase_n"]) == {
        "admit", "prefill", "decode", "fetch", "sample", "idle"}
    assert all(v >= 0 for v in c["phase_s"].values())
    assert sum(c["phase_s"].values()) <= wall
    assert c["phase_n"]["decode"] == c["phase_n"]["fetch"] == c["steps"]
    assert c["phase_n"]["admit"] == c["phase_n"]["prefill"] \
        == c["admitted"] == len(prompts)
    assert c["phase_n"]["sample"] == c["steps"] + len(prompts)
    # A prefill gives a request its first token, every decode step one
    # token to each occupied slot.
    assert c["tokens_out"] == c["slot_steps"] + c["admitted"] \
        == 6 * len(prompts)
    assert c["slot_steps"] <= c["steps"] * eng.max_batch
    assert c["finish_reasons"] == {"length": len(prompts)}
    assert len(eng.finished) == len(prompts)
    for s, text in zip(streams, texts):
        t = s.timing.as_dict()
        assert t in list(eng.finished)
        assert 0 <= t["queued_s"] <= t["first_token_s"] <= t["done_s"] \
            <= wall
        assert t["tokens"] == 6 and t["finish_reason"] == "length"
    # The third request had to wait for a slot; the first did not.
    assert streams[2].timing.as_dict()["queued_s"] \
        > streams[0].timing.as_dict()["queued_s"]


def test_stop_token_is_a_finish_reason(small_setup):
    from ray_tpu.llm.continuous import ContinuousBatchingEngine
    cfg, params = small_setup
    eng = ContinuousBatchingEngine(cfg=cfg, params=params, max_batch=2)
    try:
        free = eng.submit("hello", 8, 0.0)
        "".join(free)
        first = next(iter(eng.finished))
        assert first["finish_reason"] == "length" and first["tokens"] == 8
        # Greedy decoding repeats itself: stop on the token it opens with.
        from ray_tpu.models.generate import generate
        opener = int(next(generate(
            params, cfg, eng.tokenizer.encode("hello"),
            max_new_tokens=1))[0])
        stopped = eng.submit("hello", 8, 0.0, stop_token=opener)
        "".join(stopped)
        t = stopped.timing.as_dict()
        assert t["finish_reason"] == "stop" and t["tokens"] == 1
    finally:
        eng.close()


def test_device_gauges_never_start_or_wait_for_a_backend():
    from ray_tpu._private import telemetry
    from ray_tpu.util import metrics as M

    def built():
        m = M._REGISTRY.get("device_programs_built")
        return None if m is None else m._samples()[0][2]

    if telemetry.enabled:
        telemetry.flush_device_gauges()
        before = built()
        assert before is not None
        jax.block_until_ready(jax.jit(lambda x: x * 3 + before)(1.0))
        telemetry.flush_device_gauges()
        assert built() == before + 1
    # Backend start-up holds jax's backend lock for as long as a
    # jax.distributed gang takes to gather; the flush runs on the thread
    # that sends completions and must not queue behind it.
    code = ("import sys, threading, jax\n"
            "from ray_tpu._private import telemetry\n"
            "from jax._src import xla_bridge\n"
            "with xla_bridge._backend_lock:\n"
            "    t = threading.Thread(target=telemetry.flush_device_gauges)\n"
            "    t.start(); t.join(20)\n"
            "    assert not t.is_alive(), 'flush waited for backend start-up'\n"
            "from ray_tpu.util import profiling\n"
            "assert profiling.COMPILES.programs_built == 0\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   timeout=60)


def test_replica_counts_the_wait_for_a_handler_thread():
    from ray_tpu._private import telemetry
    from ray_tpu.serve._private.replica import Replica
    from ray_tpu.util import metrics as M

    if not telemetry.enabled:
        pytest.skip("telemetry off in this run")
    replica = Replica(cloudpickle.dumps(lambda x: x), (), {}, "waitdep")
    assert asyncio.run(replica.handle_request("__call__", (5,), {})) == 5
    text = M.prometheus_text()
    assert re.search(r'serve_replica_handler_wait_s_count\{[^}]*'
                     r'deployment="waitdep"[^}]*\} 1', text), text[-2000:]


def test_llm_reply_timing_and_cli_profile_of_the_replica(
        ray_start_shared, small_setup, tmp_path, capsys):
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.llm.serving import LLMEngine
    from ray_tpu.scripts import cli
    from ray_tpu.util.state import list_actors

    cfg, params = small_setup
    serve.start()
    serve.run(build_llm_app(cfg=cfg, params=params,
                            continuous_batching=True, max_batch=4),
              name="timed", route_prefix="/timed")
    try:
        h = serve.get_deployment_handle("LLMServer", "timed")
        ask = {"body": {"prompt": "hi", "max_tokens": 8}}
        out = h.remote(ask).result(timeout_s=120)
        assert out["text"] == LLMEngine(cfg=cfg, params=params).complete(
            "hi", max_new_tokens=8, temperature=0.0)
        assert out["device"]["platform"] == "cpu"
        assert out["device"]["pid"] != os.getpid()
        assert out["engine_steps"] >= 7
        t = out["timing"]
        assert set(t) == {"submit_unix", "queued_s", "first_token_s",
                          "done_s", "tokens", "finish_reason"}
        assert 0 <= t["queued_s"] <= t["first_token_s"] <= t["done_s"]
        assert t["tokens"] == 8 and t["finish_reason"] == "length"
        assert abs(t["submit_unix"] - time.time()) < 120

        name = next(a["name"] for a in list_actors()
                    if a["state"] == "ALIVE" and a["name"]
                    and a["name"].startswith("SERVE_REPLICA::")
                    and "LLMServer" in a["name"])
        path = str(tmp_path / "replica.xplane.pb")
        done = {}
        prof = threading.Thread(target=lambda: done.update(rc=cli.main(
            ["profile", name, "--seconds", "1.5", "-o", path,
             "--by-scope"])))
        prof.start()
        replies = []
        while prof.is_alive():
            replies.append(h.remote(ask).result(timeout_s=120))
        prof.join()
        assert done == {"rc": 0} and replies
        assert all(r["text"] == out["text"] for r in replies)
        spans = ray_tpu_spans(path)
        for phase in ("admit", "prefill", "decode", "fetch", "sample"):
            assert "ray_tpu.engine." + phase in spans, sorted(spans)
        assert "ray_tpu.serve.handle" in spans
        assert "$python" not in spans
        # --by-scope reduces the trace it has just written: on the CPU
        # there is no device plane to reduce, and it says so (the chip's
        # table is PERF.md's; tests/test_by_scope.py has the arithmetic)
        said = capsys.readouterr().out
        assert f"profile to {path}" in said
        assert "no device plane (/device:TPU:n) in this trace" in said
        assert profiling.read_device_events(path)["planes"] == []
    finally:
        serve.shutdown()


# A step as XLA:TPU prints it, cut to what scope_writes reads
# (tests/test_compile_v5e_granite.py reads a whole one): a multi-output
# fusion under the scope, the views of its parts, a fusion under another
# scope, a fused computation whose instructions carry the scope too, and a
# loop's body.
_SCOPED = """HloModule jit_train_step, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[1,64,256]) -> (f32[256], bf16[1,64,256]) {
  %param_0.1 = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} parameter(0)
  %convert.1 = f32[1,64,256]{2,1,0:T(8,128)} convert(%param_0.1), metadata={op_name="jit(train_step)/ssm_conv/convert_element_type"}
  %reduce.1 = f32[256]{0:T(256)} reduce(%convert.1, %param_0.1), dimensions={0,1}, metadata={op_name="jit(train_step)/ssm_conv/reduce_sum"}
  ROOT %tuple.1 = (f32[256]{0:T(256)}, bf16[1,64,256]{2,1,0:T(8,128)(2,1)}) tuple(%reduce.1, %param_0.1)
}

%body.3 (p: (bf16[1,64,256])) -> (bf16[1,64,256]) {
  %p = (bf16[1,64,256]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %fusion.99 = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(train_step)/while/body/ssm_conv/mul"}
  ROOT %tuple.9 = (bf16[1,64,256]{2,1,0:T(8,128)(2,1)}) tuple(%fusion.99)
}

ENTRY %main.1 (x: bf16[1,64,256], w: bf16[256,4]) -> bf16[1,64,256] {
  %x = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="x"}
  %w = bf16[256,4]{0,1:T(4,128)(2,1)} parameter(1)
  %convert_element_type.5 = f32[256,4]{0,1:T(4,128)} convert(%w), metadata={op_name="jit(train_step)/jvp(layers)/ssm_conv/convert_element_type" stack_frame_id=46}
  %divide_multiply_fusion.2 = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} fusion(%x, %convert_element_type.5), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(train_step)/jvp(layers)/ssm_conv/jit(silu)/mul"}
  %fusion.7 = (f32[256]{0:T(256)}, bf16[1,64,256]{2,1,0:T(8,128)(2,1)}) fusion(%divide_multiply_fusion.2), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(train_step)/transpose(jvp(layers))/ssm_conv/reduce_sum"}
  %get-tuple-element.1 = f32[256]{0:T(256)} get-tuple-element(%fusion.7), index=0, metadata={op_name="jit(train_step)/transpose(jvp(layers))/ssm_conv/reduce_sum"}
  %get-tuple-element.2 = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} get-tuple-element(%fusion.7), index=1, metadata={op_name="jit(train_step)/transpose(jvp(layers))/ssm_conv/reduce_sum"}
  %broadcast_in_dim.3 = f32[256,1]{0,1:T(1,128)} reshape(%get-tuple-element.1), metadata={op_name="jit(train_step)/transpose(jvp(layers))/ssm_conv/broadcast_in_dim"}
  %fusion.8 = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(train_step)/transpose(jvp(layers))/ssm_gate_norm/mul"}
  %while.3 = (bf16[1,64,256]{2,1,0:T(8,128)(2,1)}) while(%fusion.8), condition=%cond.3, body=%body.3
  ROOT %copy.4 = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} copy(%fusion.8)
}
"""


def test_scope_writes_counts_what_the_entry_computation_writes_under_a_scope():
    got = profiling.scope_writes(_SCOPED, "ssm_conv")
    assert got["writes"] == [
        {"name": "convert_element_type.5", "opcode": "convert",
         "results": [256 * 4 * 4]},
        {"name": "divide_multiply_fusion.2", "opcode": "fusion",
         "results": [64 * 256 * 2]},
        {"name": "fusion.7", "opcode": "fusion",
         "results": [256 * 4, 64 * 256 * 2]}]
    assert got["instructions"] == 3
    assert got["bytes"] == 4096 + 32768 + 1024 + 32768
    # another scope of the same step; a scope no instruction carries; a
    # text with no entry computation
    assert [w["name"] for w in profiling.scope_writes(
        _SCOPED, "ssm_gate_norm")["writes"]] == ["fusion.8"]
    empty = {"instructions": 0, "writes": [], "bytes": 0}
    assert profiling.scope_writes(_SCOPED, "flash_attention_fwd") == empty
    assert profiling.scope_writes("", "ssm_conv") == empty
