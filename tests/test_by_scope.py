"""A device trace's time by the program's own names (util/profiling.py
`by_scope`, `scope_path`, `step_events`, `mixed_fusions`,
`read_device_events`): plain arithmetic on hand-made events, named as the
v5e names them (an event's name is its whole HLO instruction, its `tf_op`
the instruction's `op_name` path), then the reader of the file format on
the traces recorded on the chip, and one step of a cell's real events
reduced to its known totals."""

import gzip
import json
import os

import pytest

from ray_tpu.util import profiling
from ray_tpu.util.profiling import by_scope, scope_path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "chipbench", "tests", "recorded")
STEP = "jit(train_step)/"


def fusion(n, shape="bf16[8,128]{1,0:T(8,128)(2,1)}"):
    return f"%fusion.{n} = {shape} fusion(%p.{n}), kind=kLoop, " \
           f"calls=%fused_computation.{n}"


WHILE = ("%while.3 = (s32[]{:T(128)}, f32[768,512]{1,0:T(8,128)}) "
         "while((s32[]{:T(128)}, f32[768,512]{1,0:T(8,128)}) %tuple.9), "
         "condition=%cond.3, body=%body.3")
COPY = "%copy.7 = f32[16384,4096]{1,0:T(8,128)} copy(f32[16384,4096]{0,1} %x)"
KERNEL = ("%ssm_scan_bwd.2 = (bf16[1,16384,4096]{2,1,0}, f32[8,128]{1,0}) "
          'custom-call(%a), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("op_name,names,which", [
    ("jvp(layers)/attention_mixer/dot_general",
     ("layers", "attention_mixer"), "forward"),
    ("transpose(jvp(layers))/channel_mixer/moe_experts_bwd/moe_dx/gather",
     ("layers", "channel_mixer", "moe_experts_bwd", "moe_dx"), "backward"),
    # a rematerialised block: its own path starts again after the call's,
    # and its second run says so
    ("transpose(jvp(layers))/jvp(layers)/checkpoint/rematted_computation/"
     "mamba2_mixer/ssm_conv/mul",
     ("layers", "mamba2_mixer", "ssm_conv"), "remade"),
    ("transpose(jvp(layers))/jvp(layers)/checkpoint/mamba2_mixer/"
     "ssm_scan_bwd/jit(_scan_backward_call)/ssm_scan_bwd/pallas_call",
     ("layers", "mamba2_mixer", "ssm_scan_bwd", "ssm_scan_bwd"), "backward"),
    # wrappers and plain components that are no scope
    ("jvp(loss)/while/body/closed_call/dot_general", ("loss",), "forward"),
    ("jvp(layers)/shard_map/attention_mixer/custom_vjp_call/pjit/mul",
     ("layers", "attention_mixer"), "forward"),
    ("vmap(jvp(loss))/cond/branch_1_fun/add", ("loss",), "forward"),
    # a jitted function's name is no scope, whatever it is called
    ("jvp(embed)/jit(_take)/gather", ("embed",), "forward"),
    ("jvp(layers)/jit(gmu)/pjit(loss)/mul", ("layers",), "forward"),
    ("optimizer_update/jit(_where)/select_n", ("optimizer_update",),
     "forward"),
    # two instructions merged into one keep the first's path
    ("jvp(loss)/reshape;jit(train_step)/jvp(final_norm)/mul", ("loss",),
     "forward"),
    ("jvp()/concatenate", (), "forward"),
    ("transpose(jvp())/reduce_sum", (), "backward"),
    ("", (), "forward"),
])
def test_scope_path_unwraps_jaxs_wrappers(op_name, names, which):
    assert scope_path(STEP + op_name if op_name else "") == (names, which)


def test_every_event_counts_its_self_time_and_the_rows_sum_to_busy():
    loss = STEP + "jvp(loss)/while"
    events = [
        (WHILE, loss, 0.0, 100.0),
        (fusion(1), loss + "/body/closed_call/dot_general", 10.0, 20.0),
        (fusion(2), loss + "/body/closed_call/exp", 40.0, 20.0),
        # idle from 100 to 120, then two rows that overlap by 10
        (fusion(3), STEP + "transpose(jvp(layers))/channel_mixer/mul",
         120.0, 30.0),
        (fusion(4), STEP + "optimizer_update/add", 140.0, 30.0),
    ]
    got = by_scope(events, steps=1)
    ms = 1e-6
    assert got["busy_ms_per_step"] == pytest.approx(150.0 * ms)
    # the loop's own 60 and its body's 40; nothing twice
    assert got["scopes"]["loss"] == {
        "forward_ms": pytest.approx(100.0 * ms), "remade_ms": 0.0,
        "backward_ms": 0.0, "calls": 3, "mixed_ms": 0.0}
    # where two rows overlap the later one has the time
    assert got["scopes"]["layers/channel_mixer"]["backward_ms"] \
        == pytest.approx(20.0 * ms)
    assert got["scopes"]["optimizer_update"]["forward_ms"] \
        == pytest.approx(30.0 * ms)
    assert sum(r[p] for r in got["scopes"].values() for p in (
        "forward_ms", "remade_ms", "backward_ms")) == pytest.approx(
        got["busy_ms_per_step"], rel=1e-9)
    assert got["coverage"] == pytest.approx(1.0) and got["unscoped"] == []
    assert profiling._self_times(events) == [60.0, 20.0, 20.0, 20.0, 30.0]


def test_the_three_passes_of_a_rematerialised_block_and_its_kernels():
    block = "jvp(layers)/checkpoint/"
    events = [
        (fusion(1), STEP + "jvp(layers)/mamba2_mixer/ssm_conv/mul", 0., 4.),
        (fusion(2), STEP + "transpose(jvp(layers))/" + block
         + "rematted_computation/mamba2_mixer/ssm_conv/mul", 10., 5.),
        (fusion(3), STEP + "transpose(jvp(layers))/" + block
         + "mamba2_mixer/ssm_conv/reduce_sum", 20., 6.),
        # the rule's own rows, then its kernel: the same name twice
        (fusion(4), STEP + "transpose(jvp(layers))/" + block
         + "mamba2_mixer/ssm_scan_bwd/convert_element_type", 30., 2.),
        (KERNEL, STEP + "transpose(jvp(layers))/" + block + "mamba2_mixer/"
         "ssm_scan_bwd/jit(_scan_backward_call)/ssm_scan_bwd/pallas_call",
         40., 8.),
        (fusion(5), STEP + "jvp(layers)/add", 50., 1.),
    ]
    again = [(n, op, start + 100.0, d) for n, op, start, d in events]
    got = by_scope(events + again, steps=2)        # two steps alike
    ns = 1e-6
    conv = got["scopes"]["layers/mamba2_mixer/ssm_conv"]
    assert (conv["forward_ms"], conv["remade_ms"], conv["backward_ms"],
            conv["calls"]) == pytest.approx((4 * ns, 5 * ns, 6 * ns, 3))
    rule = got["scopes"]["layers/mamba2_mixer/ssm_scan_bwd"]
    kernel = got["scopes"]["layers/mamba2_mixer/ssm_scan_bwd/ssm_scan_bwd"]
    assert rule["backward_ms"] == pytest.approx(2 * ns)
    assert kernel["backward_ms"] == pytest.approx(8 * ns)
    assert got["steps"] == 2
    # under `layers` alone is under no branch of a block
    assert got["scopes"]["layers"]["forward_ms"] == pytest.approx(1 * ns)
    assert got["coverage"] == pytest.approx(25 / 26)


def test_events_with_no_name_of_ours_are_filed_by_opcode_and_shape():
    events = [(COPY, "", 0.0, 50.0), (COPY, "", 60.0, 30.0),
              (fusion(1, "f32[64]{0:T(128)}"),
               STEP + "jvp()/concatenate", 100.0, 5.0),
              (fusion(2), STEP + "jvp(loss)/mul", 110.0, 15.0)]
    # twenty-two more shapes, smaller and smaller: twenty rows are listed
    events += [(fusion(10 + i, f"f32[{i + 1}]{{0}}"), "", 200.0 + i,
                0.5 - i / 100) for i in range(22)]
    got = by_scope(events, steps=1)
    rows = got["unscoped"]
    assert rows[0] == {"opcode": "copy", "shape": "f32[16384,4096]",
                       "ms": pytest.approx(80e-6), "calls": 2, "after": ""}
    assert rows[1]["opcode"] == "fusion" and rows[1]["shape"] == "f32[64]"
    assert len(rows) == 21 and rows[-1]["opcode"] == "(rest)"
    assert rows[-1]["calls"] == 4
    assert [r["ms"] for r in rows[:20]] == sorted(
        (r["ms"] for r in rows[:20]), reverse=True)
    assert sum(r["ms"] for r in rows) + got["scopes"]["loss"]["forward_ms"] \
        == pytest.approx(got["busy_ms_per_step"])
    assert got["coverage"] == pytest.approx(15.0 / (100.0 + sum(
        0.5 - i / 100 for i in range(22))))
    assert by_scope([], steps=0) == {
        "steps": 0, "busy_ms_per_step": 0.0, "coverage": 0.0, "scopes": {},
        "unscoped": []}


def test_step_events_counts_the_runs_of_the_steps_program():
    modules = [("jit_init(1)", 0.0, 50.0),
               ("jit_train_step(2)", 100.0, 100.0),
               ("jit_train_step(2)", 300.0, 100.0),
               ("jit__where(3)", 250.0, 1.0),
               ("jit_train_step(2)", 500.0, 100.0),     # cut off after 40
               ("jit_train_step(2)", 700.0, 40.0),      # clipped to 40
               ("jit_train_step(2)", 900.0, 100.0)]     # cut off: no event
    op = STEP + "jvp(loss)/mul"
    events = [(fusion(0), "jit(init)/mul", 10.0, 30.0),
              (fusion(1), op, 100.0, 40.0), (fusion(2), op, 150.0, 50.0),
              (fusion(9), "jit(_where)/select_n", 250.0, 1.0),
              (fusion(1), op, 300.0, 40.0), (fusion(2), op, 350.0, 50.0),
              (fusion(1), op, 500.0, 40.0), (fusion(1), op, 700.0, 40.0)]
    inside, runs = profiling.step_events(events, modules)
    assert runs == 2 and [e[0] for e in inside] == [
        fusion(1), fusion(2), fusion(1), fusion(2)]
    got = by_scope(inside, runs)
    assert got["busy_ms_per_step"] == pytest.approx(90e-6)
    assert got["scopes"]["loss"]["calls"] == 2
    # a hand-made list with no modules is one run
    assert profiling.step_events(events, []) == (events, 1)


# A compiled step cut to what mixed_fusions reads: a fusion of one scope
# (and a cast of the weights that stands under the step alone, which is no
# second scope), one that holds the mixer's matmul and the convolution's
# taps, one whose instructions carry no op_name at all.
_COMPILED = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0} parameter(0)
  %k.1 = bf16[8,128]{1,0} convert(%p), metadata={op_name="jit(train_step)/convert_element_type"}
  %m.1 = bf16[8,128]{1,0} multiply(%k.1, %p), metadata={op_name="jit(train_step)/jvp(layers)/mamba2_mixer/ssm_conv/mul"}
  ROOT %a.1 = bf16[8,128]{1,0} add(%m.1, %p), metadata={op_name="jit(train_step)/jvp(layers)/mamba2_mixer/ssm_conv/add"}
}

%fused_computation.2 (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0} parameter(0)
  %c.2 = bf16[8,128]{1,0} convolution(%p, %p), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/jvp(layers)/mamba2_mixer/bsd,de->bse/dot_general"}
  ROOT %m.2 = bf16[8,128]{1,0} multiply(%c.2, %p), metadata={op_name="jit(train_step)/jvp(layers)/mamba2_mixer/ssm_conv/mul"}
}

%fused_computation.3 (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0} parameter(0)
  ROOT %b.3 = bf16[8,128]{1,0} bitcast(%p)
}

ENTRY %main.1 (x: bf16[8,128]) -> bf16[8,128] {
  %x = bf16[8,128]{1,0} parameter(0)
  %fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(layers)/mamba2_mixer/ssm_conv/add"}
  %fusion.2 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(train_step)/jvp(layers)/mamba2_mixer/bsd,de->bse/dot_general"}
  ROOT %fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
}
"""


def test_a_fusion_of_two_scopes_is_flagged_and_no_time_is_split():
    # `fusion.1` holds `ssm_conv` and a cast under no scope: one scope
    assert profiling.mixed_fusions(_COMPILED) == {
        "fusion.2": ["layers/mamba2_mixer", "layers/mamba2_mixer/ssm_conv"]}
    mixer = STEP + "jvp(layers)/mamba2_mixer/bsd,de->bse/dot_general"
    events = [(fusion(1), STEP + "jvp(layers)/mamba2_mixer/ssm_conv/add",
               0.0, 10.0),
              (fusion(2), mixer, 10.0, 30.0),
              # no tf_op on the event: the compiled text has its op_name
              (fusion(2), "", 40.0, 30.0),
              (fusion(3), "", 70.0, 5.0)]
    got = by_scope(events, 1, _COMPILED)
    assert got["scopes"]["layers/mamba2_mixer"] == {
        "forward_ms": pytest.approx(60e-6), "remade_ms": 0.0,
        "backward_ms": 0.0, "calls": 2, "mixed_ms": pytest.approx(60e-6)}
    assert got["scopes"]["layers/mamba2_mixer/ssm_conv"]["mixed_ms"] == 0.0
    # the row with no name of its own lays out what `fusion.2` made
    assert got["unscoped"] == [{
        "opcode": "fusion", "shape": "bf16[8,128]",
        "ms": pytest.approx(5e-6), "calls": 1,
        "after": "layers/mamba2_mixer"}]
    # without the text the row with no tf_op has no name, and nothing is
    # flagged
    blind = by_scope(events, 1)
    assert blind["scopes"]["layers/mamba2_mixer"]["calls"] == 1
    assert blind["scopes"]["layers/mamba2_mixer"]["mixed_ms"] == 0.0
    assert blind["unscoped"][0]["ms"] == pytest.approx(35e-6)


def test_the_table_prints_a_row_a_scope_largest_first():
    events = [(fusion(1), STEP + "jvp(loss)/mul", 0.0, 1e6),
              (fusion(2), STEP + "transpose(jvp(layers))/channel_mixer/mul",
               2e6, 3e6), (COPY, "", 6e6, 1e6)]
    lines = profiling.format_by_scope(by_scope(events, 1)).splitlines()
    assert lines[0].startswith("1 step(s), 5.000 ms busy a step, 80.00%")
    assert [line.split()[-1] for line in lines[2:4]] == [
        "layers/channel_mixer", "loss"]
    assert "unscoped: copy f32[16384,4096]" in lines[4]


@pytest.mark.parametrize("trace,has_tf_op", [
    ("tiny-train-v5e.xplane.pb", False),
    (os.path.join("scoped", "tiny-train-v5e-scoped.xplane.pb"), True)])
def test_read_device_events_reads_what_the_v5e_wrote(trace, has_tf_op):
    """The two traces recorded on the chip (PR 22's before the program had
    scopes, PR 23's after): the reader's events are jax's own, name for
    name and to the nanosecond, and carry the `tf_op` jax does not show."""
    import jax

    path = os.path.join(RECORDED, trace)
    got = profiling.read_device_events(path)
    assert got["plane"] == "/device:TPU:0" and got["planes"] == [got["plane"]]
    plane = next(p for p in jax.profiler.ProfileData.from_file(path).planes
                 if p.name == got["plane"])
    want = {line.name: [(e.name, e.start_ns, e.duration_ns)
                        for e in line.events] for line in plane.lines}
    assert len(got["events"]) == len(want["XLA Ops"]) == 598
    for (name, _, start, duration), (n, s, d) in zip(got["events"],
                                                     want["XLA Ops"]):
        assert name == n and abs(start - s) <= 1 and abs(duration - d) <= 1
    assert [m[0] for m in got["modules"]] == [
        n for n, _, _ in want["XLA Modules"]]
    events, runs = profiling.step_events(got["events"], got["modules"])
    assert runs == 2 and len(events) == 598
    table = by_scope(events, runs)
    if not has_tf_op:
        assert table["scopes"] == {} and table["coverage"] == 0.0
        return
    # PR 23's names: the layer stack, the loss, the optimizer; the kernels
    # under their scopes, at the durations that trace's README gives
    named = [tf_op for _, tf_op, _, _ in events if tf_op]
    assert len(named) > 200 and not any(":" in tf_op for tf_op in named)
    assert {tf_op for tf_op in named
            if not tf_op.startswith("jit(train_step)")} == {
        "state['params']['embed']"}         # a copy of an argument
    assert {"layers", "loss", "optimizer_update",
            "layers/flash_attention_fwd", "layers/flash_attention_dq",
            "layers/flash_attention_dkv"} == set(table["scopes"])
    for scope, ns in (("fwd", 78_392), ("dq", 23_104), ("dkv", 23_728)):
        row = table["scopes"]["layers/flash_attention_" + scope]
        assert (row["forward_ms"] + row["backward_ms"]) * 2 \
            == pytest.approx(ns * 1e-6, rel=1e-4)
    # ... and jax's own reader shows none of them: why the reader parses
    # the bytes. Once this fails, read through ProfileData and delete it.
    shown = {name for line in plane.lines if line.name == "XLA Ops"
             for e in line.events for name, _ in e.stats}
    assert shown and not shown & {"tf_op", "hlo_op"}


def test_one_recorded_step_of_a_cell_reduces_to_its_known_totals():
    """One step of gpt2s-train-1chip's real events (tests/recorded/
    README.txt), names and tf_ops as the v5e gives them: the totals the
    chip run printed, and the arithmetic's own invariants at real size
    (3,996 events, the loss's loop round its body's rows)."""
    with gzip.open(os.path.join(ROOT, "tests", "recorded",
                                "gpt2s-step-v5e.events.json.gz"), "rt") as f:
        events = [tuple(e) for e in json.load(f)]
    assert len(events) == 3996
    got = by_scope(events, steps=1)
    assert got["busy_ms_per_step"] == pytest.approx(142.9951, abs=1e-4)
    assert got["coverage"] == pytest.approx(0.87322, abs=1e-5)
    ms = {path: (r["forward_ms"], r["remade_ms"], r["backward_ms"],
                 r["calls"]) for path, r in got["scopes"].items()}
    mixer = "layers/attention_mixer"
    assert ms == {
        "embed": pytest.approx((0.2375, 0, 0.9118, 7), abs=1e-4),
        "final_norm": pytest.approx((0.0404, 0, 0.0713, 6), abs=1e-4),
        mixer: pytest.approx((15.0342, 0, 21.3089, 327), abs=1e-4),
        mixer + "/flash_attention_fwd": pytest.approx(
            (8.5755, 0, 0, 12), abs=1e-4),
        mixer + "/flash_attention_bwd": pytest.approx(
            (0, 0, 3.2404, 48), abs=1e-4),
        mixer + "/flash_attention_bwd/flash_attention_dq": pytest.approx(
            (0, 0, 7.3497, 12), abs=1e-4),
        mixer + "/flash_attention_bwd/flash_attention_dkv": pytest.approx(
            (0, 0, 9.6828, 12), abs=1e-4),
        "layers/channel_mixer": pytest.approx(
            (10.2251, 0, 21.2566, 108), abs=1e-4),
        "loss": pytest.approx((26.3382, 0, 0, 39), abs=1e-4),
        "optimizer_update": pytest.approx((0.5934, 0, 0, 26), abs=1e-4)}
    # what no name covers: XLA's relayout copies round the attention
    # kernels, which carry no op_name (PERF.md section 7)
    assert [(r["opcode"], r["shape"]) for r in got["unscoped"][:2]] == [
        ("copy", "f32[16,12,1024,32]"), ("copy-done", "f32[16,12,1024,32]")]
    assert got["unscoped"][0]["ms"] == pytest.approx(5.9001, abs=1e-4)
    scoped = sum(sum(t[:3]) for t in ms.values())
    assert scoped + sum(r["ms"] for r in got["unscoped"]) == pytest.approx(
        got["busy_ms_per_step"], rel=1e-9)
    # the loss's loop does not swallow its body: the loop rows' own time
    # is a sliver of what they span
    own = profiling._self_times(events)
    loops = [(e[3], mine) for e, mine in zip(events, own)
             if profiling._instruction(e[0])[1] == "while"]
    assert loops and sum(d for d, _ in loops) > 20e6
    assert sum(mine for _, mine in loops) < 0.05 * sum(d for d, _ in loops)
