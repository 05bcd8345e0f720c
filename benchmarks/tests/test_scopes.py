"""``harness/scopes.py`` on hand-built tuples and texts, and the readers
it feeds in the rehearsal cell on four virtual CPU devices.

The rehearsal goes through a copy of the benchmark as ``test_cells.py``'s
does (``overlay.py``), with the scope readers' manifest entries extended
to the rehearsal cells and one rehearsal-only reader added: new files and
appended entries only.
"""

import json
import os

import pytest

from conftest import run_cell
from harness import scopes

NEW_READERS = ["forward_share.train", "backward_share.train",
               "optimizer_share.train", "batchnorm_share.train",
               "exposed_collective_share.train",
               "sync_bn_exposed_share.train", "allreduce_mb.train",
               "host_dispatch_ms.train"]
REHEARSALS = ["resnet-tiny-train-cpu1", "resnet-tiny-train-cpu4"]
STEP = "jit(local_step)/shard_map/"

HLO = '''HloModule jit_local_step, is_scheduled=true

FileNames
1 "/root/repo/horovod_tpu/optimizer.py"

StackFrames
1 1 0

%fused_computation.1 (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %convert.3 = f32[8]{0} convert(%p0), metadata={op_name="jit(local_step)/shard_map/transpose(jvp(ResNet))/head/Dense_0/convert_element_type" stack_frame_id=3}
  %mul.1 = f32[8]{0} multiply(%convert.3, %p1), metadata={op_name="jit(local_step)/shard_map/hvd::optimizer/inner_update/mul" stack_frame_id=1}
  ROOT %add.9 = f32[8]{0} add(%p0, %mul.1), metadata={op_name="jit(local_step)/shard_map/add" stack_frame_id=2}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p0.1)
}

ENTRY %main.1_spmd (param.1: f32[8], param.2: f32[8]) -> f32[8] {
  %param.1 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %param.2 = f32[8]{0} parameter(1)
  %psum_invariant.7 = f32[17]{0:T(256)S(1)} all-reduce(%param.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_2.0, metadata={op_name="jit(local_step)/shard_map/jvp(ResNet)/stage1/BottleneckBlock_0/BatchNorm_0/hvd::batch_norm/hvd::sync_bn_stats/psum_invariant" stack_frame_id=19}
  %all-reduce.3 = (bf16[3,3,8,8]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[]{:T(128)}) all-reduce(%param.1, %param.2), channel_id=2, to_apply=%region_3.0, metadata={op_name="jit(local_step)/shard_map/transpose(jvp(ResNet))/stage1/BottleneckBlock_0/Conv_1/psum_invariant"}
  %all-reduce-start.1 = bf16[16]{0} all-reduce-start(%param.2), channel_id=3, to_apply=%region_4.0, metadata={op_name="jit(local_step)/shard_map/transpose(jvp(ResNet))/stage1/BottleneckBlock_0/BatchNorm_0/hvd::batch_norm/psum_invariant"}
  %all-reduce-done.1 = bf16[16]{0} all-reduce-done(%all-reduce-start.1)
  %multiply_add_fusion.4 = f32[8]{0} fusion(%param.1, %param.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/shard_map/add" stack_frame_id=2}
  ROOT %fusion.5 = f32[8]{0} fusion(%multiply_add_fusion.4), kind=kLoop, calls=%fused_computation.2
}
'''


# -- the compiled step's text -------------------------------------------------

def test_op_names_reads_every_instruction_and_looks_into_fusions():
    names = scopes.op_names(HLO)
    assert names["psum_invariant.7"].endswith("hvd::sync_bn_stats/"
                                              "psum_invariant")
    assert names["param.2"] is None and names["neg.1"] is None
    # Its root is the job's own add, outside every scope: the op_name
    # nearest the root that names a phase is the optimizer's.
    assert names["multiply_add_fusion.4"] == \
        STEP + "hvd::optimizer/inner_update/mul"
    assert names["fusion.5"] is None          # nothing inside names one
    assert "all-reduce-done.1" in names and "nowhere.1" not in names


def test_scopes_held_looks_into_fusions():
    assert scopes.scopes_held(HLO) == {
        "psum_invariant.7": {"hvd::batch_norm", "hvd::sync_bn_stats"},
        "all-reduce-start.1": {"hvd::batch_norm"},
        "mul.1": {"hvd::optimizer"},
        "multiply_add_fusion.4": {"hvd::optimizer"}}


@pytest.mark.parametrize("op_name,want", [
    (None, ("unscoped", "-", False, False)),
    (STEP + "add", ("other", "-", False, False)),
    (STEP + "hvd::optimizer/inner_update/mul",
     ("optimizer", "-", False, False)),
    (STEP + "jvp(ResNet)/stem/conv_init/conv_general_dilated",
     ("forward", "stem", False, False)),
    (STEP + "jvp(ResNet)/max_pool/reduce_window_max",
     ("forward", "max_pool", False, False)),
    (STEP + "transpose(jvp(ResNet))/stage3/BottleneckBlock_9/Conv_1/"
     "conv_general_dilated", ("backward", "stage3", False, False)),
    (STEP + "transpose(jvp(ResNet))/stage4/BottleneckBlock_15/BatchNorm_2/"
     "hvd::batch_norm/mul", ("backward", "stage4", True, False)),
    (STEP + "jvp(ResNet)/stage2/BottleneckBlock_4/BatchNorm_0/"
     "hvd::batch_norm/hvd::sync_bn_stats/psum_invariant",
     ("forward", "stage2", True, True)),
    (STEP + "jvp(ResNet)/head/Dense_0/dot_general",
     ("forward", "head", False, False)),
    (STEP + "jvp()/reduce_max", ("forward", "loss", False, False)),
    (STEP + "transpose(jvp(jit(take_along_axis)))/scatter-add",
     ("backward", "loss", False, False)),
    # A module that merely has a part's name inside another word is not it.
    (STEP + "jvp(ResNet)/stemless/mul", ("forward", "loss", False, False)),
])
def test_classify(op_name, want):
    c = scopes.classify(op_name)
    assert (c["phase"], c["part"], c["batch_norm"],
            c["sync_bn_stats"]) == want


@pytest.mark.parametrize("op_name,want", [
    (STEP + "jvp(ResNet)/stage1/B/BatchNorm_0/hvd::batch_norm/"
     "hvd::sync_bn_stats/psum_invariant", "sync_bn_stats forward"),
    (STEP + "transpose(jvp(ResNet))/stage1/B/BatchNorm_0/hvd::batch_norm/"
     "psum_invariant", "sync_bn backward"),
    (STEP + "transpose(jvp(ResNet))/stage1/B/Conv_0/psum_invariant",
     "gradients"),
    (STEP + "hvd::allreduce::loss/psum", "hvd::allreduce::loss"),
    (STEP + "hvd::optimizer/reduce_gradients/hvd::allreduce/psum",
     "hvd::allreduce"),
    (STEP + "jvp(ResNet)/stage1/B/BatchNorm_0/psum_invariant", "forward"),
    (None, "unscoped"),
])
def test_collective_scope(op_name, want):
    assert scopes.collective_scope(op_name) == want


def test_all_reduces_and_their_bytes():
    found = scopes.all_reduces(HLO)
    assert [(name, nbytes) for name, _, nbytes in found] == [
        ("psum_invariant.7", 17 * 4),
        ("all-reduce.3", 3 * 3 * 8 * 8 * 2 + 4),   # bf16 kernel + f32 scalar
        ("all-reduce-start.1", 16 * 2)]
    assert scopes.all_reduce_bytes_by_scope(HLO) == {
        "sync_bn_stats forward": [1, 68], "gradients": [1, 1156],
        "sync_bn backward": [1, 32]}
    assert scopes.array_bytes("(f32[2,3]{1,0}, pred[5], s32[])") == 33


def test_stripped_text_forgets_names_and_nothing_else():
    renamed = HLO.replace("hvd::optimizer/inner_update/", "") \
        .replace("stack_frame_id=1}", "stack_frame_id=7}") \
        .replace('1 "/root/repo/horovod_tpu/optimizer.py"',
                 '1 "/elsewhere/optimizer.py"\n2 "more.py"')
    assert renamed != HLO
    assert scopes.stripped(renamed) == scopes.stripped(HLO)
    assert "metadata" not in scopes.stripped(HLO)
    assert "ENTRY %main.1_spmd" in scopes.stripped(HLO)
    assert scopes.stripped(HLO.replace("negate(", "abs(")) != \
        scopes.stripped(HLO)


def test_collectives_are_found_by_opcode():
    assert scopes.is_collective(
        "%psum_invariant.7 = f32[17]{0:T(256)S(1)} all-reduce(%x), "
        "channel_id=1")
    assert scopes.is_collective(
        "%all-reduce.3 = (bf16[3,3,8,8]{3,2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[]{:T(128)}) all-reduce(%a, %b), channel_id=2")
    assert scopes.is_collective("%ag.1 = f32[8]{0} all-gather-start(%x)")
    assert not scopes.is_collective(
        "%fusion.2 = bf16[2]{0:T(8,128)(2,1)} fusion(%all-reduce.3), "
        "kind=kLoop, calls=%fused_computation.2")
    assert scopes.instruction_of(
        "%fusion.2 = bf16[2]{0} fusion(%x), kind=kLoop") == "fusion.2"


# -- intervals ----------------------------------------------------------------

@pytest.mark.parametrize("collectives,others,want", [
    ([(10, 20)], [], 10),                        # nothing else runs
    ([(10, 20)], [(0, 30)], 0),                  # wholly hidden
    ([(10, 20)], [(0, 12), (18, 40)], 6),        # hidden at both ends
    ([(10, 20), (15, 30)], [(12, 14), (29, 35)], 17),   # a union, two holes
    ([(10, 20), (40, 50)], [(20, 40)], 20),      # touching is not covering
    ([(10, 20), (40, 50)], [(0, 45)], 5),        # one cover over two
    ([], [(0, 5)], 0),
])
def test_exposed(collectives, others, want):
    assert scopes.exposed(collectives, others) == want


def spans_of(starts, duration=2, name="hvd::shard_step::local_step",
             first_step=0):
    return [(name, s, duration, first_step + k)
            for k, s in enumerate(starts)]


def test_join_gives_the_lead_of_each_call():
    modules = [("jit_local_step(123)", 100, 40), ("jit_other(9)", 120, 5),
               ("jit_local_step(123)", 150, 40),
               ("jit_local_step(123)", 200, 40)]
    assert scopes.join(spans_of([90, 95, 101]), modules) == [10, 55, 99]
    # By step where the program wrote one, whatever the order of the list.
    shuffled = list(reversed(spans_of([90, 95, 101], first_step=7)))
    assert scopes.join(shuffled, modules) == [10, 55, 99]
    # A span with no execution in the trace, and a program with no span.
    assert scopes.join(spans_of([90, 95, 101, 130, 140]), modules) == \
        [10, 55, 99]
    assert scopes.join([], modules) == []
    assert scopes.join(spans_of([90], name="hvd::allreduce::x"),
                       modules) == []


def test_gaps_between_programs_name_the_open_span():
    modules = [("jit_local_step(1)", 100, 50), ("jit_local_step(1)", 170, 30)]
    ops = [(100, 120), (125, 150), (170, 200)]       # 120-125 is inside
    spans = spans_of([155], duration=10) + \
        [("hvd::allreduce::loss", 204, 2, None)]
    assert scopes.gaps_between_programs(ops, modules, spans, (90, 210)) == {
        "no hvd span": 10,                           # 90-100
        "hvd::shard_step::local_step": 20,           # 150-170
        "hvd::allreduce::loss": 10}                  # 200-210
    # Open when the gap starts, or when it ends; touching is not open.
    for span, want in (((140, 12), "hvd::x"), ((168, 30), "hvd::x"),
                       ((140, 10), "no hvd span"),
                       ((170, 5), "no hvd span")):
        gaps = scopes.gaps_between_programs(
            ops, modules, [("hvd::x", *span, None)], (100, 200))
        assert gaps == {want: 20}, span


def test_reduce_on_a_hand_built_trace():
    names = scopes.op_names(HLO)
    event = {name: f"%{name} = f32[8]{{0}} {opcode}(%x)" for name, opcode in
             (("psum_invariant.7", "all-reduce"),
              ("all-reduce-start.1", "all-reduce-start"),
              ("multiply_add_fusion.4", "fusion"), ("fusion.5", "fusion"),
              ("fusion.99", "fusion"))}
    devices = {"/device:TPU:0": {
        "ops": [(event["psum_invariant.7"], 100, 10),
                (event["all-reduce-start.1"], 110, 2),
                (event["multiply_add_fusion.4"], 112, 20),
                (event["fusion.5"], 140, 10),
                (event["fusion.99"], 150, 10)],
        "modules": [("jit_local_step(5)", 100, 60)],
        "async": [(event["all-reduce-start.1"], 110, 30)]}}
    t = scopes.reduce(devices, spans_of([70]), names,
                      scopes.scopes_held(HLO))
    ns = 1e-9
    assert t["window_s"] == pytest.approx(60 * ns)
    assert t["op_s"] == pytest.approx(52 * ns)
    assert t["busy_s"] == pytest.approx(52 * ns)
    assert t["by_phase"] == pytest.approx(
        {"forward": 10 * ns, "backward": 2 * ns, "optimizer": 20 * ns,
         "other": 0, "unscoped": 20 * ns})
    assert sum(t["by_phase"].values()) == pytest.approx(t["op_s"])
    assert t["batch_norm_s"] == pytest.approx(12 * ns)
    assert t["unmatched_s"] == pytest.approx(10 * ns)        # fusion.99
    assert t["cells"][("optimizer", "-", False)] == pytest.approx(20 * ns)
    assert t["holding"] == pytest.approx(
        {"hvd::optimizer": 20 * ns, "hvd::batch_norm": 12 * ns,
         "hvd::sync_bn_stats": 10 * ns})
    # The statistics' all-reduce is synchronous: all of it is exposed; the
    # backward one is in flight 110-140, and 112-132 hides behind a fusion.
    assert t["exposed_s"] == pytest.approx(
        {"sync_bn_stats forward": 10 * ns, "sync_bn backward": 10 * ns,
         "sync_bn": 20 * ns, "all": 20 * ns})
    assert t["in_flight_s"] == pytest.approx(
        {"sync_bn_stats forward": 10 * ns, "sync_bn backward": 30 * ns,
         "sync_bn": 40 * ns, "all": 40 * ns})
    assert t["leads_ms"] == pytest.approx([30e-6])
    assert t["host_dispatch_ms"] == pytest.approx([2e-6])
    assert t["gaps_between_programs"] == {}
    assert t["named"] == {"hvd::optimizer", "hvd::batch_norm",
                          "hvd::sync_bn_stats"}


def test_readers_and_the_logged_table_on_a_hand_built_run(capsys):
    """The readers and ``log_table`` never run past ``None`` on the CPU:
    hand them a run whose table and text are already made."""
    import types
    from harness import manifest as mf
    event = "%{0} = f32[8]{{0}} {1}(%x)".format
    devices = {"/device:TPU:0": {
        "ops": [(event("psum_invariant.7", "all-reduce"), 100, 10),
                (event("multiply_add_fusion.4", "fusion"), 110, 30),
                (event("fusion.5", "fusion"), 150, 10)],
        "modules": [("jit_local_step(5)", 100, 60)], "async": []}}
    t = scopes.reduce(devices, spans_of([70]), scopes.op_names(HLO),
                      scopes.scopes_held(HLO))
    run = types.SimpleNamespace(scopes={"table": t, "text": HLO}, results={})
    read = {name: mf.load_module("layer_metrics", name).read(run)
            for name in NEW_READERS}
    assert read == pytest.approx({
        "forward_share.train": 20.0, "backward_share.train": 0.0,
        "optimizer_share.train": 60.0, "batchnorm_share.train": 20.0,
        "exposed_collective_share.train": 100 * 10 / 60,
        "sync_bn_exposed_share.train": 100 * 10 / 60,
        "allreduce_mb.train": (68 + 1156 + 32) / 1e6,
        "host_dispatch_ms.train": 2e-6})
    logged = capsys.readouterr().out
    for line in ("scopes: phase optimizer 0.00000 s 60.00 %",
                 "scopes: unscoped instruction fusion.5",
                 "scopes: exposed collective time, sync_bn:",
                 "scopes: all-reduce gradients: 1 operations, 1156 bytes",
                 "scopes: 1 hvd::shard_step spans",
                 "scopes: lead of dispatch over execution: median 0.000 ms",
                 "0 started before their span"):
        assert line in logged, line
    # A program that writes no scope: the phases still read, its scopes'
    # metrics are absent, not zero.
    bare = dict(t, named=set(), host_dispatch_ms=[])
    run = types.SimpleNamespace(scopes={"table": bare, "text": HLO},
                                results={})
    absent = {name for name in NEW_READERS
              if mf.load_module("layer_metrics", name).read(run) is None}
    assert absent == {"optimizer_share.train", "batchnorm_share.train",
                      "sync_bn_exposed_share.train", "host_dispatch_ms.train"}


# -- the readers in the rehearsal cell ----------------------------------------

@pytest.fixture(scope="module")
def scopes_copy(tmp_path_factory):
    import overlay
    copy = overlay.make_copy(str(tmp_path_factory.mktemp("bench_scopes")))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_READERS:
            metric["workloads"] = metric["workloads"] + REHEARSALS
    manifest["per_layer"].append({
        "name": "named_instructions.train", "unit": "count",
        "better": "higher", "source": "program_counter",
        "layer": "step wrapper", "moves": "train_samples_per_s",
        "workloads": REHEARSALS})
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)
    return copy


@pytest.fixture(scope="module")
def traced_cpu4(scopes_copy):
    rc, lines, err = run_cell(scopes_copy, "resnet-tiny-train-cpu4", 1,
                              devices=4)
    assert rc == 0, err[-3000:]
    return json.loads(lines[-1]), lines


def test_manifest_lists_the_readers_and_each_has_its_file():
    from harness import manifest as mf
    manifest = mf.Manifest()
    listed = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["moves"] == "train_samples_per_s"
        assert hasattr(mf.load_module("layer_metrics", name), "read")
    dp4 = {m["name"] for m in
           manifest.metrics("per_layer", "resnet50-train-dp4")}
    one = {m["name"] for m in
           manifest.metrics("per_layer", "resnet50-train-1chip")}
    assert set(NEW_READERS) <= dp4
    assert set(NEW_READERS) - one == {"exposed_collective_share.train",
                                      "sync_bn_exposed_share.train",
                                      "allreduce_mb.train"}


def test_trace_readers_are_absent_on_the_cpu_never_zero(traced_cpu4):
    last, _ = traced_cpu4
    assert last["correct"] is True
    assert set(NEW_READERS) & set(last["metrics"]) == {"allreduce_mb.train"}


def test_allreduce_mb_is_what_the_shapes_say(traced_cpu4):
    """4 B x (parameters + the statistics of 17 batch norms, 2 C + 1 each,
    + the loss), less what XLA merges: a block's last batch norm and its
    projection's share the cotangent of their offsets (4 f floats a block
    with a projection).  On the CPU every all-reduce is float32."""
    last, _ = traced_cpu4
    channels = [8] + [c for f in (8, 16, 32, 64) for c in (f, f, 4 * f, 4 * f)]
    convs = 7 * 7 * 3 * 8 + sum(
        i * f + 9 * f * f + f * 4 * f + i * 4 * f
        for i, f in ((8, 8), (32, 16), (64, 32), (128, 64)))
    parameters = convs + 2 * sum(channels) + 256 * 10 + 10
    statistics = sum(2 * c + 1 for c in channels)
    shared = sum(4 * f for f in (8, 16, 32, 64))
    assert last["metrics"]["allreduce_mb.train"]["value"] * 1e6 == \
        pytest.approx(4 * (parameters + statistics + 1 - shared))


def test_op_names_of_the_rehearsal_step(traced_cpu4):
    last, lines = traced_cpu4
    assert last["metrics"]["named_instructions.train"]["value"] > 0
    found = next(l for l in lines if "scopes: found" in l).split()
    for scope in ("hvd::optimizer", "hvd::batch_norm", "hvd::sync_bn_stats",
                  "hvd::allreduce", "stem", "max_pool", "stage1", "stage2",
                  "stage3", "stage4", "head"):
        assert scope in found, scope
    phases = next(l for l in lines if "instructions by phase" in l)
    for phase in ("forward", "backward", "optimizer"):
        assert f"'{phase}'" in phases
    by_scope = [l for l in lines if "scopes: all-reduce " in l]
    assert any("sync_bn_stats forward: 13 operations" in l for l in by_scope)
    assert any("sync_bn backward" in l for l in by_scope)
    assert any("gradients" in l for l in by_scope)
