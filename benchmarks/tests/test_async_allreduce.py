"""``harness/async_collectives.py`` and the four readers cut from it, on
hand-built texts: a step whose all-reduces are all synchronous (the
parent's), one with an ``all-reduce-start``, and one in the TPU
compiler's form, an ``async_collective_fusion`` cut into a start half, a
piece that rides in a convolution fusion and a done half, each holding a
copy of the ``all-reduce``; and on hand-built device events."""

import json
import os
import types

import pytest

from conftest import run_cell

from harness import async_collectives as ac
from harness import manifest as mf

STEP = "jit(local_step)/shard_map/"
GRADIENT = STEP + "transpose(jvp(ResNet))/stage4/BottleneckBlock_15/Conv_1/" \
    "psum_invariant"
STATISTIC = STEP + "jvp(ResNet))/stage1/BottleneckBlock_0/BatchNorm_0/" \
    "hvd::batch_norm/hvd::sync_bn_stats/psum_invariant"


def all_reduce(name, shape, operand, op_name, opcode="all-reduce"):
    return (f'  %{name} = {shape} {opcode}(%{operand}), channel_id=1, '
            f'replica_groups={{{{0,1,2,3}}}}, to_apply=%region_0.0, '
            f'metadata={{op_name="{op_name}" stack_frame_id=6}}')


SYNCHRONOUS = "\n".join([
    "HloModule jit_local_step, is_scheduled=true", "",
    "ENTRY %main.1_spmd (param.1: bf16[3,3,8,8], param.2: f32[17]) -> "
    "bf16[3,3,8,8] {",
    "  %param.1 = bf16[3,3,8,8]{3,2,1,0} parameter(0)",
    "  %param.2 = f32[17]{0} parameter(1)",
    all_reduce("psum_invariant.7", "f32[17]{0}", "param.2", STATISTIC),
    all_reduce("all-reduce.164", "(bf16[3,3,8,8]{3,2,1,0}, f32[4]{0})",
               "param.1", GRADIENT),
    "  ROOT %copy.1 = bf16[3,3,8,8]{3,2,1,0} copy(%param.1)", "}", ""])

STARTED = SYNCHRONOUS.replace(
    all_reduce("all-reduce.164", "(bf16[3,3,8,8]{3,2,1,0}, f32[4]{0})",
               "param.1", GRADIENT),
    all_reduce("all-reduce-start.1", "bf16[3,3,8,8]{3,2,1,0}", "param.1",
               GRADIENT, opcode="all-reduce-start")
    + "\n  %all-reduce-done.1 = bf16[3,3,8,8]{3,2,1,0} "
      "all-reduce-done(%all-reduce-start.1)")

FUSED = "\n".join([
    "HloModule jit_local_step, is_scheduled=true", "",
    "%fused_computation.107 (param_0.195: bf16[3,3,8,8]) -> "
    "(bf16[3,3,8,8], u32[]) {",
    "  %param_0.195 = bf16[3,3,8,8]{3,2,1,0} parameter(0)",
    all_reduce("all-reduce.49", "bf16[3,3,8,8]{3,2,1,0}", "param_0.195",
               GRADIENT),
    '  ROOT %custom-call.1 = (bf16[3,3,8,8]{3,2,1,0}, u32[]) custom-call('
    '%param_0.195, %all-reduce.49), custom_call_target="AsyncCollectiveStart"',
    "}", "",
    "%async_collective_fusion.89 (param_0.198: bf16[3,3,8,8]) -> "
    "bf16[3,3,8,8] {",
    "  %param_0.198 = bf16[3,3,8,8]{3,2,1,0} parameter(0)",
    all_reduce("all-reduce.51", "bf16[3,3,8,8]{3,2,1,0}", "param_0.198",
               GRADIENT),
    "  ROOT %convolution.3 = bf16[3,3,8,8]{3,2,1,0} convolution("
    "%param_0.198, %all-reduce.51), dim_labels=b01f_01io->b01f",
    "}", "",
    "%fused_computation.109 (param_0.199: bf16[3,3,8,8]) -> bf16[3,3,8,8] {",
    "  %param_0.199 = bf16[3,3,8,8]{3,2,1,0} parameter(0)",
    all_reduce("all-reduce.53", "bf16[3,3,8,8]{3,2,1,0}", "param_0.199",
               GRADIENT),
    '  ROOT %custom-call.3 = bf16[3,3,8,8]{3,2,1,0} custom-call('
    '%param_0.199, %all-reduce.53), custom_call_target="AsyncCollectiveDone"',
    "}", "",
    "%fused_computation.7 (param_0.9: f32[17]) -> (f32[17], u32[]) {",
    "  %param_0.9 = f32[17]{0} parameter(0)",
    all_reduce("all-reduce.60", "f32[17]{0}", "param_0.9", STATISTIC),
    '  ROOT %custom-call.9 = (f32[17]{0}, u32[]) custom-call(%param_0.9, '
    '%all-reduce.60), custom_call_target="AsyncCollectiveStart"',
    "}", "",
    "ENTRY %main.1_spmd (param.1: bf16[3,3,8,8], param.2: f32[17]) -> "
    "bf16[3,3,8,8] {",
    "  %param.1 = bf16[3,3,8,8]{3,2,1,0} parameter(0)",
    "  %param.2 = f32[17]{0} parameter(1)",
    "  %async-collective-start.4 = (f32[17]{0}, u32[]) fusion(%param.2), "
    "kind=kCustom, calls=%fused_computation.7",
    all_reduce("all-reduce.170", "(bf16[1,1,8,8]{3,2,1,0}, f32[4]{0})",
               "param.1", GRADIENT),
    "  %async-collective-start.2 = (bf16[3,3,8,8]{3,2,1,0}, u32[]) "
    "fusion(%param.1), kind=kCustom, calls=%fused_computation.107",
    "  %fusion.89 = bf16[3,3,8,8]{3,2,1,0} fusion(%param.1), kind=kOutput, "
    "calls=%async_collective_fusion.89",
    "  %async-collective-done.2 = bf16[3,3,8,8]{3,2,1,0} fusion(%fusion.89), "
    "kind=kCustom, calls=%fused_computation.109",
    "  ROOT %copy.1 = bf16[3,3,8,8]{3,2,1,0} copy(%async-collective-done.2)",
    "}", ""])


READERS = ("async_allreduce_ops.train", "grad_allreduce_exposed_ms.train",
           "async_collective_exposed_share.train", "allreduce_wire_mb.train")


@pytest.fixture(scope="module")
def readers():
    return {name: mf.load_module("layer_metrics", name) for name in READERS}


def run_of(text, **results):
    return types.SimpleNamespace(scopes={"text": text}, results=results)


def event(inst, opcode="fusion"):
    return f"%{inst} = bf16[3,3,8,8]{{3,2,1,0}} {opcode}(%x), kind=kCustom"


#: Two steps on one device of the ``FUSED`` text: the tuple bucket runs
#: synchronously, the gradient fusion's pieces twice, the statistic's once
#: (its second done piece is missing: an unpaired start counts nothing).
DEVICES = {"/device:TPU:0": {
    "ops": [(event("all-reduce.170", "all-reduce"), 60, 30),
            (event("async-collective-start.2"), 100, 4),
            (event("fusion.89"), 104, 50),
            (event("async-collective-done.2"), 154, 6),
            (event("all-reduce.170", "all-reduce"), 260, 30),
            (event("async-collective-start.2"), 300, 4),
            (event("async-collective-done.2"), 340, 10),
            (event("async-collective-start.4"), 400, 3),
            (event("async-collective-done.4"), 420, 5),
            (event("async-collective-start.4"), 440, 3)],
    "modules": [("jit_local_step(1)", 50, 200), ("jit_local_step(1)", 250, 200)],
    "async": []}}


@pytest.mark.parametrize("text,count,wire_mb,logged", [
    (SYNCHRONOUS, 0, (68 + 1168) / 1e6,
     "gradients: 0 of 1 all-reduces asynchronous, 0 of 1168 bytes"),
    (STARTED, 1, (68 + 1152) / 1e6,
     "gradients: 1 of 1 all-reduces asynchronous, 1152 of 1152 bytes"),
    (FUSED, 1, (68 + 144 + 1152) / 1e6,
     "gradients: 1 of 2 all-reduces asynchronous, 1152 of 1296 bytes"),
], ids=["synchronous", "all-reduce-start", "async_collective_fusion"])
def test_counts_read_each_all_reduce_once(readers, capsys, text, count,
                                          wire_mb, logged):
    run = run_of(text)
    assert readers["async_allreduce_ops.train"].read(run) == count
    assert readers["allreduce_wire_mb.train"].read(run) == \
        pytest.approx(wire_mb)
    assert logged in capsys.readouterr().out
    # No device trace (the CPU rehearsal): the timed readings are absent.
    assert readers["grad_allreduce_exposed_ms.train"].read(run) is None
    assert readers["async_collective_exposed_share.train"].read(run) is None


def test_a_fusion_counts_once_and_names_its_pieces():
    assert sorted(ac.all_reduces(FUSED), key=lambda r: r.nbytes) == [
        ("sync_bn_stats forward", 68, None, "async-collective-start.4",
         "async-collective-done.4"),
        ("gradients", 144, "all-reduce.170", None, None),
        ("gradients", 1152, None, "async-collective-start.2",
         "async-collective-done.2")]
    started = [r for r in ac.all_reduces(STARTED) if r.start]
    assert started == [("gradients", 1152, None, "all-reduce-start.1",
                        "all-reduce-done.1")]


def test_core_time_pairs_each_start_with_its_done():
    by_scope = ac.core_time(DEVICES, ac.all_reduces(FUSED))
    assert by_scope["gradients"] == {
        "synchronous_ns": 60, "pieces_ns": 4 + 6 + 4 + 10,
        "in_flight_ns": 60 + 50, "executions": 2}
    assert by_scope["sync_bn_stats forward"] == {
        "synchronous_ns": 0, "pieces_ns": 3 + 5, "in_flight_ns": 25,
        "executions": 1}


def test_timed_readers_on_a_device_trace(readers, monkeypatch):
    monkeypatch.setattr(ac.tracing, "load", lambda trace_dir: DEVICES)
    run = run_of(FUSED, trace_dir="somewhere")
    # Per step and device: 30 ns synchronous + 12 ns of pieces.
    assert readers["grad_allreduce_exposed_ms.train"].read(run) == \
        pytest.approx((60 + 24) * 1e-6 / 2)
    # Pieces of both fusions over the window (60 .. 443).
    assert readers["async_collective_exposed_share.train"].read(run) == \
        pytest.approx(100.0 * (24 + 8) / 383)
    # The parent's step: the synchronous time is read, the share absent.
    parent = run_of(SYNCHRONOUS, trace_dir="somewhere")
    monkeypatch.setattr(ac.tracing, "load", lambda trace_dir: {
        "/device:TPU:0": {"ops": [(event("all-reduce.164", "all-reduce"),
                                   10, 40)],
                          "modules": [("jit_local_step(1)", 0, 100)],
                          "async": []}})
    assert readers["grad_allreduce_exposed_ms.train"].read(parent) == \
        pytest.approx(40e-6)
    assert readers["async_collective_exposed_share.train"].read(parent) \
        is None


def test_readers_through_the_cpu_rehearsal(tmp_path):
    """The four through ``run.py`` in a copy whose manifest lists them for
    the four-device rehearsal cell: the counts read the step compiled anew
    on the CPU (every all-reduce synchronous), the timed ones are absent."""
    import overlay
    copy = overlay.make_copy(str(tmp_path))
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("resnet-tiny-train-cpu4")
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, lines, err = run_cell(copy, "resnet-tiny-train-cpu4", 1, devices=4)
    assert rc == 0, err[-3000:]
    metrics = json.loads(lines[-1])["metrics"]
    assert metrics["async_allreduce_ops.train"]["value"] == 0
    assert metrics["allreduce_wire_mb.train"]["value"] > 0
    assert "grad_allreduce_exposed_ms.train" not in metrics
    assert "async_collective_exposed_share.train" not in metrics
    assert any("async_collectives: gradients: 0 of " in line
               for line in lines + err.split("\n"))


ENTRIES = [
    {"name": "async_allreduce_ops.train", "unit": "count",
     "better": "higher", "source": "program_counter"},
    {"name": "grad_allreduce_exposed_ms.train", "unit": "ms",
     "better": "lower", "source": "device_trace"},
    {"name": "async_collective_exposed_share.train", "unit": "%",
     "better": "lower", "source": "device_trace"},
    {"name": "allreduce_wire_mb.train", "unit": "MB", "better": "lower",
     "source": "program_counter"},
]


@pytest.mark.parametrize("position,entry", list(enumerate(ENTRIES)),
                         ids=[e["name"] for e in ENTRIES])
def test_manifest_lists_the_reader_for_the_four_chip_cell_only(position,
                                                               entry):
    listed = mf.Manifest().data["per_layer"][len(ENTRIES) * -1:][position]
    assert listed == dict(entry, layer="step wrapper",
                          moves="train_samples_per_s",
                          workloads=["resnet50-train-dp4"])
