"""The names the training step carries from inside (ISSUE 25): ``hvd::``
scopes and the model's parts in the compiled step's ``op_name`` metadata,
and the ``hvd::shard_step::<function>`` host span on the profiler's clock.

A tiny ResNet through ``hvd.shard_step`` + ``DistributedOptimizer`` + sync
batch norm on four of the virtual devices.  Names are metadata: with
``metadata={...}`` stripped the compiled text is the one the program has
without them, and the parameter tree is what it was.
"""

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

STAGES = [1, 1, 1, 1]
PER_DEVICE, SIZE, CLASSES, DEVICES = 2, 32, 10, 4

# The parameter tree of ``ResNet(stage_sizes=[1, 1, 1, 1])``, recorded
# before the scopes went in: a named scope must not rename a parameter.
BLOCK = ["BatchNorm_0/bias", "BatchNorm_0/scale", "BatchNorm_1/bias",
         "BatchNorm_1/scale", "BatchNorm_2/bias", "BatchNorm_2/scale",
         "Conv_0/kernel", "Conv_1/kernel", "Conv_2/kernel",
         "conv_proj/kernel", "norm_proj/bias", "norm_proj/scale"]
PARAM_PATHS = sorted(
    [f"BottleneckBlock_{i}/{leaf}" for i in range(4) for leaf in BLOCK]
    + ["Dense_0/bias", "Dense_0/kernel", "bn_init/bias", "bn_init/scale",
       "conv_init/kernel"])


def _model(axis_name):
    from horovod_tpu.models.resnet import ResNet
    return ResNet(stage_sizes=STAGES, num_classes=CLASSES, num_filters=8,
                  dtype=jnp.bfloat16, axis_name=axis_name)


def _variables():
    return jax.jit(lambda key: _model(None).init(
        key, jnp.zeros((2, SIZE, SIZE, 3), jnp.float32), train=False))(
            jax.random.PRNGKey(0))


def _step_and_args(hvd):
    """The benchmark's step (``benchmarks/jobs/resnet.py``) at a tiny
    size, over a mesh of four devices."""
    mesh = hvd.parallel.make_mesh({"hvd": DEVICES},
                                  devices=jax.devices()[:DEVICES])
    model = _model("hvd")
    opt = hvd.DistributedOptimizer(optax.sgd(0.02, momentum=0.9))

    def local_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean(), mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        loss = hvd.allreduce(loss, op=hvd.Average, name="loss")
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_stats,
                opt_state, loss)

    step = hvd.parallel.shard_step(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P()))
    variables = _variables()
    params, stats = variables["params"], variables["batch_stats"]
    n = PER_DEVICE * DEVICES
    images = jax.random.uniform(jax.random.PRNGKey(1), (n, SIZE, SIZE, 3))
    labels = jnp.arange(n, dtype=jnp.int32) % CLASSES
    return step, (params, stats, opt.init(params), images, labels)


def _compiled_text(hvd) -> str:
    step, args = _step_and_args(hvd)
    return step.lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def step_text():
    import horovod_tpu as hvd
    hvd.shutdown()
    hvd.init()
    yield _compiled_text(hvd)
    hvd.shutdown()


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _stripped(text: str) -> str:
    """The compiled text without what names alone change: ``metadata`` and
    the tables of files, functions and stack frames it points into.  The
    numbers XLA appends to instruction names go too: the CPU compiler
    does not repeat them from one compile of the same program to the next
    (one ``convert`` of this step comes out as ``.1850`` or ``.1852``)."""
    text = re.sub(r",?\s*metadata=\{[^{}]*\}", "", text)
    head, tables, rest = text.partition("\nFileNames\n")
    if tables:
        rest = rest[re.search(r"\n\n(?=\S.*\{\n)", rest).end():]
    return re.sub(r"(%[A-Za-z_][\w\-]*?)(?:\.\d+)+\b", r"\1",
                  head + "\n" + rest)


@pytest.mark.parametrize("scope", [
    "hvd::optimizer", "hvd::optimizer/reduce_gradients",
    "hvd::optimizer/inner_update", "hvd::batch_norm",
    "hvd::batch_norm/hvd::sync_bn_stats", "stem", "max_pool", "stage1",
    "stage2", "stage3", "stage4", "head", "hvd::allreduce::loss"])
def test_compiled_step_holds_the_scope(step_text, scope):
    assert any(f"/{scope}/" in name + "/" for name in _op_names(step_text))


def test_parts_are_inside_the_model_and_in_both_passes(step_text):
    names = _op_names(step_text)
    for part in ("stem", "stage1", "stage4", "head"):
        assert any(f"/jvp(ResNet)/{part}/" in n for n in names), part
        assert any(f"/transpose(jvp(ResNet))/{part}/" in n
                   for n in names), part
    assert not any("hvd::optimizer" in n and "jvp(" in n for n in names)


def test_every_all_reduce_says_whose_it_is(step_text):
    """A statistic's all-reduce is under ``hvd::sync_bn_stats``, an
    explicit one under ``hvd::allreduce``, and every other one is a
    transpose of the backward pass (where the ones under
    ``hvd::batch_norm`` are sync batch norm's)."""
    lines = [l for l in step_text.split("\n")
             if re.search(r"\ball-reduce(?:-start)?\(", l)]
    assert lines
    kinds = set()
    for line in lines:
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        kind = ("stats" if "hvd::sync_bn_stats" in name
                else "explicit" if "hvd::allreduce" in name
                else "backward" if "transpose(jvp(" in name else None)
        assert kind, line[:300]
        kinds.add(kind)
        if kind == "stats":
            assert "transpose(" not in name
    assert {"stats", "backward"} <= kinds
    # The statistics' psum transposes to no collective (varying-axes
    # tracking): sync batch norm's backward all-reduce is the one under
    # ``hvd::batch_norm`` in the backward pass.
    assert any("transpose(jvp(" in re.search(r'op_name="([^"]*)"', l).group(1)
               and "hvd::batch_norm" in l for l in lines)


def test_parameter_tree_is_unchanged():
    flat = jax.tree_util.tree_flatten_with_path(_variables()["params"])[0]
    paths = sorted("/".join(k.key for k in path) for path, _ in flat)
    assert paths == PARAM_PATHS


OURS = re.compile(r"hvd::|(stem|max_pool|stage\d|head|reduce_gradients|"
                  r"inner_update)$")
_named_scope = jax.named_scope


@contextlib.contextmanager
def _no_scope_of_ours(name):
    """``jax.named_scope`` for flax's module names, nothing for the names
    this package adds."""
    with contextlib.nullcontext() if OURS.match(name) else \
            _named_scope(name):
        yield


def test_scopes_are_metadata_only(step_text, monkeypatch):
    """Stripped of metadata, the step's text equals the text compiled with
    ``jax.named_scope`` patched to do nothing for this package's names."""
    import horovod_tpu as hvd
    from horovod_tpu import sync_batch_norm
    monkeypatch.setattr(jax, "named_scope", _no_scope_of_ours)
    # The layer's class is built once, with its scope: build it anew.
    monkeypatch.setattr(sync_batch_norm, "_FusedBatchNorm", None)
    bare = _compiled_text(hvd)
    assert "hvd::" not in bare and "/stage1/" not in bare
    assert "hvd::optimizer" in step_text and "/stage1/" in step_text
    assert _stripped(bare) == _stripped(step_text)


@pytest.mark.parametrize("call,scope", [
    (lambda hvd, x: hvd.allreduce(x, name="loss"), "hvd::allreduce::loss"),
    (lambda hvd, x: hvd.allreduce(x), "hvd::allreduce"),
    (lambda hvd, x: hvd.grouped_allreduce([x, x], name="g")[0],
     "hvd::grouped_allreduce::g"),
    (lambda hvd, x: hvd.allgather(x, name="rows"), "hvd::allgather::rows"),
    (lambda hvd, x: hvd.broadcast(x, 0, name="w"), "hvd::broadcast::w"),
    (lambda hvd, x: hvd.alltoall(x, name="a2a"), "hvd::alltoall::a2a"),
    (lambda hvd, x: hvd.reducescatter(x, name="rs"),
     "hvd::reducescatter::rs"),
])
def test_in_trace_collective_is_named_like_the_eager_one(hvd8, call, scope):
    """``hvd::<kind>[::<name>]``, the label ops/eager.py gives the profiler
    for an eager dispatch, is the traced collective's named scope."""
    step = hvd8.parallel.shard_step(lambda x: call(hvd8, x),
                                    in_specs=(P("hvd"),), out_specs=P("hvd"))
    text = step.lower(jnp.ones((64, 4), jnp.float32)).as_text(
        debug_info=True)
    assert re.search(rf'["/]{re.escape(scope)}/', text), scope


@pytest.mark.parametrize("make,inner", [
    (lambda hvd: hvd.DistributedOptimizer(optax.sgd(0.1)), True),
    (lambda hvd: hvd.DistributedOptimizer(optax.sgd(0.1),
                                          backward_passes_per_step=2), True),
    (lambda hvd: optax.chain(hvd.distributed_gradient_transformation(),
                             optax.sgd(0.1)), False),
    (lambda hvd: hvd.PartialDistributedOptimizer(
        optax.sgd(0.1), local_filter=lambda path, leaf: False), True),
], ids=["default", "backward_passes", "bare_transformation", "partial"])
def test_every_optimizer_form_is_scoped(hvd8, make, inner):
    opt = make(hvd8)
    params = {"w": jnp.ones((4,), jnp.float32)}

    def local_step(params, opt_state, x):
        grads = jax.grad(lambda p: jnp.sum(p["w"] * x.sum(0)))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    step = hvd8.parallel.shard_step(
        local_step, in_specs=(P(), P(), P("hvd")), out_specs=(P(), P()))
    text = step.lower(params, opt.init(params),
                      jnp.ones((16, 4), jnp.float32)).as_text(
                          debug_info=True)
    assert "hvd::optimizer/reduce_gradients" in text
    assert "hvd::optimizer/hvd::optimizer" not in text
    assert ("hvd::optimizer/inner_update" in text) == inner


def test_host_span_of_each_call_is_in_the_profilers_trace(tmp_path):
    """Two steps under ``jax.profiler``: two ``hvd::shard_step::local_step``
    events on a host plane, with the call's index as ``step``."""
    import horovod_tpu as hvd
    from jax.profiler import ProfileData
    hvd.shutdown()
    hvd.init()
    try:
        step, (params, stats, opt_state, images, labels) = \
            _step_and_args(hvd)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for _ in range(2):
                params, stats, opt_state, loss = step(
                    params, stats, opt_state, images, labels)
            float(loss)
        finally:
            jax.profiler.stop_trace()
    finally:
        hvd.shutdown()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = [(plane.name, dict(e.stats).get("step"))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name == "hvd::shard_step::local_step"]
    assert sorted(step for _, step in events) == [0, 1]
    assert all(plane.startswith("/host:") for plane, _ in events)


# -- programs with names in serve/engine.py ------------------------------------

def test_serving_programs_have_names_of_their_own():
    """Every program the serving engine compiles is ``jit_<family>`` in a
    device trace (``XLA Modules``), not ``jit_fn`` or ``jit__lambda_``."""
    from horovod_tpu.models.transformer import Transformer, TransformerConfig
    from horovod_tpu.serve import MLPAdapter, TransformerAdapter
    from horovod_tpu.models.mlp import create_mlp
    cfg = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_len=64, causal=True,
                            dtype=jnp.float32, scan_layers=False)
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    ad = TransformerAdapter(cfg, params, block_tokens=8)
    cache = ad.init_paged_cache(6, 4)
    ad.copy_block(cache, 0, 1)
    programs = {
        "prefill_chunk": ad._build_prefill_chunk(2, 8, 6),
        "prefill_chunk_logits": ad._build_prefill_chunk_logits(2, 8, 6),
        "verify_chunk": ad._build_verify_chunk(2, 8, 6),
        "decode_paged": ad._build_paged_decode(4),
        "decode_paged_logits": ad._build_paged_decode_logits(4),
        "decode_paged_sampled": ad._build_paged_decode_sampled(4),
        "draft_decode": ad._build_draft_decode(4),
        "copy_block": ad._copy_block_fn,
    }
    mlp = create_mlp(features=(8, 61))
    mlp_params = mlp.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 61), jnp.float32))["params"]
    toy = MLPAdapter(mlp, mlp_params, vocab_size=61)
    programs.update(mlp_logits=toy._logits_of, mlp_greedy=toy._apply,
                    mlp_sampled=toy._sampled_step)
    names = {want: fn.__name__ for want, fn in programs.items()}
    assert names == {want: want for want in programs}
    # Seven transformer program families, and the pool's only other writer.
    assert len(programs) == 7 + 1 + 3
    assert len(set(names.values())) == len(programs)
    # The name of the function is the name of the lowered program.
    lowered = toy._apply.lower(jnp.zeros((2,), jnp.int32)).as_text()
    assert "@jit_mlp_greedy" in lowered
    lowered = ad._copy_block_fn.lower(
        cache, jnp.int32(0), jnp.int32(1)).as_text()
    assert "@jit_copy_block" in lowered


# -- the step as a partition (ISSUE 35) ----------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
MODELS = {"sdar_moe": "sdar-tiny", "afmoe": "trinity-tiny",
          "joyai_flash": "joyai-tiny", "kimi_linear": "kimi-tiny"}
#: What the package traced: inside a model's loss (JAX's marker holds the
#: outermost scope) or under any scope of ours.  The rest of a step is the
#: caller's own code (the job's ``optax.apply_updates``, its outputs).
OURS_TRACED = re.compile(r"jvp\((loss|ResNet)\)|hvd::")
NO_OPERATION = ("parameter", "constant", "tuple", "get-tuple-element",
                "bitcast", "while", "conditional", "call")


@pytest.fixture(scope="module")
def parts():
    """``benchmarks/harness/parts.py``, found by name as the benchmark
    finds it."""
    import importlib
    import sys
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("harness.parts")
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module", params=sorted(MODELS))
def model_texts(request, bench_job):
    """``(model, the compiled step's text, the text with this package's
    scopes patched out)`` of a benchmark job's program at its rehearsal
    size, over the eight virtual devices."""
    import json
    import horovod_tpu as hvd
    from horovod_tpu import scopes
    job = bench_job(request.param)
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           MODELS[request.param] + ".json")) as f:
        config = json.load(f)

    def text():
        program = job.Program(config, 1, 11)
        return program.hlo_text(program.fresh_state())

    hvd.shutdown()
    hvd.init()
    try:
        named = text()
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(scopes, "scope",
                          lambda name: contextlib.nullcontext())
            bare = text()
    finally:
        hvd.shutdown()
    return request.param, named, bare


def _without_a_part(parts, text):
    """``(ours, the caller's)``: the instructions of ``text`` that are
    operations, have an ``op_name`` and no part of the exported tuples."""
    step = parts.Step(text, *parts.exported())
    ours, callers = [], []
    for inst in step.instructions.values():
        if "/" not in (inst.op_name or "") or inst.opcode in NO_OPERATION \
                or inst.computation in step.reducers \
                or parts.part_of(inst.op_name, step.parts, step.collectives):
            continue
        (ours if OURS_TRACED.search(inst.op_name) else callers).append(
            (inst.opcode, inst.op_name))
    return ours, callers


def test_every_model_exports_its_parts():
    from horovod_tpu import scopes
    from horovod_tpu.models import (afmoe, joyai_flash, kimi_linear, resnet,
                                    sdar_moe)
    exported, collectives = scopes.exported_parts()
    assert exported[0] == "hvd::optimizer"
    for module in (resnet, sdar_moe, afmoe, joyai_flash, kimi_linear):
        assert set(module.PARTS) <= set(exported), module.__name__
    assert resnet.PARTS == ("stem", "max_pool", "stage1", "stage2",
                            "stage3", "stage4", "head")
    for module in (sdar_moe, afmoe, joyai_flash, kimi_linear):
        assert {"hvd::loss", "hvd::embed", "hvd::layer_loop", "hvd::moe",
                "hvd::lm_head_loss"} <= set(module.PARTS)
        assert not [p for p in module.PARTS if "::" in p[len("hvd::"):]]
    assert "hvd::mtp" in joyai_flash.PARTS
    assert {"hvd::kda_attention", "hvd::mla_attention",
            "hvd::dense_mlp"} <= set(kimi_linear.PARTS)
    assert "hvd::allreduce" in collectives and len(collectives) == 7


def test_no_operation_of_the_resnet_step_lacks_a_part(step_text, parts):
    ours, callers = _without_a_part(parts, step_text)
    assert ours == []
    # The test's own loss and its updates: outside the model, no part.
    assert any(name.endswith("jvp()/log") for _, name in callers)


def test_no_operation_of_a_models_step_lacks_a_part(model_texts, parts):
    """Coverage as a count: everything the package traced has a part;
    what has none is the job's own code, outside every scope."""
    _, named, _ = model_texts
    ours, callers = _without_a_part(parts, named)
    assert ours == []
    # The job's ``optax.apply_updates`` and its outputs, nothing else.
    assert {name.split("/")[-1].split(".")[0] for _, name in callers} <= {
        "add", "concatenate", "broadcast", "broadcast_in_dim", "reshape",
        "shard_map"}


def test_a_models_scopes_are_metadata_only(model_texts, parts):
    """Instructions are compared under ``parts.renamed``: XLA makes an
    instruction's name from its ``op_name`` (``%jvp_jit_remainder__``
    without a scope around it, ``%jit_remainder_`` with one)."""
    _, named, bare = model_texts
    assert "hvd::" not in bare and "/decoder/" not in bare
    assert "hvd::layer_loop" in named and "/decoder/" in named
    assert parts.renamed(_stripped(bare)) == parts.renamed(_stripped(named))


def test_gradient_sums_and_scatter_add_carry_their_part(model_texts, parts):
    """The transposed ``add_any`` sums over a layer's sequences are the
    layer loop's, the embedding's scatter-add is the embedding's."""
    model, named, _ = model_texts
    names = _op_names(named)
    exported = parts.exported()
    sums = [n for n in names if n.endswith("/add_any")
            and "/while/body/" in n]
    assert sums
    for name in sums:       # inside a loop: the layers' or the head's
        assert parts.part_of(name, *exported) != "hvd::loss", name
        assert "/hvd::layer_loop/" in name or "/hvd::lm_head_loss/" in name
    looped = {parts.part_of(n, *exported) for n in names
              if "transpose(jvp(loss))" in n and "/while/body/" in n}
    assert "hvd::layer_loop" in looped
    assert any(n.endswith("/hvd::loss/embed/hvd::embed/scatter-add")
               and "transpose(jvp(loss))" in n for n in names)
    assert any(n.endswith("/hvd::loss/embed/hvd::embed/gather")
               and "/jvp(loss)/" in n for n in names)
    if model == "joyai_flash":
        assert any("/mtp/hvd::mtp/embed/hvd::embed/" in n for n in names)
        assert any("/hvd::mtp/hvd::layer_loop/" in n for n in names)
    if model == "kimi_linear":
        # Kimi Delta Attention's five spans and the latent half's three
        # (the projections and the scan in both passes); the scan's own span
        # holds the kernels' operations (the interpreter inlines them here)
        # and lies inside the part.
        for half, spans in (("kda_attention", ("project", "conv", "gates",
                                               "scan", "out")),
                            ("mla_attention", ("compress", "expand",
                                               "out"))):
            for span in spans:
                inside = f"/hvd::{half}/hvd::{half}::{span}/"
                assert any(inside in n for n in names), inside
        for span in ("project", "scan"):
            inside = f"/hvd::kda_attention/hvd::kda_attention::{span}/"
            assert any(inside in n and "/jvp(loss)/" in n for n in names)
            assert any(inside in n and "transpose(jvp(loss))" in n
                       for n in names), inside
        scanned = [n for n in names if "hvd::kda_attention::scan" in n]
        assert all(parts.part_of(n, *exported) == "hvd::kda_attention"
                   for n in scanned)
        assert any("/hvd_kda_fwd" in n for n in scanned) \
            or any("/dot_general" in n for n in scanned)


# -- the reader of self time on a hand-built step -------------------------------

STEP = "jit(local_step)/"
LOSS = STEP + "jvp(loss)/hvd::loss/"
BACK = STEP + "transpose(jvp(loss))/hvd::loss/"
LOOP = "decoder/hvd::layer_loop/while/body/closed_call/"
HAND_PARTS = ("hvd::optimizer", "hvd::loss", "hvd::embed", "hvd::layer_loop",
              "hvd::bd_attention", "hvd::mla_attention", "hvd::moe",
              "hvd::lm_head_loss", "hvd::mtp", "stage1")
HAND_COLLECTIVES = ("hvd::allreduce", "hvd::allgather")
HAND_TEXT = f"""HloModule jit_local_step, is_scheduled=true

%region_0.1 (x.1: f32[], y.1: f32[]) -> f32[] {{
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%x.1, %y.1), metadata={{op_name="{STEP}reduce_sum"}}
}}

%fused_update (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %multiply.1 = f32[8]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{STEP}hvd::optimizer/inner_update/mul"}}
  ROOT %add.9 = f32[8]{{0}} add(%multiply.1, %param_0.1), metadata={{op_name="{STEP}add"}}
}}

%fused_relayout (param_0.2: f32[8]) -> f32[8] {{
  %param_0.2 = f32[8]{{0}} parameter(0)
  ROOT %negate.1 = f32[8]{{0}} negate(%param_0.2)
}}

%cond (p.1: (s32[], f32[8], f32[8])) -> pred[] {{
  %p.1 = (s32[], f32[8]{{0}}, f32[8]{{0}}) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %n.1 = s32[] constant(5)
  ROOT %lt.1 = pred[] compare(%i.1, %n.1), direction=LT
}}

%body (p: (s32[], f32[8], f32[8])) -> (s32[], f32[8], f32[8]) {{
  %p = (s32[], f32[8]{{0}}, f32[8]{{0}}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8]{{0}} get-tuple-element(%p), index=1
  %w = f32[8]{{0:S(1)}} get-tuple-element(%p), index=2
  %attn.1 = f32[8]{{0}} multiply(%x, %x), metadata={{op_name="{LOSS}{LOOP}hvd::bd_attention/mul"}}
  %experts.1 = f32[8]{{0}} add(%attn.1, %w), metadata={{op_name="{LOSS}{LOOP}hvd::moe/hvd::moe::experts/add"}}
  %sum.1 = f32[8]{{0}} add(%experts.1, %x), metadata={{op_name="{BACK}{LOOP}add_any"}}
  %c.2 = f32[] constant(0)
  %zeros.2 = f32[8]{{0}} broadcast(%c.2), dimensions={{}}
  %pad.1 = f32[8]{{0}} add(%zeros.2, %x), metadata={{op_name="{LOSS}{LOOP}hvd::bd_attention/add"}}
  %pad.2 = f32[8]{{0}} add(%zeros.2, %w), metadata={{op_name="{LOSS}{LOOP}hvd::moe/add"}}
  %next.1 = f32[8]{{0}} copy(%experts.1)
  %copy-start.2 = (f32[8]{{0:S(1)}}, f32[8]{{0}}, u32[]{{:S(2)}}) copy-start(%next.1)
  %copy-done.2 = f32[8]{{0:S(1)}} copy-done(%copy-start.2)
  ROOT %tuple.1 = (s32[], f32[8]{{0}}, f32[8]{{0:S(1)}}) tuple(%i, %sum.1, %copy-done.2)
}}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0), metadata={{op_name="params['embed']"}}
  %b = f32[8]{{0}} parameter(1), metadata={{op_name="tokens"}}
  %copy-start.1 = (f32[8]{{0:S(1)}}, f32[8]{{0}}, u32[]{{:S(2)}}) copy-start(%a)
  %copy-done.1 = f32[8]{{0:S(1)}} copy-done(%copy-start.1)
  %gather.1 = f32[8]{{0}} add(%copy-done.1, %b), metadata={{op_name="{LOSS}embed/hvd::embed/gather"}}
  %fusion.776 = f32[8]{{0}} fusion(%gather.1), kind=kLoop, calls=%fused_relayout
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{{0}}, f32[8]{{0}}) tuple(%zero, %fusion.776, %b)
  %while.1 = (s32[], f32[8]{{0}}, f32[8]{{0}}) while(%init), condition=%cond, body=%body, metadata={{op_name="{LOSS}decoder/hvd::layer_loop/while"}}
  %out = f32[8]{{0}} get-tuple-element(%while.1), index=1
  %head.1 = f32[8]{{0}} multiply(%out, %fusion.776), metadata={{op_name="{LOSS}head/hvd::lm_head_loss/dot_general"}}
  %mtp.1 = f32[8]{{0}} multiply(%head.1, %fusion.776), metadata={{op_name="{LOSS}mtp/hvd::mtp/mul"}}
  %mtp.2 = f32[8]{{0}} multiply(%mtp.1, %mtp.1), metadata={{op_name="{LOSS}mtp/hvd::mtp/hvd::layer_loop/while/body/closed_call/hvd::mla_attention/hvd::mla_attention::expand/mul"}}
  %mtp.3 = f32[8]{{0}} multiply(%mtp.2, %mtp.2), metadata={{op_name="{LOSS}mtp/hvd::mtp/head/hvd::lm_head_loss/dot_general"}}
  %bn.1 = f32[8]{{0}} multiply(%mtp.3, %mtp.3), metadata={{op_name="{STEP}jvp(ResNet)/stage1/BottleneckBlock_0/BatchNorm_0/hvd::batch_norm/mul"}}
  %mean.1 = f32[] reduce(%bn.1, %zero), dimensions={{0}}, to_apply=%region_0.1, metadata={{op_name="{LOSS}reduce_sum"}}
  %psum.1 = f32[8]{{0}} all-reduce(%bn.1), to_apply=%region_0.1, metadata={{op_name="{STEP}hvd::allreduce::loss/psum"}}
  %psum.2 = f32[8]{{0}} all-reduce(%psum.1), to_apply=%region_0.1, metadata={{op_name="{STEP}hvd::optimizer/reduce_gradients/hvd::allreduce/psum"}}
  %copy.5 = f32[8]{{0}} copy(%psum.2)
  %update.1 = f32[8]{{0}} fusion(%copy.5), kind=kLoop, calls=%fused_update, metadata={{op_name="{STEP}add"}}
  %callers.1 = f32[8]{{0}} add(%update.1, %b), metadata={{op_name="{STEP}add"}}
  %orphan.1 = f32[8]{{0}} copy(%b)
  ROOT %result = f32[8]{{0}} add(%callers.1, %update.1), metadata={{op_name="{STEP}hvd::optimizer/inner_update/add"}}
}}
"""
#: Instruction -> nanoseconds, one execution each.
HAND_TIMES = {
    "copy-start.1": 10, "copy-done.1": 40, "gather.1": 100,
    "fusion.776": 50, "while.1": 5000, "attn.1": 400, "experts.1": 300,
    "sum.1": 70, "zeros.2": 6, "pad.1": 4, "pad.2": 4, "next.1": 20, "copy-start.2": 5, "copy-done.2": 25,
    "head.1": 200, "mtp.1": 30, "mtp.2": 90, "mtp.3": 60, "bn.1": 80,
    "mean.1": 15, "psum.1": 35, "psum.2": 45, "copy.5": 12, "update.1": 110,
    "callers.1": 8, "orphan.1": 3, "result": 92}
HAND_OWNERS = {
    "copy-start.1": ("hvd::embed", "consumer"),
    "copy-done.1": ("hvd::embed", "consumer"),
    "gather.1": ("hvd::embed", "name"),
    # Its consumers (the loop, the head, the module) disagree: the producer.
    "fusion.776": ("hvd::embed", "producer"),
    "attn.1": ("hvd::bd_attention", "name"),
    "experts.1": ("hvd::moe", "name"),
    "sum.1": ("hvd::layer_loop", "name"),
    # Read by both halves and made from a constant: the loop's.
    "zeros.2": ("hvd::layer_loop", "container"),
    "pad.1": ("hvd::bd_attention", "name"),
    "pad.2": ("hvd::moe", "name"),
    # Carried to the next iteration, where the expert layer reads it.
    "next.1": ("hvd::moe", "consumers"),
    "copy-start.2": ("hvd::moe", "consumer"),
    "copy-done.2": ("hvd::moe", "consumer"),
    "head.1": ("hvd::lm_head_loss", "name"),
    "mtp.1": ("hvd::mtp", "name"),
    "mtp.2": ("hvd::mla_attention", "name"),
    "mtp.3": ("hvd::lm_head_loss", "name"),
    "bn.1": ("stage1", "name"),
    "mean.1": ("hvd::loss", "name"),
    "psum.1": ("hvd::allreduce::loss", "name"),
    "psum.2": ("hvd::optimizer", "name"),
    "copy.5": ("hvd::optimizer", "consumers"),
    "update.1": ("hvd::optimizer", "inside"),
    "callers.1": ("unattributed", "named, no part"),
    "orphan.1": ("unattributed", "no name"),
    "result": ("hvd::optimizer", "name")}


@pytest.fixture(scope="module")
def hand(parts):
    step = parts.Step(HAND_TEXT, HAND_PARTS, HAND_COLLECTIVES)
    event = "%{0} = f32[8]{{0}} {1}(%x)".format
    ops, modules, at = [], [], 1000
    for _ in range(2):                  # two steps, a gap before each copy
        began = at
        for name, d in HAND_TIMES.items():
            if name == "while.1":       # spans what it runs
                ops.append((event(name, "while"), at, d))
                continue
            at += 7 if name.startswith("copy-done") else 0
            ops.append((event(name, step.instructions[name].opcode), at, d))
            at += d
        modules.append(("jit_local_step(1)", began, at - began))
        at += 1000                      # between the two programs
    devices = {"/device:TPU:0": {"ops": ops, "async": [],
                                 "modules": modules}}
    return step, parts.reduce(devices, step)


@pytest.mark.parametrize("op_name,part", [
    (LOSS + LOOP + "hvd::moe/hvd::moe::experts/hvd_gmm/pallas_call",
     "hvd::moe"),
    (LOSS + "mtp/hvd::mtp/hvd::layer_loop/while/body/closed_call/"
     "hvd::mla_attention/hvd::mla_attention::compress/dot_general",
     "hvd::mla_attention"),
    (LOSS + "mtp/hvd::mtp/concatenate", "hvd::mtp"),
    (LOSS + "mtp/hvd::mtp/hvd::layer_loop/while/body/dynamic_slice",
     "hvd::layer_loop"),
    (LOSS + "mtp/hvd::mtp/embed/hvd::embed/gather", "hvd::embed"),
    (BACK + LOOP + "add_any", "hvd::layer_loop"),
    (BACK + "embed/hvd::embed/scatter-add", "hvd::embed"),
    (LOSS + "concatenate", "hvd::loss"),
    (STEP + "jvp(ResNet)/stage1/BottleneckBlock_0/BatchNorm_0/"
     "hvd::batch_norm/hvd::sync_bn_stats/psum", "stage1"),
    (STEP + "hvd::optimizer/reduce_gradients/hvd::allreduce/psum",
     "hvd::optimizer"),
    (STEP + "hvd::allreduce::loss/psum", "hvd::allreduce::loss"),
    (STEP + "hvd::allgather/all_gather", "hvd::allgather"),
    (STEP + "hvd::allreducer/psum", None),
    (STEP + "jvp(hvd::embed)/gather", None),
    (STEP + "add", None),
    (None, None),
])
def test_an_operations_part_is_the_innermost_exported_one(parts, op_name,
                                                          part):
    assert parts.part_of(op_name, HAND_PARTS, HAND_COLLECTIVES) == part


@pytest.mark.parametrize("name", sorted(HAND_OWNERS))
def test_every_instruction_has_one_owner_and_the_rule_that_placed_it(
        hand, name):
    step, _ = hand
    assert step.owner(name) == HAND_OWNERS[name]


def test_the_parts_self_times_sum_to_the_operations_time(hand):
    _, t = hand
    counted = {k: v for k, v in HAND_TIMES.items() if k != "while.1"}
    assert t["programs"] == 2
    assert t["op_s"] == pytest.approx(2 * sum(counted.values()) * 1e-9)
    assert sum(t["by_part"].values()) == pytest.approx(t["op_s"])
    assert sum(t["by_rule"].values()) == pytest.approx(t["op_s"])
    by_part = {}
    for name, d in counted.items():
        part = HAND_OWNERS[name][0]
        by_part[part] = by_part.get(part, 0) + 2 * d * 1e-9
    assert t["by_part"] == pytest.approx(by_part)
    # ``hvd::mtp`` is what the module does itself, not what nests in it.
    assert t["by_part"]["hvd::mtp"] == pytest.approx(2 * 30e-9)
    assert t["by_part"]["unattributed"] == pytest.approx(2 * 11e-9)
    assert t["left"] == pytest.approx({"callers.1": 16e-9,
                                       "orphan.1": 6e-9})


def test_compiler_made_copies_are_counted_where_they_were_placed(hand):
    step, t = hand
    copies = ("copy-start.1", "copy-done.1", "next.1", "copy-start.2",
              "copy-done.2", "copy.5", "orphan.1")
    assert t["copy_s"] == pytest.approx(
        2 * sum(HAND_TIMES[c] for c in copies) * 1e-9)
    assert set(t["by_copy"]) == set(copies)
    assert t["copies_by_part"]["hvd::embed"] == pytest.approx(2 * 50e-9)
    assert t["copies_by_part"]["hvd::moe"] == pytest.approx(2 * 50e-9)
    line = step.copy_line("copy-done.1")
    assert "f32[8] 32 bytes S(0) -> S(1) feeds gather.1 (add)" in line
    assert line.endswith("embed/hvd::embed/gather")
    assert "feeds experts.1" in step.copy_line("copy-start.2")
    assert t["inside"]["hvd::layer_loop", "add"] == pytest.approx(140e-9)


def test_idle_inside_a_program_goes_to_the_part_that_ends_it(hand):
    _, t = hand
    # Seven nanoseconds before each ``copy-done``, twice; the second
    # between the programs is no part's.
    assert t["gaps"] == pytest.approx({"hvd::embed": 14e-9,
                                       "hvd::moe": 14e-9})


def test_readers_are_absent_without_a_trace_or_without_parts(parts,
                                                             monkeypatch):
    import sys
    import types
    run = types.SimpleNamespace(scopes={"parts": None})
    assert parts.share(run, "hvd::embed") is None
    assert parts.copy_share(run) is None
    table = {"op_s": 2.0, "copy_s": 0.5, "parts": ("hvd::embed",),
             "by_part": {"hvd::embed": 0.25, "unattributed": 0.01}}
    run = types.SimpleNamespace(scopes={"parts": table})
    assert parts.share(run, "hvd::embed") == 12.5
    assert parts.share(run, "hvd::layer_loop") is None
    assert parts.share(run, parts.UNATTRIBUTED) == 0.5
    assert parts.copy_share(run) == 25.0
    # A program that exports no parts (the parent): nothing to read.
    monkeypatch.setitem(sys.modules, "horovod_tpu.scopes", None)
    assert parts.exported() is None


def test_kernel_bodies_compare_without_their_source_locations(parts):
    """The hash the traced run logs: a Pallas kernel's serialized body
    holds file names and line numbers; without them two programs that
    differ in names and source lines alone give one text."""
    import base64
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    def body(line):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(
                'module { func.func @k(%a: i32) -> i32 { %b = arith.addi '
                f'%a, %a : i32 loc("flash.py":{line}:0) return %b : i32 }} }}')
            from io import BytesIO
            out = BytesIO()
            module.operation.write_bytecode(out)
        return base64.b64encode(out.getvalue()).decode()

    text = ('  %k.1 = f32[8]{{0}} custom-call(%x), custom_call_target='
            '"tpu_custom_call", metadata={{op_name="{0}"}}, backend_config='
            '{{"custom_call_config":{{"body":"{1}"}}}}\n').format
    here, there = text("a/hvd::moe/k", body(10)), text("a/k", body(99))
    assert here != there
    assert parts.without_locations(here) == parts.without_locations(there)
    assert parts.without_locations(here) != parts.without_locations(
        here.replace("f32[8]", "f32[9]"))
