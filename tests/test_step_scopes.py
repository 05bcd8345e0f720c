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
