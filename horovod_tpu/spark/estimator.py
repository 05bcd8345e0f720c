"""Spark ML Estimator layer: ``HorovodTpuEstimator.fit(df)`` → trained
``TpuTransformer``.

Reference: horovod/spark/common/estimator.py:25 (HorovodEstimator: fit
materializes the DataFrame to Parquet via a Store, trains inside
horovod.spark.run, returns a Spark ML Transformer holding the model) and
keras/estimator.py:98 (parameter surface).  The petastorm reader stack is
replaced by plain pyarrow Parquet readers sharded by row group
(store.shard_row_groups) — petastorm existed to stream Parquet into
framework tensors; pyarrow → numpy → jax does that directly.

Works with or without pyspark:

* a **pyspark DataFrame** is written with ``df.write.parquet`` and training
  launches on Spark barrier tasks (spark_integration.run);
* a **pandas DataFrame** (or anything ``pandas.DataFrame(data)`` accepts)
  is written with pyarrow and training launches through the local
  multi-process launcher (``horovod_tpu.run``) — the same per-rank training
  function either way.
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Callable, List, Optional, Sequence, Union

from .store import Store, shard_row_groups


def _is_spark_df(df) -> bool:
    mod = type(df).__module__ or ""
    return mod.startswith("pyspark.")


def _resolve_loss(loss) -> Callable:
    """Accept a callable(pred, label)->scalar or a named loss
    (keras/estimator.py accepts keras loss names)."""
    if callable(loss):
        return loss
    import jax.numpy as jnp
    import optax
    name = str(loss).lower()
    if name in ("mse", "mean_squared_error"):
        return lambda p, y: jnp.mean((p - y) ** 2)
    if name in ("mae", "mean_absolute_error"):
        return lambda p, y: jnp.mean(jnp.abs(p - y))
    if name in ("sparse_categorical_crossentropy", "softmax_cross_entropy",
                "cross_entropy"):
        return lambda p, y: optax.softmax_cross_entropy_with_integer_labels(
            p, y).mean()
    raise ValueError(f"unknown loss {loss!r}; pass a callable(pred, label)")


def _columns_to_array(table_cols: dict, cols: Sequence[str]):
    """Assemble named columns into one [n, ...] numpy array: scalar columns
    stack to [n, len(cols)]; a single list-valued column keeps its row
    shape [n, k] (the reference's DenseVector feature column analog)."""
    import numpy as np
    arrs = []
    for c in cols:
        v = table_cols[c]
        first = v[0]
        if isinstance(first, (list, tuple, np.ndarray)):
            arrs.append(np.stack([np.asarray(x) for x in v]))
        else:
            arrs.append(np.asarray(v))
    if len(arrs) == 1:
        return arrs[0]
    return np.stack(arrs, axis=-1)


class RowGroupStream:
    """Streams a rank's (file, row_group) units one group at a time —
    the petastorm-reader contract the reference's estimator relies on
    (spark/common/estimator.py:25: bigger-than-memory shards stream from
    Parquet): peak memory is one row group plus a partial batch, never
    the whole shard.  Epoch shuffling is two-level, the standard
    streaming scheme: the row-group ORDER is re-permuted every epoch and
    rows shuffle within each group; successive epochs see different
    batch compositions without ever materializing the shard.

    ``peak_rows_resident`` records the largest row count ever held, so
    tests can assert the bounded-memory contract on shards much larger
    than the budget."""

    # Open-file cache bound: a shard spanning hundreds of Parquet files
    # must not hold one fd per file for the fit's lifetime (the bounded-
    # resource claim covers descriptors too); a few stay open because the
    # per-epoch group shuffle revisits files in mixed order.
    MAX_OPEN_FILES = 4

    def __init__(self, units, feature_cols, label_cols, filesystem=None,
                 seed: int = 0):
        self.units = list(units)
        self.feature_cols = list(feature_cols)
        self.label_cols = list(label_cols)
        self.filesystem = filesystem
        self.seed = seed
        self._files: dict = {}  # insertion-ordered: LRU eviction
        self.peak_rows_resident = 0

    def _pf(self, f):
        if f in self._files:
            entry = self._files.pop(f)  # re-insert: most-recently-used
            self._files[f] = entry
            return entry[0]
        while len(self._files) >= self.MAX_OPEN_FILES:
            self._close_one(next(iter(self._files)))
        import pyarrow.parquet as pq
        src = self.filesystem.open(f, "rb") \
            if self.filesystem is not None else f
        pf = pq.ParquetFile(src)
        self._files[f] = (pf, src if src is not f else None)
        return pf

    def _close_one(self, f) -> None:
        pf, src = self._files.pop(f)
        for h in (pf, src):
            if h is None:
                continue
            try:
                h.close()
            except Exception:
                pass

    def close(self) -> None:
        """Release every open Parquet handle (idempotent)."""
        for f in list(self._files):
            self._close_one(f)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def num_rows(self) -> int:
        """Total rows across the shard, from metadata only (no data read)."""
        return sum(self._pf(f).metadata.row_group(g).num_rows
                   for f, g in self.units)

    def _read_group(self, f, g):
        import numpy as np
        d = self._pf(f).read_row_group(g).to_pydict()
        X = _columns_to_array(d, self.feature_cols)
        Y = _columns_to_array(d, self.label_cols)
        return np.asarray(X), np.asarray(Y)

    def iter_groups(self):
        """(X, Y) per row group — validation evaluates group-wise."""
        for f, g in self.units:
            yield self._read_group(f, g)

    def iter_batches(self, batch: int, epoch: int = 0,
                     shuffle: bool = True):
        """Exactly-``batch``-row arrays (static shapes for jit), streamed.
        Yields floor(num_rows / batch) batches, or one wrap-filled batch
        when the shard is smaller than a batch.  The sub-batch tail of
        each group carries into the next group's batches."""
        import numpy as np
        rng = np.random.RandomState(self.seed * 100003 + epoch)
        order = list(self.units)
        if shuffle:
            rng.shuffle(order)
        carryX = carryY = None
        yielded = 0
        for f, g in order:
            X, Y = self._read_group(f, g)
            if shuffle:
                p = rng.permutation(len(X))
                X, Y = X[p], Y[p]
            if carryX is not None and len(carryX):
                X = np.concatenate([carryX, X])
                Y = np.concatenate([carryY, Y])
            self.peak_rows_resident = max(self.peak_rows_resident, len(X))
            i = 0
            while i + batch <= len(X):
                yield X[i:i + batch], Y[i:i + batch]
                yielded += 1
                i += batch
            carryX, carryY = X[i:], Y[i:]
        if yielded == 0 and carryX is not None and len(carryX):
            # Shard smaller than one batch: wrap-fill (static shapes).
            reps = -(-batch // len(carryX))
            yield (np.concatenate([carryX] * reps)[:batch],
                   np.concatenate([carryY] * reps)[:batch])


def _estimator_train_fn(cfg: dict) -> List[dict]:
    """Per-rank training body (reference: torch/remote.py:107 RemoteTrainer
    — runs inside every Spark task / launcher worker)."""
    if cfg.get("platform"):
        import jax
        jax.config.update("jax_platforms", cfg["platform"])
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd

    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    store: Store = cfg["store"]

    import contextlib
    with contextlib.ExitStack() as streams:
        fs = store.fs()
        units = shard_row_groups(store.get_parquet_files(cfg["train_path"]),
                                 rank, size, filesystem=fs)
        stream = streams.enter_context(
            RowGroupStream(units, cfg["feature_cols"], cfg["label_cols"],
                           filesystem=fs, seed=cfg["seed"] + rank))
        total_rows = stream.num_rows()
        if total_rows == 0:
            raise ValueError(
                f"rank {rank} received no parquet row groups; write the "
                f"training data with at least {size} row groups "
                f"(row_group_size small enough) or lower num_proc")
        vstream = None
        if cfg.get("val_path"):
            vunits = shard_row_groups(
                store.get_parquet_files(cfg["val_path"]), rank, size,
                filesystem=fs)
            vstream = streams.enter_context(
                RowGroupStream(vunits, cfg["feature_cols"],
                               cfg["label_cols"], filesystem=fs))
        return _estimator_train_loop(cfg, stream, vstream, total_rows)


def _estimator_train_loop(cfg, stream, vstream, total_rows) -> List[dict]:
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd

    rank = hvd.rank()
    store: Store = cfg["store"]
    model, loss_fn = cfg["model"], _resolve_loss(cfg["loss"])
    batch = cfg["batch_size"]
    X0, _ = next(stream.iter_batches(min(batch, total_rows), epoch=0,
                                     shuffle=False))
    params = model.init(jax.random.PRNGKey(cfg["seed"]),
                        jnp.asarray(X0[:1]))
    # Rank 0's initialization reaches everyone (BroadcastGlobalVariables
    # idiom) — model.init is deterministic here, but user models may not be.
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt = hvd.DistributedOptimizer(cfg["optimizer"])
    opt_state = opt.init(params)

    @jax.jit
    def grad_step(p, xb, yb):
        return jax.value_and_grad(
            lambda q: loss_fn(model.apply(q, xb), yb))(p)

    @jax.jit
    def eval_loss(p, xb, yb):
        return loss_fn(model.apply(p, xb), yb)

    # Equal step counts across ranks: collectives are SPMD-total, so every
    # rank must dispatch the same number of optimizer updates per epoch
    # (the reference equalizes via steps_per_epoch / join; MIN-allreduce of
    # the local batch count is the static-shape-friendly form).
    local_steps = max(total_rows // batch, 1)
    nsteps = int(hvd.allreduce(jnp.asarray(float(local_steps)),
                               op=hvd.Min, name="est.steps"))
    from ..callbacks import CallbackList
    cbs = CallbackList(cfg.get("callbacks") or [])
    cbs.on_train_begin()
    history: List[dict] = []
    for epoch in range(cfg["epochs"]):
        cbs.on_epoch_begin(epoch)
        # Streamed batches, two-level shuffle per epoch (RowGroupStream):
        # the shard never materializes — bigger-than-memory shards train
        # at one-row-group peak memory (the petastorm contract).
        batches = stream.iter_batches(batch, epoch=epoch,
                                      shuffle=cfg["shuffle"])
        ep_loss = 0.0
        for _ in range(nsteps):
            xb, yb = next(batches)
            loss, grads = grad_step(params, jnp.asarray(xb),
                                    jnp.asarray(yb))
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            ep_loss += float(loss)
        entry = {"epoch": epoch, "loss": float(hvd.allreduce(
            jnp.asarray(ep_loss / nsteps), op=hvd.Average,
            name="est.loss"))}
        if cfg.get("val_path"):
            # EVERY rank dispatches this collective even if its shard got no
            # validation row groups (collectives are SPMD-total; a guarded
            # dispatch would deadlock).  Weighted sum handles the raggedness.
            # Validation streams group-wise too: the row-weighted sum over
            # groups equals the full-shard loss for mean-reducing losses.
            vloss_sum, vrows = 0.0, 0.0
            if vstream is not None:
                for vxb, vyb in vstream.iter_groups():
                    if len(vxb) == 0:
                        continue
                    vloss_sum += float(eval_loss(
                        params, jnp.asarray(vxb),
                        jnp.asarray(vyb))) * len(vxb)
                    vrows += len(vxb)
            agg = hvd.allreduce(jnp.asarray([vloss_sum, vrows]), op=hvd.Sum,
                                name="est.val_loss")
            if float(agg[1]) > 0:
                entry["val_loss"] = float(agg[0]) / float(agg[1])
        history.append(entry)
        if cfg["verbose"] and rank == 0:
            print(f"[estimator] epoch {epoch + 1}/{cfg['epochs']}: {entry}")
        # Fit callbacks (the reference estimators accept Keras callbacks).
        # The metrics in ``entry`` are allreduce-averaged, so callback
        # decisions (e.g. EarlyStoppingCallback) are rank-consistent and
        # every rank breaks out of the epoch loop together — an
        # inconsistent break would strand peers in the next epoch's
        # collectives.
        cbs.on_epoch_end(epoch, logs=entry)
        if cbs.stop_training:
            if cfg["verbose"] and rank == 0:
                print(f"[estimator] early stop after epoch {epoch + 1}")
            break
    if rank == 0:
        store.write_obj(store.get_checkpoint_path(cfg["run_id"]), {
            "params": jax.device_get(params),
            "history": history,
            "feature_cols": cfg["feature_cols"],
            "label_cols": cfg["label_cols"],
        })
    return history


class HorovodTpuEstimator:
    """Estimator with the reference's fit contract
    (spark/common/estimator.py:25; parameter names follow
    keras/estimator.py:98).

    Args:
      model: a flax ``linen.Module`` (anything with ``.init(rng, x)`` /
        ``.apply(params, x)``).
      optimizer: an optax gradient transformation.
      loss: callable(pred, label) -> scalar, or one of "mse", "mae",
        "sparse_categorical_crossentropy".
      feature_cols / label_cols: DataFrame column names.
      store: a ``Store`` (defaults to a LocalStore under /tmp).
      validation: fraction in (0, 1) for a random split, or the name of a
        boolean column selecting validation rows (estimator.py semantics).
      num_proc: ranks to train with (Spark tasks or local processes).
      callbacks: fit callbacks (horovod_tpu.callbacks.Callback objects,
        cloudpickled to the workers): ``on_epoch_end(epoch, logs)`` fires
        with the rank-averaged metrics entry, and a callback setting
        ``stop_training`` (e.g. EarlyStoppingCallback) ends the fit on
        every rank together — the Keras-callback surface the reference's
        estimators accept.
      worker_platform: force a jax platform inside workers (tests use
        "cpu"; leave None on real TPU hosts).
    """

    def __init__(self,
                 model=None,
                 optimizer=None,
                 loss=None,
                 feature_cols: Optional[Sequence[str]] = None,
                 label_cols: Optional[Sequence[str]] = None,
                 batch_size: int = 32,
                 epochs: int = 1,
                 validation: Union[None, float, str] = None,
                 store: Optional[Store] = None,
                 num_proc: int = 1,
                 shuffle: bool = True,
                 verbose: int = 1,
                 run_id: Optional[str] = None,
                 random_seed: int = 0,
                 callbacks: Optional[list] = None,
                 worker_platform: Optional[str] = None):
        if model is None or optimizer is None or loss is None:
            raise ValueError("model, optimizer and loss are required")
        if not feature_cols or not label_cols:
            raise ValueError("feature_cols and label_cols are required")
        _resolve_loss(loss)  # validate early
        self.model = model
        self.optimizer = optimizer
        self.loss = loss
        self.feature_cols = list(feature_cols)
        self.label_cols = list(label_cols)
        self.batch_size = batch_size
        self.epochs = epochs
        self.validation = validation
        self.store = store
        self.num_proc = num_proc
        self.shuffle = shuffle
        self.verbose = verbose
        self.run_id = run_id
        self.random_seed = random_seed
        self.callbacks = list(callbacks or [])
        self.worker_platform = worker_platform
        self.history: List[dict] = []

    # -- data materialization (spark/common/util.py prepare_data analog) ----

    def _write_parquet(self, df, store: Store):
        """Materialize ``df`` under the store's intermediate paths; returns
        (train_path, val_path or None)."""
        train_path = store.get_train_data_path()
        val_path = store.get_val_data_path()
        if _is_spark_df(df):
            train_df, val_df = self._split_spark(df)
            train_df.write.mode("overwrite").parquet(train_path)
            if val_df is not None:
                val_df.write.mode("overwrite").parquet(val_path)
            return train_path, (val_path if val_df is not None else None)
        return self._write_pandas(df, store, train_path, val_path)

    def _split_spark(self, df):
        if self.validation is None:
            return df, None
        if isinstance(self.validation, str):
            return (df.filter(f"NOT {self.validation}"),
                    df.filter(self.validation))
        frac = float(self.validation)
        train_df, val_df = df.randomSplit([1.0 - frac, frac],
                                          seed=self.random_seed)
        return train_df, val_df

    def _write_pandas(self, df, store: Store, train_path: str,
                      val_path: str):
        import numpy as np
        import pandas as pd
        import pyarrow as pa
        import pyarrow.parquet as pq
        if not isinstance(df, pd.DataFrame):
            df = pd.DataFrame(df)
        if self.validation is None:
            train_df, val_df = df, None
        elif isinstance(self.validation, str):
            mask = df[self.validation].astype(bool)
            train_df = df[~mask].drop(columns=[self.validation])
            val_df = df[mask].drop(columns=[self.validation])
        else:
            rng = np.random.RandomState(self.random_seed)
            mask = rng.rand(len(df)) < float(self.validation)
            train_df, val_df = df[~mask], df[mask]

        def write(frame, path):
            # Enough row groups that every rank gets data
            # (store.shard_row_groups shards by row group).
            rows_per_group = max(1, len(frame) // max(self.num_proc * 4, 1))
            fs = store.fs()
            p = store._strip(path)
            fs.makedirs(p, exist_ok=True)
            pq.write_table(pa.Table.from_pandas(frame.reset_index(drop=True)),
                           f"{p}/part-00000.parquet",
                           row_group_size=rows_per_group,
                           filesystem=fs)

        write(train_df, train_path)
        if val_df is not None and len(val_df):
            write(val_df, val_path)
            return train_path, val_path
        return train_path, None

    # -- fit (estimator.py:25 fit -> Transformer) ---------------------------

    def fit(self, df) -> "TpuTransformer":
        from .store import LocalStore
        store = self.store
        if store is None:
            import tempfile
            store = LocalStore(tempfile.mkdtemp(prefix="hvd_tpu_store_"))
        run_id = self.run_id or \
            f"run_{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:6]}"
        train_path, val_path = self._write_parquet(df, store)
        cfg = {
            "model": self.model, "optimizer": self.optimizer,
            "loss": self.loss, "feature_cols": self.feature_cols,
            "label_cols": self.label_cols, "batch_size": self.batch_size,
            "epochs": self.epochs, "shuffle": self.shuffle,
            "verbose": self.verbose, "seed": self.random_seed,
            "callbacks": self.callbacks,
            "store": store, "run_id": run_id,
            "train_path": train_path, "val_path": val_path,
            "platform": self.worker_platform,
        }
        try:
            import pyspark
            from pyspark import SparkContext
            has_spark_ctx = SparkContext._active_spark_context is not None
        except ImportError:
            has_spark_ctx = False
        if has_spark_ctx and _is_spark_df(df):
            from .. import spark_integration
            results = spark_integration.run(
                _estimator_train_fn, args=(cfg,), num_proc=self.num_proc)
        else:
            from .. import runner
            results = runner.run(_estimator_train_fn, args=(cfg,),
                                 np=self.num_proc)
        self.history = results[0]
        ckpt = store.read_obj(store.get_checkpoint_path(run_id))
        return TpuTransformer(model=self.model, params=ckpt["params"],
                              feature_cols=self.feature_cols,
                              label_cols=self.label_cols,
                              history=ckpt["history"], run_id=run_id,
                              store=store)


def _append_predictions(model, params, feature_cols, outs, pdf):
    """Predict one pandas frame and append ``<label>__output`` columns —
    the single definition shared by distributed (mapInPandas) and
    in-process transform so the two paths cannot diverge."""
    import numpy as np
    import jax.numpy as jnp
    pdf = pdf.copy()
    if len(pdf) == 0:
        # Empty partitions are routine after filters/repartitions; emit
        # the frame with empty output columns, matching schema.
        for c in outs:
            pdf[c] = []
        return pdf
    cols = {c: list(pdf[c]) for c in feature_cols}
    X = _columns_to_array(cols, feature_cols)
    pred = np.asarray(model.apply(params, jnp.asarray(X)))
    if len(outs) == 1:
        pdf[outs[0]] = list(pred) if pred.ndim > 1 else pred
    else:
        for i, c in enumerate(outs):
            pdf[c] = pred[..., i]
    return pdf


def _transform_partition(payload: bytes, frames):
    """Executor-side batch predictor for ``TpuTransformer.transform`` on a
    pyspark DataFrame (the mapInPandas UDF body, factored out so the logic
    is unit-testable without a Spark cluster).  ``payload`` is a
    cloudpickled {model, params (host copies), feature_cols, label_cols};
    yields each incoming pandas frame with ``<label>__output`` columns
    appended.  Reference: HorovodModel.transform's pandas-UDF per-partition
    prediction (spark/torch/estimator.py, keras/estimator.py)."""
    import os
    # Executors have no accelerator claim; force the CPU backend before
    # jax initializes (a chip belongs to one process at a time).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import cloudpickle
    d = cloudpickle.loads(payload)
    outs = [f"{c}__output" for c in d["label_cols"]]
    for pdf in frames:
        yield _append_predictions(d["model"], d["params"],
                                  d["feature_cols"], outs, pdf)


class TpuTransformer:
    """Trained-model Transformer (spark/common/estimator.py
    HorovodModel.transform analog): adds ``<label>__output`` prediction
    columns.  Accepts a pandas or pyspark DataFrame; pyspark input is
    predicted DISTRIBUTED on the executors via ``mapInPandas`` (the
    reference's pandas-UDF pattern), pandas input on the caller."""

    def __init__(self, model, params, feature_cols, label_cols,
                 history=None, run_id=None, store=None):
        self.model = model
        self.params = params
        self.feature_cols = list(feature_cols)
        self.label_cols = list(label_cols)
        self.history = history or []
        self.run_id = run_id
        self.store = store

    def output_cols(self) -> List[str]:
        return [f"{c}__output" for c in self.label_cols]

    def predict(self, X):
        import jax.numpy as jnp
        return self.model.apply(self.params, jnp.asarray(X))

    def _udf_payload(self) -> bytes:
        import cloudpickle
        import jax
        return cloudpickle.dumps({
            "model": self.model, "params": jax.device_get(self.params),
            "feature_cols": self.feature_cols,
            "label_cols": self.label_cols})

    def transform(self, df):
        import numpy as np
        if _is_spark_df(df):
            # DISTRIBUTED inference: each executor partition predicts via
            # _transform_partition (mapInPandas), never funneling rows
            # through the driver.  The output schema extends the input with
            # one column per label; its Spark type is inferred from a
            # one-row driver-side prediction (array column for vector
            # outputs, double for scalars).
            from pyspark.sql.types import (
                ArrayType, DoubleType, StructField, StructType)
            sample = df.limit(1).toPandas()
            if len(sample) == 0:
                # Empty DataFrame: no row to infer the vector-vs-scalar
                # output shape from; default to scalar columns.  Caveat: a
                # vector-output model's empty transform then has DoubleType
                # where a non-empty one has ArrayType — unioning the two
                # needs an explicit cast (unknowable here without a row).
                out_type = DoubleType()
            else:
                scols = {c: list(sample[c]) for c in self.feature_cols}
                spred = np.asarray(self.predict(
                    _columns_to_array(scols, self.feature_cols)))
                out_type = ArrayType(DoubleType()) if spred.ndim > 1 \
                    and len(self.output_cols()) == 1 else DoubleType()
            schema = StructType(list(df.schema.fields) + [
                StructField(c, out_type, True) for c in self.output_cols()])
            payload = self._udf_payload()
            return df.mapInPandas(
                lambda frames: _transform_partition(payload, frames),
                schema=schema)
        import pandas as pd
        pdf = df if isinstance(df, pd.DataFrame) else pd.DataFrame(df)
        return _append_predictions(self.model, self.params,
                                   self.feature_cols, self.output_cols(),
                                   pdf)

    # -- persistence (Spark ML write().save analog) -------------------------

    def save(self, path: str) -> None:
        import cloudpickle
        from .store import FilesystemStore
        st = self.store or FilesystemStore(path.rsplit("/", 1)[0] or ".")
        st.write_bytes(path, cloudpickle.dumps({
            "model": self.model, "params": self.params,
            "feature_cols": self.feature_cols,
            "label_cols": self.label_cols, "history": self.history,
        }))

    @staticmethod
    def load(path: str) -> "TpuTransformer":
        import cloudpickle
        from .store import FilesystemStore
        st = FilesystemStore(path.rsplit("/", 1)[0] or ".")
        d = cloudpickle.loads(st.read_bytes(path))
        return TpuTransformer(**d)
