#!/usr/bin/env python
"""End-to-end data pipeline: ETL -> rank-sharded training with
checkpoint/resume -> batch inference -> predictions file.

This is the TPU-native counterpart of the reference's largest example,
the Rossmann store-sales pipeline (examples/keras_spark_rossmann.py:
Spark ETL -> feature engineering -> distributed Keras training ->
inference writing a submission file). Same pipeline shape, JAX-native
stages, tabular regression like the original:

  1. **ETL** (rank 0): raw "sales log" records -> feature engineering
     (normalization, one-hot calendar features) -> shard files on disk
     (the Parquet-stage equivalent), with a held-out inference split.
     Other ranks wait on a barrier allreduce.
  2. **Train**: every rank reads ONLY its shard files
     (``files[rank::size]``, the DistributedSampler partition at file
     granularity), per-epoch reshuffle keyed on (seed, epoch, rank),
     initial state broadcast from rank 0, gradients averaged by
     ``hvd.DistributedGradientTransformation`` inside one jitted step;
     rank 0 writes a checkpoint every epoch (``hvd.save_checkpoint``).
  3. **Resume**: training state is rebuilt FRESH and restored from the
     last checkpoint (``hvd.restore_checkpoint`` broadcasts rank 0's
     file to all ranks — the spot-restart recipe), then training
     finishes. The resumed loss must continue from, not restart above,
     the pre-checkpoint loss.
  4. **Inference**: the final checkpoint serves batch predictions over
     the held-out shard; rank 0 writes ``predictions.csv`` (the
     submission-file stage) and prints a validation RMSPE-style metric.

Run:
    python examples/jax_pipeline_end_to_end.py
    python -m horovod_tpu.runner -np 2 python examples/jax_pipeline_end_to_end.py
"""

import glob
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_HERE, os.path.dirname(_HERE)]

import numpy as np

import jax

import jax.numpy as jnp
import optax

import horovod_tpu as hvd

BATCH = int(os.environ.get("BATCH", 128))
STEPS = int(os.environ.get("STEPS", 40))        # per epoch
EPOCHS = int(os.environ.get("EPOCHS", 2))       # pre-resume epochs
DATA_DIR = os.environ.get("DATA_DIR", "/tmp/hvd_tpu_pipeline")
CKPT_DIR = os.environ.get("CKPT_DIR",
                          os.path.join(DATA_DIR, "checkpoints"))
NUM_SHARD_FILES = 8
N_ROWS = int(os.environ.get("N_ROWS", 20000))
SEED = 4242

D_FEAT = 7 + 12  # engineered features: 7 numeric/cyclic + 12 month 1-hot


# --------------------------------------------------------------------- ETL

def etl_stage():
    """Rank 0: raw records -> engineered feature shards + held-out split
    (the Spark-DataFrame -> Parquet stage of keras_spark_rossmann.py).
    Everyone else waits on the barrier below."""
    rank = hvd.process_rank()
    done = os.path.join(DATA_DIR, "_ETL_DONE")
    # The done-marker records the ETL config: a re-run with different
    # sizing must rebuild, not silently train on stale shards.
    stamp = f"rows={N_ROWS} shards={NUM_SHARD_FILES}\n"
    if rank == 0 and os.path.exists(done):
        with open(done) as f:
            if f.read() != stamp:
                os.unlink(done)
    if rank == 0 and os.path.exists(done):
        print("[etl] reusing existing shards", flush=True)
    if rank == 0 and not os.path.exists(done):
        rng = np.random.RandomState(SEED)
        os.makedirs(DATA_DIR, exist_ok=True)
        # Raw "sales log": (store, day-of-year, promo flag, base demand)
        store = rng.randint(0, 50, N_ROWS)
        day = rng.randint(0, 365, N_ROWS)
        promo = rng.randint(0, 2, N_ROWS)
        noise = rng.randn(N_ROWS) * 0.1
        # Ground-truth generative process the model must learn.
        sales = (2.0 + 0.5 * np.sin(2 * np.pi * day / 365.0)
                 + 0.8 * promo + 0.02 * (store % 7) + noise)

        # Feature engineering: normalized store id, cyclic day-of-year
        # encoding, promo, store-weekday bucket, plus a month one-hot —
        # the continuous+categorical mix of the Rossmann features.
        month = (day * 12 // 365)
        feats = np.stack([
            store / 50.0,
            np.sin(2 * np.pi * day / 365.0),
            np.cos(2 * np.pi * day / 365.0),
            promo.astype(np.float64),
            (store % 7) / 7.0,
            day / 365.0,
            np.ones(N_ROWS),  # bias-ish constant column
        ], axis=1)
        onehot = np.eye(12)[month]
        feats = np.concatenate([feats, onehot], axis=1).astype(np.float32)
        labels = sales.astype(np.float32)

        # Held-out inference split (the Kaggle test.csv role).
        n_hold = N_ROWS // 10
        np.savez(os.path.join(DATA_DIR, "holdout.npz"),
                 feats=feats[:n_hold], labels=labels[:n_hold])
        train_f, train_y = feats[n_hold:], labels[n_hold:]
        per = len(train_y) // NUM_SHARD_FILES
        for s in range(NUM_SHARD_FILES):
            lo = s * per
            hi = len(train_y) if s == NUM_SHARD_FILES - 1 else lo + per
            np.savez(os.path.join(DATA_DIR, f"shard_{s:03d}.npz"),
                     feats=train_f[lo:hi], labels=train_y[lo:hi])
        with open(done, "w") as f:
            f.write(stamp)
        print(f"[etl] wrote {NUM_SHARD_FILES} train shards + holdout "
              f"({N_ROWS} rows)", flush=True)
    # Barrier: no rank may read shards before rank 0 finished writing.
    hvd.allreduce(jnp.zeros((1,)), average=False, name="etl.barrier")


class ShardReader:
    """files[rank::size] partition + per-(epoch, rank) reshuffle — the
    DistributedSampler pattern at file granularity (see
    jax_mnist_file_data.py for the full rationale)."""

    def __init__(self, rank: int, size: int):
        files = sorted(glob.glob(os.path.join(DATA_DIR, "shard_*.npz")))
        if len(files) < size:
            raise ValueError(f"{len(files)} shards cannot feed {size} ranks")
        self.mine = files[rank::size]
        self.rank = rank

    def epoch_batches(self, epoch: int):
        parts = [np.load(f) for f in self.mine]
        feats = np.concatenate([p["feats"] for p in parts])
        labels = np.concatenate([p["labels"] for p in parts])
        order = np.random.RandomState(
            (SEED, epoch, self.rank).__hash__() & 0x7FFFFFFF
        ).permutation(len(labels))
        for i in range(STEPS):
            idx = order[(i * BATCH) % len(order):][:BATCH]
            if len(idx) < BATCH:  # wrap the tail
                idx = np.concatenate([idx, order[:BATCH - len(idx)]])
            yield feats[idx], labels[idx]


# ------------------------------------------------------------------- model

def init_params(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w1": jax.random.normal(k1, (D_FEAT, 64)) * (D_FEAT ** -0.5),
        "b1": jnp.zeros((64,)),
        "w2": jax.random.normal(k2, (64, 64)) * (64 ** -0.5),
        "b2": jnp.zeros((64,)),
        "w3": jax.random.normal(k3, (64, 1)) * (64 ** -0.5),
        "b3": jnp.zeros((1,)),
    }


def predict(params, x):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    h = jax.nn.relu(h @ params["w2"] + params["b2"])
    return (h @ params["w3"] + params["b3"])[:, 0]


def main():
    hvd.init()
    rank, nproc = hvd.process_rank(), hvd.process_count()
    etl_stage()
    reader = ShardReader(rank, nproc)

    opt = hvd.DistributedGradientTransformation(optax.adam(1e-2))

    def fresh_state():
        params = hvd.broadcast_parameters(
            init_params(jax.random.PRNGKey(SEED)), root_rank=0)
        return {"params": params, "opt": opt.init(params), "epoch": 0}

    @jax.jit
    def train_step(state, x, y):
        def loss_fn(p):
            return jnp.mean((predict(p, x) - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        updates, new_opt = opt.update(grads, state["opt"],
                                      state["params"])
        return {"params": optax.apply_updates(state["params"], updates),
                "opt": new_opt, "epoch": state["epoch"]}, loss

    def run_epochs(state, n_epochs):
        last = None
        for _ in range(n_epochs):
            epoch = int(state["epoch"])
            for x, y in reader.epoch_batches(epoch):
                state, loss = train_step(state, jnp.asarray(x),
                                         jnp.asarray(y))
            state["epoch"] = epoch + 1
            last = float(loss)
            if rank == 0:
                print(f"[train] epoch {epoch} loss {last:.4f}", flush=True)
            # Rank-0 checkpoint each epoch (the reference's
            # checkpoint-on-worker-0 convention).
            hvd.save_checkpoint(state, CKPT_DIR, step=epoch)
        return state, last

    # ---- train, then simulate a restart and RESUME from the checkpoint
    state, pre_loss = run_epochs(fresh_state(), EPOCHS)
    del state  # the "crash": all in-memory training state is gone

    resumed = hvd.restore_checkpoint(CKPT_DIR, step=EPOCHS - 1)
    assert int(resumed["epoch"]) == EPOCHS, resumed["epoch"]
    state, post_loss = run_epochs(resumed, 1)
    if rank == 0:
        print(f"[resume] restored epoch {EPOCHS - 1} checkpoint; "
              f"continued to loss {post_loss:.4f}", flush=True)
        # A real resume continues the descent (generous 3x guard: the
        # loss must not restart anywhere near an untrained model's).
        assert post_loss < max(3.0 * pre_loss, 0.2), (post_loss, pre_loss)

    # ---- inference from the final checkpoint over the held-out shard
    final = hvd.restore_checkpoint(CKPT_DIR, step=EPOCHS)
    hold = np.load(os.path.join(DATA_DIR, "holdout.npz"))
    preds = np.asarray(jax.jit(predict)(
        final["params"], jnp.asarray(hold["feats"])))
    if rank == 0:
        rmse = float(np.sqrt(np.mean((preds - hold["labels"]) ** 2)))
        out_csv = os.path.join(DATA_DIR, "predictions.csv")
        with open(out_csv, "w") as f:
            f.write("row,prediction\n")
            for i, p in enumerate(preds):
                f.write(f"{i},{p:.5f}\n")
        print(f"[infer] holdout RMSE {rmse:.4f}; wrote "
              f"{len(preds)} predictions to {out_csv}", flush=True)
        # The generative process has noise sigma 0.1; an untrained model
        # sits ~1.0. Anything near the noise floor means the whole
        # pipeline (ETL -> sharded train -> resume -> infer) worked.
        assert rmse < 0.5, rmse
        print("PIPELINE_OK", flush=True)

    hvd.shutdown()


if __name__ == "__main__":
    main()
