"""Synchronous data-parallel training as one compiled SPMD program.

This replaces the reference's entire BSP protocol — W workers each
``Push``-ing a gradient, the server buffering ``KVMeta`` requests and
withholding every ``Response`` until all ``NumWorkers()`` pushes arrived,
then applying SGD and releasing the barrier (reference
``src/main.cc:57-78``, ``src/lr.cc:116-132``) — with a single
``shard_map``-ped step: per-shard gradients meet in a ``psum`` over the
mesh's ``data`` axis (ICI collectives, no RPC), the SGD update is computed
replicated, and the BSP barrier is implicit in the collective.

Quirk Q1 (SURVEY.md §3.5): the reference's sync server applies the
*last-arriving* worker's gradient divided by W — not the merged mean
(``src/main.cc:63-77``).  ``cfg.sync_last_gradient`` reproduces that
(deterministically: the highest-rank shard stands in for "last-arriving",
which in the reference is a race); the default is the correct ``pmean``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distlr_tpu.config import Config
from distlr_tpu.parallel import feed
from distlr_tpu.parallel.mesh import DATA_AXIS


def _batch_spec(batch) -> tuple:
    """Every leaf of the batch pytree is sharded along its leading (batch)
    axis over ``data``."""
    return jax.tree.map(lambda _: P(DATA_AXIS), batch)


def make_sync_train_step(model, cfg: Config, mesh: Mesh, *, with_metrics: bool = True):
    """Build the jitted sync step: ``step(w, batch) -> (w_new, metrics)``.

    ``batch`` is the model's batch pytree (dense: ``(X, y, mask)``), with
    leading axes divisible by the mesh's ``data`` size.  Weights are
    donated, so the update is in-place in HBM.
    """

    def local_step(w, batch):
        g_local = model.grad(w, batch, cfg)
        if cfg.sync_last_gradient:
            # Q1 compat: psum of (g_i masked to the top rank) == g_last;
            # the reference then divides by the number of workers.
            n_shards = lax.axis_size(DATA_AXIS)
            is_last = (lax.axis_index(DATA_AXIS) == n_shards - 1)
            g = lax.psum(jax.tree.map(lambda t: t * is_last, g_local), DATA_AXIS)
            g = jax.tree.map(lambda t: t / n_shards, g)
        else:
            g = lax.pmean(g_local, DATA_AXIS)
        w_new = jax.tree.map(lambda p, t: p - cfg.learning_rate * t, w, g)
        if not with_metrics:
            return w_new, {}
        metrics = {
            "loss": lax.pmean(model.loss(w, batch, cfg), DATA_AXIS),
            "grad_norm": jnp.sqrt(
                sum(jnp.sum(t * t) for t in jax.tree.leaves(g))
            ),
        }
        return w_new, metrics

    def step(w, batch):
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), _batch_spec(batch)),
            out_specs=(P(), P()),
        )(w, batch)

    return jax.jit(step, donate_argnums=0)


def make_eval_step(model, mesh: Mesh):
    """Jitted global eval over a data-sharded eval batch:
    ``step(w, batch) -> {"accuracy": a, "logloss": l}``.

    Sums correct-prediction counts, per-sample loglosses and mask counts
    with ``psum`` so both results are exact global masked means.  The
    reference evaluates accuracy only, on rank 0, over the full test set
    (``src/lr.cc:47-63``); test logloss is the driver's parity metric
    (BASELINE.json epochs-to-logloss) so it is first-class here."""

    def local_eval(w, batch):
        *inputs, y, mask = batch
        pred = model.predict(w, *inputs)
        correct = lax.psum(jnp.sum((pred == y) * mask), DATA_AXIS)
        # per-shard logloss SUM (masked mean would double-normalize)
        ll_mean = model.logloss(w, batch)
        ll_sum = lax.psum(ll_mean * jnp.sum(mask), DATA_AXIS)
        total = jnp.maximum(lax.psum(jnp.sum(mask), DATA_AXIS), 1)
        return {
            "accuracy": correct.astype(jnp.float32) / total,
            "logloss": ll_sum / total,
        }

    def evaluate(w, batch):
        return jax.shard_map(
            local_eval,
            mesh=mesh,
            in_specs=(P(), _batch_spec(batch)),
            out_specs=P(),
        )(w, batch)

    return jax.jit(evaluate)


def shard_batch(batch, mesh: Mesh, pacer=None):
    """Place a host batch pytree onto the mesh, sharded over ``data``.

    Host->HBM streaming: the successor of the reference's per-step
    ``DataIter`` -> ``Push``/``Pull`` flow (``include/data_iter.h`` +
    ``src/lr.cc:116-132``).

    What a plain ``device_put`` costs, read from a trace on the v5e: the
    runtime relays the whole array into the device's layout on its own
    host threads, and issues the DMA only when that is done; for a dense
    ``(rows, D)`` matrix whose rows are a multiple of 128 and whose D is
    not, that relayout is a transpose (5-7 GB/s, in series with a 14
    GB/s DMA).  Each leaf therefore goes through :func:`feed.place`,
    which hands a large dense matrix over as the row-major bytes the
    host holds and restores shape, dtype and layout on the device; every
    other leaf is put as it always was.  Either way a leaf comes back
    with the values, shape, dtype and ``P(data)`` sharding of a plain
    ``device_put``.  ``pacer`` is a streaming caller's ``feed.Pacer``:
    the large leaf's pieces are then put in their turn at the link."""
    return jax.tree.map(lambda x: feed.place(x, mesh, pacer), batch)
