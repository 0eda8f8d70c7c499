"""Feature-axis (model) sharding: 2D ``data x model`` parallelism.

The reference scales its weight vector by range-sharding the key space
across S server processes (``GetServerKeyRanges`` / ``DecodeKey``,
reference ``src/main.cc:98-101``) while every worker still materializes
the FULL dense vector per step (``src/lr.cc:116-132``).  Here the shard
is real end-to-end: the weight vector (and the feature axis of every
batch) lives partitioned over the mesh's ``model`` axis — each device
touches only D/S features, so D can exceed single-device HBM.

Per step, for mesh axes (data=W, model=S):

* ``z_partial = X_shard @ w_shard``  — local matvec on each device
* ``z = psum(z_partial, 'model')``   — logits need all feature shards
* residual, per-example loss       — replicated along ``model``
* ``g_shard = X_shard^T r / n``      — local; already model-sharded
* ``g = pmean(g_shard, 'data')``     — the usual data-parallel mean
* ``w_shard -= lr * g_shard``        — update stays shard-local

i.e. exactly one small collective per direction (the (B,)-sized logit
psum and the gradient pmean) instead of the reference's full-D
pull/push per worker per step.

Supports :class:`BinaryLR` (w: (D,)) and :class:`SoftmaxRegression`
(W: (D, K), feature axis sharded).  The sparse model keeps its own path
(PS mode / segment_sum).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlr_tpu.config import Config
from distlr_tpu.models import BinaryLR, SoftmaxRegression
from distlr_tpu.models.linear import _int8_contract, quantize_sym
from distlr_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def _check_mesh(mesh: Mesh, num_features: int) -> None:
    if MODEL_AXIS not in mesh.axis_names:
        raise ValueError("feature-sharded step needs a mesh with a 'model' axis")
    s = mesh.shape[MODEL_AXIS]
    if num_features % s != 0:
        raise ValueError(
            f"num_features={num_features} must be divisible by the model-axis "
            f"size {s} (pad the feature dimension)"
        )


def _per_sample_logloss(z, y, is_softmax: bool):
    """Per-sample logloss from global logits (shared by the train-metrics
    and eval paths; the canonical definition lives on the model classes —
    tests pin these against model.logloss)."""
    if is_softmax:
        return -jax.nn.log_softmax(z)[jnp.arange(z.shape[0]), y]
    return jax.nn.softplus(z) - y.astype(jnp.float32) * z


def partial_logits(model, w_shard, X_shard):
    """This device's feature-shard contribution to the logits (already
    feature-scaled); the caller reduces over ``model`` (psum or ring).

    int8_dot models quantize the weight shard on a GLOBAL grid (|w| max
    via pmax over shards), so the formulation matches the single-device
    int8_dot path bit-for-bit on the weight side, and feed the native
    int8 x int8 -> int32 contraction; others take the compute-dtype
    matmul with the convert fused in."""
    if getattr(model, "int8_dot", False):
        wq, s_w = quantize_sym(
            w_shard, lax.pmax(jnp.max(jnp.abs(w_shard)), MODEL_AXIS))
        return _int8_contract(X_shard, wq, X_shard.ndim - 1) * (
            s_w * model.feature_scale)
    cdt = jnp.dtype(model.compute_dtype)
    z_partial = jnp.dot(
        X_shard.astype(cdt), w_shard.astype(cdt), preferred_element_type=jnp.float32
    )
    if model.feature_scale != 1.0:  # int8-quantized features (BinaryLR doc)
        z_partial = z_partial * model.feature_scale
    return z_partial


def resid_grad(model, resid, X_shard, n):
    """Residual-times-features gradient term, int8_dot-aware.

    ``resid (B,)`` (binary) gives ``resid @ X / n -> (D_shard,)``;
    ``resid (B, K)`` (softmax) gives ``X^T @ resid / n -> (D_shard, K)``.

    Residuals are replicated along ``model`` (computed from the reduced
    logits), so a local max IS the model-axis global max; along ``data``
    each shard quantizes its own batch slice — the same semantics as the
    data-parallel int8_dot step.  feature_scale is NOT applied here (the
    callers multiply it with their other scale factors)."""
    if getattr(model, "int8_dot", False):
        rq, s_r = quantize_sym(resid, jnp.max(jnp.abs(resid)))
        if resid.ndim == 2:
            return _int8_contract(X_shard, rq, 0) * s_r / n
        return _int8_contract(rq, X_shard, 0) * s_r / n
    cdt = jnp.dtype(model.compute_dtype)
    if resid.ndim == 2:
        return jnp.dot(X_shard.astype(cdt).T, resid.astype(cdt),
                       preferred_element_type=jnp.float32) / n
    return jnp.dot(resid.astype(cdt), X_shard.astype(cdt),
                   preferred_element_type=jnp.float32) / n


def _local_forward(model, w_shard, X_shard):
    """Partial logits from this device's feature shard, then psum."""
    return lax.psum(partial_logits(model, w_shard, X_shard), MODEL_AXIS)


def make_feature_sharded_train_step(model, cfg: Config, mesh: Mesh, *, with_metrics: bool = True):
    """Jitted 2D-parallel sync step: ``step(w, (X, y, mask)) -> (w, metrics)``.

    ``w`` is model-axis sharded; ``X`` is ``(data, model)``-sharded;
    ``y``/``mask`` are data-sharded.  Weights are donated.
    """
    if not isinstance(model, (BinaryLR, SoftmaxRegression)):
        raise TypeError(f"feature sharding supports dense models, got {type(model).__name__}")
    _check_mesh(mesh, model.num_features)
    is_softmax = isinstance(model, SoftmaxRegression)

    def local_step(w, X, y, mask):
        n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
        z = _local_forward(model, w, X)
        if is_softmax:
            p = jax.nn.softmax(z)
            onehot = jax.nn.one_hot(y, model.num_classes, dtype=jnp.float32)
            resid = (p - onehot) * mask[:, None]
        else:
            resid = (jax.nn.sigmoid(z) - y.astype(jnp.float32)) * mask
        g = resid_grad(model, resid, X, n)
        ll = _per_sample_logloss(z, y, is_softmax)
        if model.feature_scale != 1.0:  # d/dw of (X*scale) @ w
            g = g * model.feature_scale
        # L2 on the local shard (gradient of 0.5*C*|w|^2 is shard-local)
        l2 = cfg.l2_c * w
        if cfg.l2_scale_by_batch:
            l2 = l2 / n
        g = lax.pmean(g + l2, DATA_AXIS)
        w_new = w - cfg.learning_rate * g
        if not with_metrics:
            return w_new, {}
        # include the L2 term so this metric is comparable with the
        # data-parallel path's model.loss (reg needs all weight shards)
        reg = 0.5 * cfg.l2_c * lax.psum(jnp.sum(w * w), MODEL_AXIS)
        if cfg.l2_scale_by_batch:
            reg = reg / n
        loss = lax.pmean(jnp.sum(ll * mask) / n + reg, DATA_AXIS)
        gn2 = lax.psum(jnp.sum(g * g), MODEL_AXIS)
        return w_new, {"loss": loss, "grad_norm": jnp.sqrt(gn2)}

    w_spec = P(MODEL_AXIS) if not is_softmax else P(MODEL_AXIS, None)
    x_spec = P(DATA_AXIS, MODEL_AXIS)

    def step(w, batch):
        X, y, mask = batch
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(w_spec, x_spec, P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(w_spec, P()),
            check_vma=False,
        )(w, X, y, mask)

    return jax.jit(step, donate_argnums=0)


def make_feature_sharded_eval_step(model, mesh: Mesh):
    """Global masked eval (``{"accuracy", "logloss"}`` like
    :func:`make_eval_step`) with model-axis-sharded weights."""
    _check_mesh(mesh, model.num_features)
    is_softmax = isinstance(model, SoftmaxRegression)

    def local_eval(w, X, y, mask):
        z = _local_forward(model, w, X)
        pred = (
            jnp.argmax(z, axis=-1).astype(jnp.int32)
            if is_softmax
            else (z > 0).astype(jnp.int32)
        )
        ll = _per_sample_logloss(z, y, is_softmax)
        correct = lax.psum(jnp.sum((pred == y) * mask), DATA_AXIS)
        ll_sum = lax.psum(jnp.sum(ll * mask), DATA_AXIS)
        total = jnp.maximum(lax.psum(jnp.sum(mask), DATA_AXIS), 1)
        return {
            "accuracy": correct.astype(jnp.float32) / total,
            "logloss": ll_sum / total,
        }

    w_spec = P(MODEL_AXIS) if not is_softmax else P(MODEL_AXIS, None)

    def evaluate(w, batch):
        X, y, mask = batch
        return jax.shard_map(
            local_eval,
            mesh=mesh,
            in_specs=(w_spec, P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(),
            check_vma=False,
        )(w, X, y, mask)

    return jax.jit(evaluate)


def shard_batch_2d(batch, mesh: Mesh):
    """Place ``(X, y, mask)`` with X sharded (data, model), rest data-sharded."""
    X, y, mask = batch
    return (
        jax.device_put(X, NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS))),
        jax.device_put(y, NamedSharding(mesh, P(DATA_AXIS))),
        jax.device_put(mask, NamedSharding(mesh, P(DATA_AXIS))),
    )


def shard_weights(w, mesh: Mesh):
    """Place weights sharded over the model axis (feature shards)."""
    spec = P(MODEL_AXIS) if w.ndim == 1 else P(MODEL_AXIS, None)
    return jax.device_put(w, NamedSharding(mesh, spec))
