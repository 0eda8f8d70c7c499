"""Explicit ring collectives over the mesh (ppermute), and a ring-based
feature-sharded training step.

The reference's only "collective" is W independent full-model RPCs
meeting at servers (SURVEY.md §2.4: reduce+broadcast split across two
ZeroMQ round trips).  The framework's default SPMD paths use XLA's
built-in collectives (``lax.psum``), which XLA already schedules as ICI
rings; this module provides the *explicit* ring formulation —
neighbor-exchange ``lax.ppermute`` steps moving one chunk per hop, the
same communication pattern ring attention / ring allreduce use for
sequence parallelism on TPU pods:

* chunked **reduce-scatter** (S-1 hops), then chunked **all-gather**
  (S-1 hops) == allreduce, with each hop touching only 1/S of the data —
  peak per-hop traffic is ``|x|/S``, and each hop can overlap with the
  consumer's compute when XLA finds the schedule;
* building block for the framework's SP-shaped axis: the *feature* axis
  (the reference's analogue of a long sequence axis is its 1M-feature
  weight vector, SURVEY.md §5.7).

Used where profiling favors it; numerically identical (up to f32
reduction order) to the psum path — pinned by tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distlr_tpu.config import Config
from distlr_tpu.models import BinaryLR
from distlr_tpu.parallel.feature_parallel import (
    _check_mesh,
    resid_grad,
    partial_logits,
)
from distlr_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def _ring_perm(s: int, reverse: bool = False):
    """Neighbor permutation i -> i+1 (mod s) on the named axis."""
    if reverse:
        return [((i + 1) % s, i) for i in range(s)]
    return [(i, (i + 1) % s) for i in range(s)]


def ring_reduce_scatter(x, axis_name: str):
    """Ring reduce-scatter of ``x`` (flat leading dim) over ``axis_name``.

    Returns this device's fully-reduced chunk, shape ``(ceil(n/s),)`` —
    device ``i`` owns chunk ``(i + 1) % s`` of the padded input.  S-1
    neighbor hops, each carrying one chunk.
    """
    s = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    n = x.shape[0]
    chunk = -(-n // s)
    x = jnp.pad(x, (0, chunk * s - n))
    chunks = x.reshape(s, chunk)

    def hop(state, step):
        acc, = state
        send_i = (idx - step) % s
        block = lax.dynamic_index_in_dim(acc, send_i, axis=0, keepdims=False)
        recvd = lax.ppermute(block, axis_name, _ring_perm(s))
        recv_i = (idx - step - 1) % s
        prev = lax.dynamic_index_in_dim(acc, recv_i, axis=0, keepdims=False)
        acc = lax.dynamic_update_index_in_dim(acc, prev + recvd, recv_i, axis=0)
        return (acc,), None

    (chunks,), _ = lax.scan(hop, (chunks,), jnp.arange(s - 1))
    own = (idx + 1) % s
    return lax.dynamic_index_in_dim(chunks, own, axis=0, keepdims=False)


def ring_all_gather(chunk, axis_name: str, *, owner_offset: int = 0):
    """Ring all-gather: every device contributes its ``chunk`` and ends
    with all S chunks, ordered by owner rank.  ``owner_offset=k`` means
    device ``i`` contributes the chunk logically numbered ``(i + k) % s``
    (reduce-scatter above leaves ownership rotated by one).  S-1 hops.
    """
    s = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    out = jnp.zeros((s,) + chunk.shape, chunk.dtype)
    own = (idx + owner_offset) % s
    out = lax.dynamic_update_index_in_dim(out, chunk, own, axis=0)

    def hop(state, step):
        out, cur = state
        block = lax.dynamic_index_in_dim(out, cur, axis=0, keepdims=False)
        recvd = lax.ppermute(block, axis_name, _ring_perm(s))
        nxt = (cur - 1) % s
        out = lax.dynamic_update_index_in_dim(out, recvd, nxt, axis=0)
        return (out, nxt), None

    (out, _), _ = lax.scan(hop, (out, own), jnp.arange(s - 1))
    return out.reshape((-1,) + chunk.shape[1:])


def ring_psum(x, axis_name: str):
    """Allreduce as ring reduce-scatter + ring all-gather (ppermute only).

    Numerically equivalent to ``lax.psum(x, axis_name)`` up to f32
    reduction order; 2(S-1) hops of ``|x|/S`` each.
    """
    shape = x.shape
    flat = x.reshape(-1)
    chunk = ring_reduce_scatter(flat, axis_name)
    full = ring_all_gather(chunk, axis_name, owner_offset=1)
    return full[: flat.shape[0]].reshape(shape)


def make_ring_train_step(model, cfg: Config, mesh: Mesh, *, with_metrics: bool = True):
    """Feature-sharded sync step using explicit ring collectives on the
    ``model`` axis (interface-compatible with
    :func:`make_feature_sharded_train_step`; BinaryLR only).

    Per step: local partial logits -> **ring allreduce** over feature
    shards -> local gradient -> pmean over ``data`` -> shard-local update.
    """
    if not isinstance(model, BinaryLR):
        raise TypeError("ring step supports BinaryLR (dense weights)")
    _check_mesh(mesh, model.num_features)

    def local_step(w, X, y, mask):
        n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
        # same int8_dot-aware partials as the psum step; only the
        # reduction differs (explicit ppermute ring vs XLA psum)
        z = ring_psum(partial_logits(model, w, X), MODEL_AXIS)
        resid = (jax.nn.sigmoid(z) - y.astype(jnp.float32)) * mask
        g = resid_grad(model, resid, X, n)
        if model.feature_scale != 1.0:  # d/dw of (X*scale) @ w
            g = g * model.feature_scale
        l2 = cfg.l2_c * w
        if cfg.l2_scale_by_batch:
            l2 = l2 / n
        g = lax.pmean(g + l2, DATA_AXIS)
        w_new = w - cfg.learning_rate * g
        if not with_metrics:
            return w_new, {}
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        reg = 0.5 * cfg.l2_c * ring_psum(jnp.sum(w * w)[None], MODEL_AXIS)[0]
        if cfg.l2_scale_by_batch:
            reg = reg / n
        loss = lax.pmean(jnp.sum(ll * mask) / n + reg, DATA_AXIS)
        return w_new, {"loss": loss}

    def step(w, batch):
        X, y, mask = batch
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(MODEL_AXIS), P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(MODEL_AXIS), P()),
            check_vma=False,
        )(w, X, y, mask)

    return jax.jit(step, donate_argnums=0)
