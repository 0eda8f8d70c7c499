"""Family ``sparse_ps_keyed_delay``: the keyed sparse-LR job of
``sparse_ps_keyed_ftrl`` under **bounded delay, tau = 1** (Li, Andersen,
Park, Smola et al., "Scaling Distributed Machine Learning with the
Parameter Server", OSDI 2014: section 3.4's third consistency model, and
section 5.1's Algorithm 3, delayed block proximal gradient, whose worker
may start iteration *t* before iteration *t* - 1 has finished and not
before *t* - 1 - tau has).

**The rule.**  One worker; rounds *k* = 0 ... *R* - 1 numbered inside one
``fit``; round *k* reads window *j*(*k*) = *k* mod (rounds an epoch) of
its shard in file order (:func:`window`: an epoch is no boundary).
``K_k`` is that window's sorted unique keys (:func:`keys`), ``L_k`` the
keyed pull of ``K_k``, ``P_k`` the keyed push of ``(K_k, g_k)``.  The
worker's one connection carries, one blocking operation at a time:

    L_0, L_1, P_0, L_2, P_1, L_3, ..., P_{R-3}, L_{R-1}, P_{R-2}, P_{R-1}

so ``L_{k+1}`` is issued after ``P_{k-1}`` is acknowledged and before
``P_k`` is issued, and with ``S`` the servers' state (w, z, n) and
``A(S, P)`` Algorithm 1 applied to every entry of one push:

    v_k = w[K_k] of the state with this worker's P_0 ... P_{k-2} applied
          whole and none later          (k >= 1: exactly one own push
                                         behind; v_0: none behind)
    g_k = the sparse-LR gradient of window j(k) at v_k, float32
    S  <- A(S, (K_k, g_k))              on arrival, under the server's lock

Peers' pushes appear in ``v_k`` as arrival has them (asynchronous
servers).  No ``L_R`` exists; nothing of the worker's is at the servers
at an eval, a checkpoint or ``fit``'s return; a second ``fit`` starts
again from ``L_0, L_1``.

**Where this departs from Li et al.'s Algorithm 3** (written from
memory, not read again for this file; nothing below rests on its
details): no KKT filter (every key of the window is pushed, a zero entry
steps nothing); no feature blocks (a round's block is its window's keys,
in file order, not a schedule over a partition of the features);
per-coordinate FTRL-Proximal with L1 (McMahan et al., KDD 2013,
Algorithm 1) where the paper has a proximal gradient step with a
coordinate-wise learning rate; tau = 1 only, and fixed, where the paper
lets tau grow with the iteration.

The worker's side (``window``, ``keys``, ``gradient``) and the servers'
(``closed_form``, ``ftrl_step``, ``replay``) are
``families/sparse_ps_keyed_ftrl.py``'s, imported: float32 ``jax.numpy``
and ``numpy`` with ``jax.default_matmul_precision("highest")`` stated
round every block, and nothing of the program.  What this file adds is
where the delay puts each round: :func:`computed_on`, the weights the
rule gives round *k* when every push's place in the order is known, and
:func:`solo`, one worker alone, which is a trajectory.
"""

from __future__ import annotations

import numpy as np

from chipbench.families.sparse_ps_keyed_ftrl import (  # noqa: F401
    closed_form,
    evaluate,
    ftrl_step,
    gradient,
    keys,
    logits,
    replay,
    rounds_an_epoch,
    step,
    step_bytes_floor,
    window,
)

F32 = np.float32
#: own pushes a pull of round k >= 1 is behind (tau)
DELAY = 1


def sgd_step(w, g, *, lr):
    """One push's entries on SGD servers (``w -= lr g`` in float32, the
    product rounded before the subtraction): the rule of the sibling
    ``sparse_ps_keyed``'s servers, for :func:`solo` under SGD."""
    return (np.asarray(w, F32) - F32(lr) * np.asarray(g, F32)).astype(F32)


def computed_on(k, pushes_in_order, state0, *, rank=0, at=None, **rule):
    """``w[K_k]`` as the rule gives it to round ``k`` of worker ``rank``.

    ``pushes_in_order[r]`` is worker *r*'s pushes ``(keys, g)`` in its
    own order; the one total order is worker after worker in rank order
    (the serial prefix).  The weights under round *k* hold every earlier
    rank's pushes whole and this worker's own through round *k* - 2
    (:data:`DELAY` behind), applied one at a time by Algorithm 1
    (``rule``: alpha, beta, l1, l2) from ``state0 = (w, z, n)``, and no
    other.  ``at``: the keys asked (those of the worker's push *k* where
    None: a round pulls what it pushes)."""
    before = [p for r in range(rank) for p in pushes_in_order[r]]
    own = list(pushes_in_order[rank][:max(k - DELAY, 0)])
    (w, _z, _n), _stood = replay(before + own, *state0, **rule)
    at = pushes_in_order[rank][k][0] if at is None else at
    return w[np.asarray(at).astype(np.int64)]


def solo(w0, shard, R, *, batch, rule=None, lr=None, pushes=None,
         precision="float32"):
    """One worker alone for ``R`` rounds from weights ``w0`` (z = n = 0):
    no peer, so every ``v_k`` is fixed by the rule and the run is a
    trajectory.  ``shard``: ``(cols, vals, y)``.  ``rule``: Algorithm 1's
    four numbers, or None for SGD servers at ``lr``.  ``pushes``: the
    gradients to push in the reference's own's place (a program's
    recorded ``g_k``, so that the states can be compared bit for bit);
    the reference's own gradient of each round is returned either way.

    Returns ``(v, g, (w, z, n))``: per round the weights pulled and the
    reference's gradient at them, and the tables after ``P_{R-1}``."""
    cols, vals, y = shard
    w = np.array(w0, F32)
    z, n = np.zeros_like(w), np.zeros_like(w)
    vs, gs, sent = [], [], []

    def apply(j):
        u, g = sent[j]
        if rule is None:
            w[u] = sgd_step(w[u], g, lr=lr)
        else:
            w[u], z[u], n[u] = ftrl_step(w[u], z[u], n[u], g, **rule)

    for k in range(R):
        # L_k comes after P_{k-2} and before P_{k-1}
        if k >= 1 + DELAY:
            apply(k - 1 - DELAY)
        at = window(k, len(y), batch)
        u = keys(cols[at]).astype(np.int64)
        v = w[u].copy()
        g = gradient(v, cols[at], vals[at], y[at], precision=precision,
                     dim=len(w))
        vs.append(v)
        gs.append(g)
        sent.append((u, g if pushes is None else np.asarray(pushes[k], F32)))
    for j in range(max(R - 1 - DELAY, 0), R):
        apply(j)
    return vs, gs, (w, z, n)
