"""Family ``dense_ps_bsp_delay``: the lock-step (BSP) parameter-server job
under bounded delay, tau = 1 (Li et al., OSDI 2014: a worker may start
round *k* + 1 before its push of round *k* is acknowledged, but not
before round *k* - tau is): dense binary logistic regression, every
worker's batch its whole float32 shard.

Rounds are numbered from 0 inside one ``fit``; ``w_0`` is what every
worker holds when it begins; ``v_k`` is the weights round *k*'s gradient
is computed on; ``g_r(v)`` is the sigmoid gradient of worker *r*'s whole
shard at ``v`` (``dense_ps.gradient``):

    v_0 = v_1 = w_0
    v_k = w_{k-1}                                     for k >= 2
    w_{k+1} = float32( w_k - lr * (sum_r g_r(v_k)) / W )

``v_k`` (k >= 2) is the reply to a worker's own push of round *k* - 2:
the weights after round *k* - 2, exactly one round stale.  The servers
do what they do in lock step: W pushes merged, one update, then every
reply; a worker sends push *k* after the reply to push *k* - 1, so a
server never holds two open rounds.  All W workers compute round *k* on
the same ``v_k``, so a run has a trajectory: a function of the seed
alone, up to the order in which a server adds four float32 gradients.
The reference has no such order: it adds the W gradients in float64 on
the host and rounds the new weights to float32 once a round, as
``dense_ps_bsp.round`` does.  When the ``fit`` returns nothing is in
flight and every worker holds ``w_E``; the next ``fit`` begins there,
its rounds 0 and 1 on that.

tau = 0 is ``dense_ps_bsp``: ``v_k = w_k``.  The two agree on ``w_1``
and part from ``w_2`` on.

The gradient, the logits and the byte floor are ``dense_ps``'s, the eval
``dense_ps_bsp_eval``'s: float32 ``jax.numpy``, ``highest`` precision,
blocks of rows, nothing of the program.  There is no new kernel: a step
of one worker moves what a lock-step worker's moves, so the floor of
``step_hbm_roofline`` is the same.
"""

from __future__ import annotations

import numpy as np

from chipbench.families.dense_ps_bsp_eval import (  # noqa: F401  (the family's surface)
    evaluate,
    gradient,
    logits,
    step,
    step_bytes_floor,
)


def rounds(w0, shards, lr, n, precision="float32"):
    """``[w_1, ..., w_n]``: the weights after each of the ``n`` rounds of
    one ``fit`` from ``w0``; ``shards`` is one ``(cols, vals, y)`` a
    worker."""
    w = np.asarray(w0, np.float32)
    on, out = w, []           # v_k: the weights under round k's gradient
    for _ in range(int(n)):
        total = np.zeros(len(w), np.float64)
        for shard in shards:
            total += np.asarray(gradient(on, *shard, precision=precision),
                                np.float64)
        after = (np.asarray(w, np.float64)
                 - float(lr) * total / len(shards)).astype(np.float32)
        # the next round runs on the weights BEFORE this update: v_{k+1}
        # = w_k; v_1 = w_0 falls out of the same line
        on, w = w, after
        out.append(after)
    return out


def computed_on(w0, after):
    """``[v_0, ..., v_n]`` for the trajectory ``after`` = ``[w_1, ...,
    w_n]`` of :func:`rounds`: what each round's gradient ran on."""
    w = [np.asarray(w0, np.float32), *after]
    return [w[max(k - 1, 0)] for k in range(len(w))]
