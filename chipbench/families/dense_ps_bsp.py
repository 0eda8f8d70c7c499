"""Family ``dense_ps_bsp``: one lock-step (BSP) round of the
parameter-server job, dense binary logistic regression, every worker's
batch its whole float32 shard.

    g_r = X_r^T (sigmoid(X_r w) - y_r) / n_r          (``dense_ps.gradient``)
    w'  = w - lr * (sum over the W workers of g_r) / W  (:func:`round`)

All W workers compute on the same ``w``; the servers apply the one update
after the W-th push and before any reply, so a run has a trajectory: the
weights after round *k* are a function of the seed alone, up to the
order in which a server adds four float32 gradients.  The reference has
no such order: it adds the W gradients in float64 on the host and rounds
the new weights to float32 once, as the servers' float32 vector holds
them.

One departure from upstream, stated in the configuration's ``assumed``:
upstream's server (``src/main.cc:71``, SURVEY.md Q1) applies the LAST
arrival's gradient over W and throws the merged sum away; this family's
rule is the mean of the W gradients, the product's default.

The gradient, the logits and the byte floor are ``dense_ps``'s: float32
``jax.numpy``, ``highest`` precision, blocks of rows, nothing of the
program.  A step of one worker moves what an asynchronous worker's
moves, so the floor of ``step_hbm_roofline`` is the same.
"""

from __future__ import annotations

import numpy as np

from chipbench.families.dense_ps import (  # noqa: F401  (the family's surface)
    gradient,
    logits,
    step,
    step_bytes_floor,
)


def round(w, shards, lr, precision="float32"):  # noqa: A001  (the issue's name)
    """The weights after one BSP round from ``w``: ``shards`` is one
    ``(cols, vals, y)`` a worker."""
    total = np.zeros(len(w), np.float64)
    for shard in shards:
        total += np.asarray(gradient(w, *shard, precision=precision),
                            np.float64)
    return (np.asarray(w, np.float64)
            - float(lr) * total / len(shards)).astype(np.float32)
