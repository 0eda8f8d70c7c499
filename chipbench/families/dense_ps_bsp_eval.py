"""Family ``dense_ps_bsp_eval``: the lock-step (BSP) parameter-server job
with the launcher's eval in it: ``dense_ps_bsp``'s round, and after every
``TEST_INTERVAL``-th round rank 0's pass over the whole test split
(upstream ``src/lr.cc:47-63``: pull, predict every row, print the
accuracy).

    z = X_test w
    accuracy = mean(1[(z > 0) == y]),  logloss = mean(softplus(z) - y z)

No L2 term, every row counted once.  The round, the gradient, the logits
and the step's byte floor are ``dense_ps_bsp``'s, imported; that file is
not edited.  :func:`evaluate` is float32 ``jax.numpy`` at the ``highest``
precision over blocks of rows, nothing of the program; with
``precision="bfloat16"`` the weights are rounded first, the nearest
precision below the one the configuration states.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.families.dense_ps_bsp import (  # noqa: F401  (the family's surface)
    gradient,
    logits,
    round,
    step,
    step_bytes_floor,
)
from chipbench.reference import logloss_terms


def evaluate(w, cols, vals, y, precision="float32"):
    """``(accuracy, logloss, z)`` of the rows at ``w``: ``z`` the logits
    as a float32 NumPy array, the two means taken in float64 over them."""
    z = np.asarray(logits(jnp.asarray(w, jnp.float32), jnp.asarray(cols),
                          jnp.asarray(vals), precision))
    y = np.asarray(y)
    accuracy = float(np.mean((z > 0) == (y > 0)))
    terms = np.asarray(logloss_terms(jnp.asarray(z), jnp.asarray(y)),
                       np.float64)
    return accuracy, float(terms.mean()), z


def eval_bytes_floor(*, rows: int, dim: int) -> float:
    """Bytes one eval cannot avoid moving through HBM: the float32 test
    rows once, as wide as they are held (``dim``: the columns of the
    resident matrix, pad columns included), and the weights once.  The
    two scalars that come back are nothing beside them."""
    return rows * dim * 4 + dim * 4
