"""Family ``dense_ps_minibatch``: the gradient a parameter-server worker
pushes when its batch is ``B`` rows of its shard and not all of them
(upstream's ``BATCH_SIZE``: ``examples/local.sh:19``, read at
``src/main.cc:154``).

Which rows: ``DataIter::NextBatch`` serves a shard's rows in file order,
``B`` at a time, and one constructed iterator serves one pass
(``include/data_iter.h:40-59``; ``src/main.cc:158-159`` builds a new one
every epoch), so round ``k`` of a worker's run reads

    rows [j B, min(j B + B, R))   with   j = k mod ceil(R / B)

of its ``R`` rows: :func:`window`, worked out here from the rule and from
nothing the program says.  Where ``B`` does not divide ``R`` the last
batch of an epoch is the rows that are left, each counted once (this
system's stated semantics; upstream wraps round to the shard's head, its
Q5 quirk, which the deployment does not keep).

    z = X[window] w,   g = X[window]^T (sigmoid(z) - y[window]) / rows

The gradient, the logits, the server's rule and the step's byte floor
are ``dense_ps``'s, imported; that file is not edited.  The floor is
asked for with the window's rows: a step reads ``B`` rows of the
resident matrix, not the shard.
"""

from __future__ import annotations

from chipbench.families.dense_ps import (  # noqa: F401  (the family's surface)
    gradient,
    logits,
    step,
    step_bytes_floor,
)


def rounds_an_epoch(rows: int, batch: int) -> int:
    return -(-rows // batch)


def window(k: int, rows: int, batch: int) -> slice:
    """The rows round ``k`` (from 0) of a worker's run reads, of a shard
    of ``rows`` rows served ``batch`` at a time in file order, every
    epoch from row 0."""
    first = (k % rounds_an_epoch(rows, batch)) * batch
    return slice(first, min(first + batch, rows))


def window_gradient(w, cols, vals, y, k: int, batch: int,
                    precision="float32"):
    """:func:`gradient` of exactly the rows round ``k`` reads."""
    at = window(k, len(y), batch)
    return gradient(w, cols[at], vals[at], y[at], precision)
