"""The plain reference: SGD on a linear model, followed step by step.

Float32 ``jax.numpy``, no mesh, no kernels, nothing imported from the
program.  What belongs to one model family (its step, its logits and
the least bytes a step moves) is a module of its own,
``chipbench/families/<family>.py``, found by the name the
configuration's file gives under ``family``: a new family is a new file.

``precision`` selects the control: the same arithmetic in the nearest
precision below the one a configuration states (``bfloat16`` under
float32), which ``correct`` has to tell from the real thing.  A control
follows the same steps in the program's place.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.manifest import NAME_RE, module_name

PRECISIONS = ("float32", "bfloat16")


def lower(x, precision):
    """``x`` as the lower precision would hold it, back in float32."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        # not astype(bfloat16).astype(float32): XLA on the TPU keeps the
        # excess precision and the control would compute in float32
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    raise ValueError(f"precision must be one of {PRECISIONS}")


def logloss_terms(z, y):
    return jax.nn.softplus(z) - y.astype(jnp.float32) * z


def family(name: str):
    """The family's module: ``step``, ``logits`` and ``step_bytes_floor``."""
    if not NAME_RE.match(name):
        raise ValueError(f"{name!r} is not a permitted name")
    try:
        return importlib.import_module(
            f"chipbench.families.{module_name(name)}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no family {name!r} under chipbench/families") from e


def follow_steps(family_name: str, w0: np.ndarray, batches, *, lr: float,
                 l2: float, precision: str = "float32"):
    """SGD from ``w0`` through ``batches`` (``(cols, vals, y)`` each).

    Returns the loss before each update and the weights after each, as
    NumPy arrays."""
    step = family(family_name).step
    w = jnp.asarray(w0, jnp.float32)
    losses, weights = [], []
    for cols, vals, y in batches:
        loss, w = step(w, jnp.asarray(cols), jnp.asarray(vals),
                       jnp.asarray(y), jnp.float32(lr), jnp.float32(l2),
                       precision=precision)
        losses.append(float(loss))
        weights.append(np.asarray(w))
    return losses, weights


def logloss(family_name: str, w: np.ndarray, cols, vals, y, *,
            precision: str = "float32", block_rows: int = 1 << 20) -> float:
    """Mean logloss of ``w`` on the rows, no L2 term, in blocks of rows."""
    logits = family(family_name).logits
    wj = jnp.asarray(w, jnp.float32)
    total = 0.0
    for s in range(0, len(y), block_rows):
        e = s + block_rows
        z = logits(wj, jnp.asarray(cols[s:e]), jnp.asarray(vals[s:e]),
                   precision)
        total += float(jnp.sum(logloss_terms(z, jnp.asarray(y[s:e]))))
    return total / len(y)
