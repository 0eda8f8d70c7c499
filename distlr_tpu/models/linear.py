"""Linear model family as pure-functional JAX: dense binary LR, multinomial
softmax regression, and sparse (CSR) binary LR.

Replaces the reference's ``distlr::LR`` (``src/lr.cc`` / ``include/lr.h``)
whose hot loop is an O(B*D^2) scalar nest (``src/lr.cc:35-41``: it
re-computes the full dot product w.x inside the per-feature loop and copies
the feature vector per access).  Here each step is two MXU matmuls —
``X @ w`` and ``X^T @ residual`` — O(B*D), bfloat16 on the MXU with float32
accumulation.

Every model exposes the same pure-function surface:

* ``init(config) -> params``          (reference-RNG or He-style init)
* ``loss(params, batch, cfg) -> scalar``  (mean logloss + L2)
* ``grad(params, batch, cfg) -> params-like``  (closed form, quirk-gated)
* ``predict(params, X) -> labels``
* ``accuracy(params, batch) -> scalar``

``batch`` is ``(X, y, mask)`` with a boolean mask for padded rows (static
shapes; see :mod:`distlr_tpu.data.iterator`).  Gradients are closed-form
rather than ``jax.grad`` of the loss so the reference's exact formula
``(sigma(Xw) - y)^T X / B + C*w/B`` (``src/lr.cc:38-40``, quirk Q4) can be
reproduced bit-for-bit in compat mode; a ``jax.grad`` path is kept in tests
as the oracle for the "correct" mode.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from distlr_tpu.config import Config
from distlr_tpu.utils.reference_rng import reference_init_weights


# Longest int8 x int8 contraction whose worst case (every product
# +/-127*127, same sign) still fits int32: floor((2^31-1) / 127^2).
_INT8_ACC_MAX = (2**31 - 1) // (127 * 127)


# Chunks below this are useless on the MXU (every k divides by 1, so a
# floor is what actually forces awkward lengths onto the convert path).
_INT8_MIN_CHUNK = 1024

# Each chunk is an unrolled dot_general in the traced step; divisor-poor
# dims (e.g. k = 1024 * 131^2 -> best divisor 4*131^2, 256 chunks) would
# blow up HLO size and compile time, so past this many chunks the
# convert path wins.
_INT8_MAX_CHUNKS = 32


def _int8_chunk_len(k: int) -> int | None:
    """Largest divisor of ``k`` that keeps a worst-case int8 x int8
    contraction inside int32.  ``None`` — caller must take the convert
    path — when no divisor of useful size exists OR the resulting chunk
    count would exceed ``_INT8_MAX_CHUNKS`` unrolled dots.  Trace-time
    only (static shapes)."""
    if k <= _INT8_ACC_MAX:
        return k
    best = None
    for d in range(1, int(k**0.5) + 1):
        if k % d:
            continue
        for c in (d, k // d):
            if c <= _INT8_ACC_MAX and (best is None or c > best):
                best = c
    if best is None or best < _INT8_MIN_CHUNK or k // best > _INT8_MAX_CHUNKS:
        return None
    return best


def _int8_contract(a, b, a_axis: int) -> jnp.ndarray:
    """Overflow-safe ``a . b`` over ``a``'s axis ``a_axis`` and ``b``'s
    leading axis, both int8, on the MXU -> float32 (unscaled).

    A single int32 accumulation wraps once the contraction length
    exceeds ``_INT8_ACC_MAX`` (~133k) in the worst case — reachable for
    the backward at ``batch_size=-1`` on a big shard, and for the
    forward at north-star D.  The contraction is therefore split into
    the largest dividing chunks that cannot wrap: one plain dot_general
    per chunk over a contraction-axis slice, accumulated in float32
    (chunk partials are < 2^31, so the f32 rounding there is ~1e-9
    relative — far below the int8 quantization noise).  The unrolled
    slice-per-chunk form matters: expressing the same split as a single
    reshape + c-batched dot_general measured 55k samples/s on the
    D=1M step vs ~165k for both the unrolled form and the (unsafe)
    unchunked dot — the batched dot forces a bad layout on the (B, D)
    operand, while column slices keep each chunk a plain MXU matmul
    (an earlier on-chip capture; not measured since).  When the length is
    awkward — no divisor <= the bound, or only divisors small enough
    that the unroll would exceed ``_INT8_MAX_CHUNKS`` dots — the
    bfloat16-convert formulation is used instead: slower, never wrong.
    """
    k = a.shape[a_axis]
    a_axis = a_axis % a.ndim
    n_c = _int8_chunk_len(k)
    if n_c == k:
        out = jax.lax.dot_general(
            a, b, (((a_axis,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return out.astype(jnp.float32)
    if n_c is None:  # no safe chunking: correct-but-slower convert path
        out = jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            (((a_axis,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return out
    acc = None
    for i in range(k // n_c):
        a_i = jax.lax.slice_in_dim(a, i * n_c, (i + 1) * n_c, axis=a_axis)
        b_i = jax.lax.slice_in_dim(b, i * n_c, (i + 1) * n_c, axis=0)
        p = jax.lax.dot_general(
            a_i, b_i, (((a_axis,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
        acc = p if acc is None else acc + p
    return acc


def quantize_sym(x, max_abs):
    """Symmetric int8 quantization on the grid defined by ``max_abs``:
    ``(q int8, scale)`` with ``x ~ q * scale``.  The ONE definition of
    the int8_dot grid — the single-device paths and the feature-sharded
    steps (which compute ``max_abs`` with a pmax) must quantize
    identically for their bit-for-bit weight-grid parity to hold."""
    scale = jnp.maximum(max_abs, 1e-8) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _masked_mean(values, mask):
    denom = jnp.maximum(jnp.sum(mask), 1)
    return jnp.sum(values * mask) / denom


def _l2_grad(w, cfg: Config, batch_n):
    # Q4 gate: reference divides the L2 term by the batch size
    # (src/lr.cc:40); "correct" applies C*w un-scaled.
    term = cfg.l2_c * w
    return term / batch_n if cfg.l2_scale_by_batch else term


def _grad_from_panels(kernel, w, batch, cfg: Config, plan, feature_scale,
                      first, interpret):
    """A dense model's ``grad`` round a row-panel kernel's ``X^T r``
    (``ops.pallas_lr.lr_grad_panels``, ``ops.pallas_softmax.
    softmax_grad_panels``): the window's ``y`` and ``mask`` sliced here,
    the mean, ``feature_scale`` and the L2 term as ``grad`` has them."""
    Xp, y, mask = batch
    if first is not None:
        y, mask = (jax.lax.dynamic_slice(a, (first,), (plan.rows,))
                   for a in (y, mask))
    n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
    scaled = feature_scale != 1.0
    g = kernel(w * feature_scale if scaled else w, Xp, y, mask, plan,
               first=first, interpret=interpret) / n
    if scaled:
        g = g * feature_scale
    return g + _l2_grad(w, cfg, n)


@dataclasses.dataclass(frozen=True)
class BinaryLR:
    """Dense binary logistic regression: params = w of shape (D,)."""

    num_features: int
    # MXU-friendly matmul dtype; set "float32" for bit-level parity runs.
    compute_dtype: str = "bfloat16"
    # Dequantization scale for reduced-precision feature storage
    # (cfg.feature_dtype="int8": X is stored as round(X/scale) and the
    # true logit is (Xq @ w) * scale).  Static so XLA folds the convert
    # into the matmul read; applied to the (B,)/(D,) RESULT vectors, not
    # the (B, D) matrix.  1.0 = features are already real-valued.
    feature_scale: float = 1.0
    # Native int8 x int8 -> int32 MXU contraction (cfg.feature_dtype=
    # "int8_dot").  The plain int8 storage path converts the whole (B, D)
    # tile to bfloat16 before the dot — a VPU-bound convert.  This path instead quantizes the
    # SMALL per-step operands — w over D for the forward, the residual
    # over B for the backward — with dynamic symmetric scales and feeds
    # both dots int8 operands end to end.  Requires X to be int8 (the
    # trainer's feature quantization guarantees it).
    int8_dot: bool = False

    @property
    def param_shape(self) -> tuple[int, ...]:
        return (self.num_features,)

    def init(self, cfg: Config) -> jnp.ndarray:
        if cfg.reference_rng_init:
            # Q2 parity: srand(seed); rand()/RAND_MAX per weight.
            # Reference default seed is 0 (lr.h:10), not RANDOM_SEED.
            return jnp.asarray(reference_init_weights(self.num_features, 0))
        key = jax.random.PRNGKey(cfg.random_seed)
        return jax.random.uniform(key, (self.num_features,), dtype=jnp.float32)

    def logits(self, w, X):
        if self.int8_dot:
            wq, s_w = quantize_sym(w, jnp.max(jnp.abs(w)))
            z = _int8_contract(X, wq, X.ndim - 1)
            return z * (s_w * self.feature_scale)
        cdt = jnp.dtype(self.compute_dtype)
        z = jnp.dot(
            X.astype(cdt),
            w.astype(cdt),
            preferred_element_type=jnp.float32,
        )
        return z * self.feature_scale if self.feature_scale != 1.0 else z

    def loss(self, w, batch, cfg: Config):
        X, y, mask = batch
        z = self.logits(w, X)
        # logloss via softplus for stability: log(1+e^z) - y*z
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        reg = 0.5 * cfg.l2_c * jnp.sum(w * w)
        if cfg.l2_scale_by_batch:
            reg = reg / jnp.maximum(jnp.sum(mask), 1)
        return _masked_mean(ll, mask) + reg

    def grad(self, w, batch, cfg: Config):
        X, y, mask = batch
        z = self.logits(w, X)
        resid = (jax.nn.sigmoid(z) - y.astype(jnp.float32)) * mask
        n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
        if self.int8_dot:
            # Residuals live in (-1, 1): a dynamic symmetric scale keeps
            # full int8 resolution on whatever range this batch actually
            # spans (near convergence |r| shrinks, and a fixed scale
            # would quantize everything to 0).
            rq, s_r = quantize_sym(resid, jnp.max(jnp.abs(resid)))
            g = _int8_contract(rq, X, 0) * (s_r * self.feature_scale) / n
            return g + _l2_grad(w, cfg, n)
        cdt = jnp.dtype(self.compute_dtype)
        g = (
            jnp.dot(
                resid.astype(cdt),
                X.astype(cdt),
                preferred_element_type=jnp.float32,
            )
            / n
        )
        if self.feature_scale != 1.0:
            g = g * self.feature_scale
        return g + _l2_grad(w, cfg, n)

    def grad_panels(self, w, batch, cfg: Config, plan, *, first=None,
                    interpret=False):
        """:meth:`grad` from one HBM read of the features, for a batch
        whose ``X`` is held as ``ops.pallas_lr.pad_columns(X, plan)``:
        the row-panel kernel gives ``X^T r`` in float32 whatever
        ``compute_dtype`` says (XLA's two fusions agree with float32 to
        1.5e-7 on the chip as well: PERF.md section 2); the mean, the L2
        term and ``feature_scale`` are this method's, as in ``grad``.

        ``first`` (an int32 scalar, traced) makes the batch the window
        ``[first, first + plan.rows)`` of taller resident arrays: ``y``
        and ``mask`` are sliced here, the kernel reads the features'
        window where it lies."""
        from distlr_tpu.ops.pallas_lr import lr_grad_panels  # noqa: PLC0415

        return _grad_from_panels(lr_grad_panels, w, batch, cfg, plan,
                                 self.feature_scale, first, interpret)

    def predict(self, w, X):
        # Reference decision rule: z > 0 (src/lr.cc:100-106).
        return (self.logits(w, X) > 0).astype(jnp.int32)

    def proba(self, w, X):
        """P(y=1) per row — the serving-side output (a CTR system ships
        the probability, not the thresholded label; the reference has no
        serving tier at all)."""
        return jax.nn.sigmoid(self.logits(w, X))

    def accuracy(self, w, batch):
        X, y, mask = batch
        correct = (self.predict(w, X) == y).astype(jnp.float32)
        return _masked_mean(correct, mask)

    def logloss(self, w, batch):
        """Mean test logloss WITHOUT the L2 term — the driver's parity
        metric (BASELINE.json epochs-to-logloss), which regularization
        must not contaminate."""
        X, y, mask = batch
        z = self.logits(w, X)
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        return _masked_mean(ll, mask)

    def logits_panels(self, w, Xp, plan):
        """:meth:`logits` over features held as
        ``ops.pallas_lr.pad_columns(X, plan)``: one read of ``Xp`` out of
        HBM, float32 whatever ``compute_dtype`` says, as
        :meth:`grad_panels`' forward sweep is."""
        from distlr_tpu.ops.pallas_lr import lr_logits_rows  # noqa: PLC0415

        z = lr_logits_rows(w, Xp, plan)
        return z * self.feature_scale if self.feature_scale != 1.0 else z

    def eval_from_logits(self, z, y, mask):
        """``(accuracy, logloss)`` of the rows whose logits are ``z``:
        what :meth:`accuracy` and :meth:`logloss` give, from one forward
        pass (no L2 term, the mask honoured)."""
        correct = ((z > 0).astype(jnp.int32) == y).astype(jnp.float32)
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        return _masked_mean(correct, mask), _masked_mean(ll, mask)


@dataclasses.dataclass(frozen=True)
class SoftmaxRegression:
    """Multinomial softmax regression: params = W of shape (D, K)."""

    num_features: int
    num_classes: int
    compute_dtype: str = "bfloat16"
    feature_scale: float = 1.0  # see BinaryLR.feature_scale
    int8_dot: bool = False      # see BinaryLR.int8_dot — same formulation,
    #                             W (D, K) quantized on one global grid

    @property
    def param_shape(self) -> tuple[int, ...]:
        return (self.num_features, self.num_classes)

    @property
    def _precision(self):
        """What both contractions state.  A product with K output columns
        goes to the MXU, and the TPU runs a float32 ``dot`` there as ONE
        bfloat16 pass unless told otherwise: ``compute_dtype="float32"``
        therefore states ``HIGHEST`` (six passes, float32 to the last
        bit or two: PERF.md section 6, PR 44, has the chip's readings that
        chose it over the three-pass ``HIGH``), so that float32 means float32
        on every backend.  ``bfloat16`` states nothing and stays the one
        pass it is.  ``BinaryLR`` needs none: its one-column product is
        a float32 multiply-reduce on the VPU."""
        return (jax.lax.Precision.HIGHEST
                if jnp.dtype(self.compute_dtype) == jnp.float32 else None)

    def init(self, cfg: Config) -> jnp.ndarray:
        shape = (self.num_features, self.num_classes)
        if cfg.reference_rng_init:
            flat = reference_init_weights(self.num_features * self.num_classes, 0)
            return jnp.asarray(flat.reshape(shape))
        key = jax.random.PRNGKey(cfg.random_seed)
        return jax.random.uniform(key, shape, dtype=jnp.float32)

    def logits(self, W, X):
        if self.int8_dot:
            Wq, s_w = quantize_sym(W, jnp.max(jnp.abs(W)))
            z = _int8_contract(X, Wq, X.ndim - 1)  # (B, K)
            return z * (s_w * self.feature_scale)
        cdt = jnp.dtype(self.compute_dtype)
        z = jnp.dot(
            X.astype(cdt),
            W.astype(cdt),
            precision=self._precision,
            preferred_element_type=jnp.float32,
        )
        return z * self.feature_scale if self.feature_scale != 1.0 else z

    def loss(self, W, batch, cfg: Config):
        X, y, mask = batch
        z = self.logits(W, X)
        ll = -jax.nn.log_softmax(z)[jnp.arange(z.shape[0]), y]
        reg = 0.5 * cfg.l2_c * jnp.sum(W * W)
        if cfg.l2_scale_by_batch:
            reg = reg / jnp.maximum(jnp.sum(mask), 1)
        return _masked_mean(ll, mask) + reg

    def grad(self, W, batch, cfg: Config):
        X, y, mask = batch
        z = self.logits(W, X)
        p = jax.nn.softmax(z)
        onehot = jax.nn.one_hot(y, self.num_classes, dtype=jnp.float32)
        resid = (p - onehot) * mask[:, None]
        n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
        if self.int8_dot:
            rq, s_r = quantize_sym(resid, jnp.max(jnp.abs(resid)))
            g = _int8_contract(X, rq, 0) * (s_r * self.feature_scale) / n
            return g + _l2_grad(W, cfg, n)
        cdt = jnp.dtype(self.compute_dtype)
        g = (
            jnp.dot(
                X.astype(cdt).T,
                resid.astype(cdt),
                precision=self._precision,
                preferred_element_type=jnp.float32,
            )
            / n
        )
        if self.feature_scale != 1.0:
            g = g * self.feature_scale
        return g + _l2_grad(W, cfg, n)

    def grad_panels(self, W, batch, cfg: Config, plan, *, first=None,
                    interpret=False):
        """:meth:`grad` from one HBM read of the features, for a batch
        whose ``X`` is held as ``ops.pallas_lr.pad_columns(X, plan)``:
        the row-panel kernel of ``ops/pallas_softmax.py`` gives
        ``X^T R`` with both products float32 by the six bfloat16 partial
        products ``Precision.HIGHEST`` is (``compute_dtype="float32"``'s
        arithmetic: a plan is made for no other, ``panel_plan``); the
        mean, the L2 term and ``feature_scale`` are this method's, as in
        ``grad``.  ``first``: see :meth:`BinaryLR.grad_panels`."""
        from distlr_tpu.ops.pallas_softmax import (  # noqa: PLC0415
            softmax_grad_panels,
        )

        return _grad_from_panels(softmax_grad_panels, W, batch, cfg, plan,
                                 self.feature_scale, first, interpret)

    def predict(self, W, X):
        return jnp.argmax(self.logits(W, X), axis=-1).astype(jnp.int32)

    def proba(self, W, X):
        """(B, K) class probabilities (see BinaryLR.proba)."""
        return jax.nn.softmax(self.logits(W, X), axis=-1)

    def accuracy(self, W, batch):
        X, y, mask = batch
        correct = (self.predict(W, X) == y).astype(jnp.float32)
        return _masked_mean(correct, mask)

    def logloss(self, W, batch):
        """Mean multiclass test logloss, no L2 (see BinaryLR.logloss)."""
        X, y, mask = batch
        z = self.logits(W, X)
        ll = -jax.nn.log_softmax(z)[jnp.arange(z.shape[0]), y]
        return _masked_mean(ll, mask)

    def eval_from_logits(self, z, y, mask):
        """``(accuracy, logloss)`` from one forward pass's ``(B, K)``
        logits (see BinaryLR.eval_from_logits)."""
        correct = (jnp.argmax(z, axis=-1).astype(jnp.int32) == y).astype(
            jnp.float32)
        ll = -jax.nn.log_softmax(z)[jnp.arange(z.shape[0]), y]
        return _masked_mean(correct, mask), _masked_mean(ll, mask)


@dataclasses.dataclass(frozen=True)
class SparseBinaryLR:
    """Binary LR over padded-COO sparse batches (one-hot / CTR style).

    A batch is ``(cols, vals, y, mask)`` where ``cols``/``vals`` are
    ``(B, NNZ_MAX)`` padded per-row index/value arrays (pad col = 0,
    pad val = 0).  The forward is a gather-dot; the gradient scatter is a
    ``segment_sum`` over the flattened column ids — the TPU-friendly
    sparse formulation (no dynamic shapes).
    """

    num_features: int

    @property
    def param_shape(self) -> tuple[int, ...]:
        return (self.num_features,)

    def init(self, cfg: Config) -> jnp.ndarray:
        if cfg.reference_rng_init:
            return jnp.asarray(reference_init_weights(self.num_features, 0))
        # Zeros, NOT the dense models' uniform-[0,1) reference mirror: with
        # F active features a positive-mean init biases every logit to
        # ~F/2, and at CTR scale each weight is touched too rarely for SGD
        # to unwind that (uniform init at D=1e5 sits at chance accuracy
        # for tens of epochs).  The reference has no sparse model to be
        # compatible with.
        return jnp.zeros(self.num_features, jnp.float32)

    def logits(self, w, cols, vals):
        return jnp.sum(w[cols] * vals, axis=-1)

    def loss(self, w, batch, cfg: Config):
        cols, vals, y, mask = batch
        z = self.logits(w, cols, vals)
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        reg = 0.5 * cfg.l2_c * jnp.sum(w * w)
        if cfg.l2_scale_by_batch:
            reg = reg / jnp.maximum(jnp.sum(mask), 1)
        return _masked_mean(ll, mask) + reg

    def grad(self, w, batch, cfg: Config):
        cols, vals, y, mask = batch
        z = self.logits(w, cols, vals)
        resid = (jax.nn.sigmoid(z) - y.astype(jnp.float32)) * mask
        n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
        contrib = (resid[:, None] * vals).reshape(-1)
        flat_cols = cols.reshape(-1)
        g = jax.ops.segment_sum(contrib, flat_cols, num_segments=self.num_features) / n
        return g + _l2_grad(w, cfg, n)

    def predict(self, w, cols, vals):
        return (self.logits(w, cols, vals) > 0).astype(jnp.int32)

    def proba(self, w, cols, vals):
        """P(y=1) per row (see BinaryLR.proba)."""
        return jax.nn.sigmoid(self.logits(w, cols, vals))

    def accuracy(self, w, batch):
        cols, vals, y, mask = batch
        correct = (self.predict(w, cols, vals) == y).astype(jnp.float32)
        return _masked_mean(correct, mask)

    def logloss(self, w, batch):
        """Mean test logloss, no L2 (see BinaryLR.logloss)."""
        cols, vals, y, mask = batch
        z = self.logits(w, cols, vals)
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        return _masked_mean(ll, mask)


@dataclasses.dataclass(frozen=True)
class SparseSoftmaxRegression:
    """Multinomial softmax over padded-COO sparse batches: params W of
    shape ``(D, K)``.

    The multiclass member of the CTR encoding family (the reference is
    binary-only — ``src/lr.cc``; BASELINE.json config 5's softmax family
    extended to the sparse path, completing the model-family x encoding
    matrix).  A batch is ``(cols, vals, y, mask)`` like
    :class:`SparseBinaryLR`, with integer class labels.  The forward
    gathers one K-wide class-weight ROW per active feature — the same
    row-gather access pattern the blocked path exploits, so TPU gather
    cost is per-feature, not per-(feature, class) — and the gradient is
    one ``segment_sum`` of per-feature outer contributions
    ``vals[:, :, None] * resid[:, None, :]`` over the flattened column
    ids.  In keyed PS mode the (D, K) rows travel as ``vals_per_key=K``
    frames (one u64 feature id per K floats).
    """

    num_features: int
    num_classes: int

    @property
    def param_shape(self) -> tuple[int, ...]:
        return (self.num_features, self.num_classes)

    def init(self, cfg: Config) -> jnp.ndarray:
        shape = (self.num_features, self.num_classes)
        if cfg.reference_rng_init:
            flat = reference_init_weights(
                self.num_features * self.num_classes, 0)
            return jnp.asarray(flat.reshape(shape))
        # zeros for the same reason as SparseBinaryLR.init: at CTR scale
        # a positive-mean init biases every logit and SGD touches each
        # row too rarely to unwind it
        return jnp.zeros(shape, jnp.float32)

    def logits(self, W, cols, vals):
        # (B, F, K) gathered rows, weighted per-feature, summed over F
        return jnp.sum(W[cols] * vals[..., None], axis=-2)

    def loss(self, W, batch, cfg: Config):
        cols, vals, y, mask = batch
        z = self.logits(W, cols, vals)
        ll = -jax.nn.log_softmax(z)[jnp.arange(z.shape[0]), y]
        reg = 0.5 * cfg.l2_c * jnp.sum(W * W)
        if cfg.l2_scale_by_batch:
            reg = reg / jnp.maximum(jnp.sum(mask), 1)
        return _masked_mean(ll, mask) + reg

    def grad(self, W, batch, cfg: Config):
        cols, vals, y, mask = batch
        z = self.logits(W, cols, vals)
        p = jax.nn.softmax(z)
        onehot = jax.nn.one_hot(y, self.num_classes, dtype=jnp.float32)
        resid = (p - onehot) * mask[:, None]                   # (B, K)
        n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
        contrib = (vals[..., None] * resid[:, None, :]).reshape(
            -1, self.num_classes)                              # (B*F, K)
        g = jax.ops.segment_sum(
            contrib, cols.reshape(-1), num_segments=self.num_features) / n
        return g + _l2_grad(W, cfg, n)

    def predict(self, W, cols, vals):
        return jnp.argmax(self.logits(W, cols, vals), axis=-1).astype(jnp.int32)

    def proba(self, W, cols, vals):
        """(B, K) class probabilities (see BinaryLR.proba)."""
        return jax.nn.softmax(self.logits(W, cols, vals), axis=-1)

    def accuracy(self, W, batch):
        cols, vals, y, mask = batch
        correct = (self.predict(W, cols, vals) == y).astype(jnp.float32)
        return _masked_mean(correct, mask)

    def logloss(self, W, batch):
        """Mean test cross-entropy, no L2 (see BinaryLR.logloss)."""
        cols, vals, y, mask = batch
        z = self.logits(W, cols, vals)
        ll = -jax.nn.log_softmax(z)[jnp.arange(z.shape[0]), y]
        return _masked_mean(ll, mask)


@dataclasses.dataclass(frozen=True)
class BlockedSparseLR:
    """Binary LR over row-aligned block batches (the row-blocked CTR
    path — see :func:`distlr_tpu.data.hashing.hash_group_blocks`).

    Params are a ``(num_blocks, block_size)`` table.  A batch is
    ``(blocks, lane_vals, y, mask)`` with ``blocks`` of shape (B, G) and
    ``lane_vals`` of shape (B, G, R): each sample gathers G contiguous
    R-wide rows instead of G*R scalars, which amortizes the TPU gather
    unit's per-index cost; the gradient scatter is a ``segment_sum`` of R-wide
    rows, blocked the same way.  Logit = sum over groups of
    ``T[block_g] . lane_vals_g`` — with lane_vals the one-hot/raw values
    of the group's member fields, this is per-(conjunction, field)
    logistic regression.
    """

    num_blocks: int
    block_size: int = 8

    @property
    def param_shape(self) -> tuple[int, ...]:
        return (self.num_blocks, self.block_size)

    def init(self, cfg: Config) -> jnp.ndarray:
        # Zeros for the same reason SparseBinaryLR uses them: untrained
        # rows (unseen conjunctions) must contribute nothing, not noise.
        return jnp.zeros((self.num_blocks, self.block_size), jnp.float32)

    def logits(self, t, blocks, lane_vals):
        return jnp.sum(t[blocks] * lane_vals, axis=(-1, -2))

    def loss(self, t, batch, cfg: Config):
        blocks, lane_vals, y, mask = batch
        z = self.logits(t, blocks, lane_vals)
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        reg = 0.5 * cfg.l2_c * jnp.sum(t * t)
        if cfg.l2_scale_by_batch:
            reg = reg / jnp.maximum(jnp.sum(mask), 1)
        return _masked_mean(ll, mask) + reg

    def grad(self, t, batch, cfg: Config):
        blocks, lane_vals, y, mask = batch
        z = self.logits(t, blocks, lane_vals)
        resid = (jax.nn.sigmoid(z) - y.astype(jnp.float32)) * mask
        n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
        # Row-blocked scatter: (B*G, R) row contributions summed per block.
        contrib = (resid[:, None, None] * lane_vals).reshape(-1, self.block_size)
        g = jax.ops.segment_sum(
            contrib, blocks.reshape(-1), num_segments=self.num_blocks
        ) / n
        return g + _l2_grad(t, cfg, n)

    def predict(self, t, blocks, lane_vals):
        return (self.logits(t, blocks, lane_vals) > 0).astype(jnp.int32)

    def proba(self, t, blocks, lane_vals):
        """P(y=1) per row (see BinaryLR.proba)."""
        return jax.nn.sigmoid(self.logits(t, blocks, lane_vals))

    def accuracy(self, t, batch):
        blocks, lane_vals, y, mask = batch
        correct = (self.predict(t, blocks, lane_vals) == y).astype(jnp.float32)
        return _masked_mean(correct, mask)

    def logloss(self, t, batch):
        """Mean test logloss, no L2 (see BinaryLR.logloss)."""
        blocks, lane_vals, y, mask = batch
        z = self.logits(t, blocks, lane_vals)
        ll = jax.nn.softplus(z) - y.astype(jnp.float32) * z
        return _masked_mean(ll, mask)


def get_model(cfg: Config):
    if cfg.model == "binary_lr":
        return BinaryLR(cfg.num_feature_dim, compute_dtype=cfg.compute_dtype,
                        int8_dot=cfg.feature_dtype == "int8_dot")
    if cfg.model == "softmax":
        return SoftmaxRegression(cfg.num_feature_dim, cfg.num_classes,
                                 compute_dtype=cfg.compute_dtype,
                                 int8_dot=cfg.feature_dtype == "int8_dot")
    if cfg.model == "sparse_lr":
        return SparseBinaryLR(cfg.num_feature_dim)
    if cfg.model == "sparse_softmax":
        return SparseSoftmaxRegression(cfg.num_feature_dim, cfg.num_classes)
    if cfg.model == "blocked_lr":
        if cfg.block_size == 0:
            raise ValueError(
                "block_size=0 (auto) must be resolved before building a "
                "model — see data.hashing.resolve_auto_block_size (the "
                "launch CLI does this for --block-size auto)"
            )
        if cfg.num_feature_dim % cfg.block_size:
            raise ValueError(
                f"num_feature_dim ({cfg.num_feature_dim}) must be a multiple "
                f"of block_size ({cfg.block_size}) for blocked_lr"
            )
        return BlockedSparseLR(cfg.num_feature_dim // cfg.block_size, cfg.block_size)
    raise ValueError(f"unknown model {cfg.model!r}")
