"""The softmax row-panel kernel (``ops/pallas_softmax.py``), interpreted on
the CPU: equal to numpy's float64 gradient and to
``SoftmaxRegression.grad`` in float32; both products the six bfloat16
partial products and no fewer; the plan's table; the flat vector in and
out of ``ps_grad_step`` with a plan as without.  (The kernel compiled for
a described v5e at the cell's size is in ``tests/test_ops.py``, the one
file that describes one.)"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlr_tpu.models.linear import BinaryLR, SoftmaxRegression
from distlr_tpu.ops import pad_columns, pallas_softmax
from distlr_tpu.ops.pallas_softmax import (
    SIX,
    softmax_grad_panels,
    softmax_panel_plan,
    split3,
    split3_xla,
)
from distlr_tpu.train import ps_trainer


def _problem(rows, dim, classes, seed=0, masked=0, dense=0.1):
    rng = np.random.default_rng(seed)
    X = (rng.standard_normal((rows, dim))
         * (rng.random((rows, dim)) < dense)).astype(np.float32)
    y = rng.integers(0, classes, rows).astype(np.int32)
    mask = np.ones(rows, np.float32)
    if masked:
        mask[rng.choice(rows, masked, replace=False)] = 0
    W = (rng.standard_normal((dim, classes)) * 0.5).astype(np.float32)
    return W, X, y, mask


def _float64_gradient(W, X, y, mask):
    """``X^T ((softmax(X W) - onehot(y)) * mask)`` in numpy's float64."""
    z = X.astype(np.float64) @ W.astype(np.float64)
    z -= z.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    p[np.arange(len(y)), y] -= 1.0
    return X.astype(np.float64).T @ (p * mask[:, None])


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# rows, dim (no multiple of 128), classes, block_tiles, l2_c, l2_scale_by_batch
CASES = [
    pytest.param(256, 1000, 20, 2, 0.0, False, id="K20-two-panels-4-blocks"),
    pytest.param(256, 1000, 20, 2, 0.01, False, id="K20-l2"),
    pytest.param(256, 1000, 20, 2, 0.01, True, id="K20-l2-by-batch"),
    pytest.param(128, 300, 2, 1, 0.0, False, id="K2-one-panel-3-blocks"),
    pytest.param(128, 300, 2, 1, 0.5, True, id="K2-l2-by-batch"),
    pytest.param(384, 2500, 42, 4, 0.0, False, id="K42-three-panels"),
    pytest.param(384, 2500, 42, 4, 0.01, False, id="K42-l2"),
    pytest.param(128, 1100, 20, 8, 0.01, True, id="K20-one-block-of-1024"),
]


@pytest.mark.parametrize("rows,dim,classes,block_tiles,l2_c,by_batch", CASES)
def test_equals_the_float64_gradient_and_the_models(rows, dim, classes,
                                                    block_tiles, l2_c,
                                                    by_batch):
    """Masked rows, a D that is no multiple of 128, the L2 term either
    way: the kernel's gradient stands as close to float64's as
    ``SoftmaxRegression.grad`` in float32 (``HIGHEST``) does."""
    plan = softmax_panel_plan(rows, dim, classes, block_tiles=block_tiles)
    assert plan.dim_padded % 128 == 0 and 0 <= plan.dim_padded - dim
    W, X, y, mask = _problem(rows, dim, classes, masked=7)
    cfg = types.SimpleNamespace(l2_c=l2_c, l2_scale_by_batch=by_batch)
    model = SoftmaxRegression(dim, classes, compute_dtype="float32")
    got = model.grad_panels(jnp.asarray(W), (pad_columns(jnp.asarray(X), plan),
                                             y, mask), cfg, plan,
                            interpret=True)
    n = mask.sum()
    want = (_float64_gradient(W, X, y, mask) / n
            + l2_c * W.astype(np.float64) / (n if by_batch else 1.0))
    assert got.shape == (dim, classes) and got.dtype == jnp.float32
    assert _rel(got, want) <= 4e-7
    xla = model.grad(jnp.asarray(W), (X, y, mask), cfg)
    assert _rel(got, xla) <= 5e-7 and _rel(xla, want) <= 4e-7


def test_feature_scale_is_the_methods_as_in_grad():
    plan = softmax_panel_plan(128, 300, 5, block_tiles=1)
    W, X, y, mask = _problem(128, 300, 5, seed=2)
    cfg = types.SimpleNamespace(l2_c=0.1, l2_scale_by_batch=False)
    model = SoftmaxRegression(300, 5, compute_dtype="float32",
                              feature_scale=0.25)
    got = model.grad_panels(jnp.asarray(W), (pad_columns(jnp.asarray(X), plan),
                                             y, mask), cfg, plan,
                            interpret=True)
    assert _rel(got, model.grad(jnp.asarray(W), (X, y, mask), cfg)) <= 5e-7


def test_a_window_is_read_where_it_lies():
    """128 of 384 resident rows from a traced first row: one executable,
    equal to the kernel over those rows alone; a window past the end
    starts where it still fits."""
    R, B, dim, classes = 384, 128, 300, 6
    W, X, y, mask = _problem(R, dim, classes, seed=5, masked=9)
    plan = softmax_panel_plan(B, dim, classes, block_tiles=2)
    Xp = pad_columns(jnp.asarray(X), plan)
    step = jax.jit(lambda first: softmax_grad_panels(
        W, Xp, jax.lax.dynamic_slice(y, (jnp.minimum(first, R - B),), (B,)),
        jax.lax.dynamic_slice(mask, (jnp.minimum(first, R - B),), (B,)),
        plan, first=first, interpret=True))
    for first in (0, 128, 256, 300):
        at = min(first, R - B)
        whole = softmax_grad_panels(W, Xp[at:at + B], y[at:at + B],
                                    mask[at:at + B], plan, interpret=True)
        assert np.array_equal(step(jnp.int32(first)), whole), first


def test_the_kernel_refuses_a_matrix_that_was_not_padded():
    plan = softmax_panel_plan(128, 300, 4)
    W, X, y, mask = _problem(128, 300, 4)
    with pytest.raises(ValueError, match="pad_columns"):
        softmax_grad_panels(W, jnp.asarray(X), y, mask, plan, interpret=True)
    with pytest.raises(ValueError, match="weights"):
        softmax_grad_panels(W[:, :3], pad_columns(jnp.asarray(X), plan), y,
                            mask, plan, interpret=True)


# -- float32 means the six partial products ----------------------------------
def test_the_three_parts_are_bfloat16_and_sum_to_the_float32():
    rng = np.random.default_rng(1)
    x = jnp.asarray((rng.standard_normal((64, 256))
                     * 2.0 ** rng.integers(-20, 20, (64, 256))
                     ).astype(np.float32))
    parts = split3(x)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    # the form XLA's simplifier leaves alone gives the same bits, jitted
    for ours, xlas in zip(parts, jax.jit(split3_xla)(x)):
        assert xlas.dtype == jnp.bfloat16 and np.array_equal(
            np.asarray(ours, np.float32), np.asarray(xlas, np.float32))
    total = sum(np.asarray(p, np.float64) for p in parts)
    assert np.array_equal(total.astype(np.float32), np.asarray(x))
    # each part is what the ones before it left, rounded: 2^-8 apart
    for big, small in zip(parts, parts[1:]):
        assert np.all(np.abs(np.asarray(small, np.float64))
                      <= 2.0 ** -8 * np.abs(np.asarray(big, np.float64)))


def test_the_six_are_highests_six_small_terms_first():
    assert sorted(SIX) == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0)]
    assert [i + j for i, j in SIX] == [2, 2, 2, 1, 1, 0]


def _full_mantissa_problem():
    """Every operand with all 24 bits of its mantissa in play, so that
    each of the six partial products carries weight: a dropped ``lo.hi``,
    ``mid.mid`` or ``hi.lo`` costs 2^-18 of a product, thirty times what
    the six leave."""
    W, X, y, mask = _problem(128, 600, 20, seed=11, dense=1.0)
    return W, X, y, mask, softmax_panel_plan(128, 600, 20, block_tiles=2)


def _kernel_error():
    W, X, y, mask, plan = _full_mantissa_problem()
    got = softmax_grad_panels(W, pad_columns(jnp.asarray(X), plan), y, mask,
                              plan, interpret=True)
    return _rel(got, _float64_gradient(W, X, y, mask))


def test_both_products_are_float32_by_the_six():
    assert _kernel_error() <= 3e-7


@pytest.mark.parametrize("dropped", SIX, ids=lambda t: f"x{t[0]}.w{t[1]}")
def test_a_dropped_partial_product_shows(dropped, monkeypatch):
    """Five of the six, whichever is left out (of both products: the
    sums read one table), is no float32 product: ten times the six's
    error at least for a small term, a thousandth and more for the
    others."""
    monkeypatch.setattr(pallas_softmax, "SIX",
                        tuple(t for t in SIX if t != dropped))
    floor = {2: 2e-6, 1: 5e-4, 0: 0.5}[sum(dropped)]
    assert _kernel_error() >= floor


# -- the plan ------------------------------------------------------------------
def test_the_plan_at_the_cells_shape_and_where_there_is_none():
    plan = softmax_panel_plan(3968, 62061, 20)
    # 485 tiles: 18 blocks of 27 pad one tile on, the fewest of 16..32
    assert (plan.panels, plan.blocks, plan.block_tiles) == (31, 18, 27)
    assert plan.dim_padded == 62208 and plan.class_rows == 32
    assert (plan.held_share, plan.ahead_share) == (1.0, 1.0)
    # two banks 63.7 MB, the weights' parts 11.9, the gradient 8.0, a
    # block's parts and products 7.5, slack
    assert plan.vmem_bytes == (
        2 * 128 * 62208 * 4 + 96 * 62208 * 2 + 32 * 62208 * 4
        + 3456 * (128 * 10 + 7 * 32 * 4) + (4 << 20))
    assert plan.vmem_bytes < plan.vmem_limit == 120 << 20
    # a narrow matrix is one block of its own tiles; ties go to the larger
    assert softmax_panel_plan(128, 1000, 20).block_tiles == 8
    assert softmax_panel_plan(128, 64 * 128, 20).block_tiles == 32
    assert softmax_panel_plan(3968, 62061, 42).class_rows == 48
    assert softmax_panel_plan(3968, 62061, 43) is None     # 3 K > 128
    assert softmax_panel_plan(3968, 62061, 1) is None      # no class axis
    assert softmax_panel_plan(100, 62061, 20) is None      # no whole panel
    assert softmax_panel_plan(3968 + 8, 62061, 20) is None
    assert softmax_panel_plan(3968, 62061, 20, vmem_limit=80 << 20) is None
    assert softmax_panel_plan(384, 1_000_000, 20) is None  # two panels: 1 GB


def test_the_selection_reads_the_model_the_device_and_the_shape():
    one_pass = ps_trainer._one_pass_plan
    tpu = type("Device", (), {"platform": "tpu"})()

    def softmax(classes=20, **kw):
        kw.setdefault("compute_dtype", "float32")
        return SoftmaxRegression(62061, classes, **kw)

    plan = one_pass(softmax(), 3968, 62061, tpu)
    assert plan == softmax_panel_plan(3968, 62061, 20)
    # the control keeps XLA's one-pass products
    assert one_pass(softmax(compute_dtype="bfloat16"), 3968, 62061,
                    tpu) is None
    assert one_pass(softmax(int8_dot=True), 3968, 62061, tpu) is None
    assert one_pass(softmax(43), 3968, 62061, tpu) is None
    assert one_pass(softmax(), 100, 62061, tpu) is None
    assert one_pass(softmax(), 3968, 62061, jax.devices()[0]) is None
    # an eval's rows: the forward alone is XLA's one fusion already
    assert one_pass(softmax(), 3968, 62061, tpu, forward=True) is None
    # ``BinaryLR``'s answers are what they were, an eval's rows too
    from distlr_tpu.ops import panel_plan

    binary = BinaryLR(1_000_000)
    for forward in (False, True):
        assert one_pass(binary, 384, 1_000_000, tpu,
                        forward=forward) == panel_plan(384, 1_000_000)
    assert one_pass(BinaryLR(1_000_000, int8_dot=True), 384, 1_000_000,
                    tpu) is None
    assert one_pass(binary, 380, 1_000_000, tpu) is None
    assert one_pass(binary, 384, 1_000_000, jax.devices()[0]) is None


# -- the flat vector in and out of the step ------------------------------------
@pytest.mark.parametrize("rows", ["whole", "window"])
def test_the_flat_step_with_a_plan_is_laid_out_as_the_one_without(rows):
    """``ps_grad_step`` takes and returns the wire's flat ``f32[D K]``,
    feature-major, whichever program computes between the two reshapes:
    element for element the two-pass step's, to float32's rounding."""
    dim, classes, R = 300, 6, 256
    model = SoftmaxRegression(dim, classes, compute_dtype="float32")
    W, X, y, mask = _problem(R, dim, classes, seed=8, masked=5)
    w = W.reshape(-1)
    step = ps_trainer._compiled_fns(model, 0.01, True)
    if rows == "window":
        plan = softmax_panel_plan(128, dim, classes, block_tiles=2)
        how = dict(first=np.int32(128))
        want = step(w, X, y, mask, window=128, **how)
    else:
        plan = softmax_panel_plan(R, dim, classes, block_tiles=2)
        how = {}
        want = step(w, X, y, mask)
    got = step(w, pad_columns(jnp.asarray(X), plan), y, mask, panels=plan,
               interpret=True, **how)
    assert got.shape == want.shape == (dim * classes,)
    assert got.dtype == jnp.float32
    got, want = np.asarray(got), np.asarray(want)
    assert np.count_nonzero(want) and _rel(got, want) <= 5e-7
    # the same element in the same place: feature-major, ``[D, K]`` flat
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2e-6 * scale


# -- the import, beside the load -------------------------------------------------
@pytest.mark.parametrize("keyed", [False, True], ids=["dense", "keyed"])
def test_the_kernels_are_imported_beside_the_load_where_a_plan_can_be(
        monkeypatch, keyed):
    """Pallas is 1.5 s of import: where the platform has a one-pass
    program a dense worker starts it on a thread of its own before it
    parses its shard (a keyed one, the module of its own kernel, before
    it localises its); nothing starts where it is imported already, or
    on a platform without such a program (the CPU)."""
    import sys
    import threading

    import distlr_tpu.ops.pallas_keyed  # noqa: F401  (so that it can be dropped)

    name = "distlr_tpu.ops." + ("pallas_keyed" if keyed else "pallas_softmax")
    called, done = [], threading.Event()

    def import_module(module):
        called.append((module, threading.current_thread().name))
        done.set()

    monkeypatch.setattr(ps_trainer, "importlib",
                        types.SimpleNamespace(import_module=import_module))
    here = (jax.default_backend(),)
    monkeypatch.setattr(ps_trainer, "_ONE_PASS_PLATFORMS", here)
    ps_trainer._import_kernels_beside_the_load(keyed)    # imported already
    monkeypatch.delitem(sys.modules, name)
    monkeypatch.setattr(ps_trainer, "_ONE_PASS_PLATFORMS", ("tpu",))
    ps_trainer._import_kernels_beside_the_load(keyed)    # no such program here
    assert not called
    monkeypatch.setattr(ps_trainer, "_ONE_PASS_PLATFORMS", here)
    ps_trainer._import_kernels_beside_the_load(keyed)
    assert done.wait(5) and called == [(name, "distlr-import-kernels")]
