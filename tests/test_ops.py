"""The row-panel kernel (``ops/pallas_lr.py``), interpreted on the CPU:
equal to ``BinaryLR.grad`` in float32 whatever share of a panel VMEM
holds and however many slots it has to fetch ahead into, and compiled
for a described v5e at the cell's size; beside it, in the one file that
describes a v5e, the multiclass PS step as the compiler leaves it there
(flat operand and result, the shard read as it lies), without a plan
and with the softmax kernel's (``ops/pallas_softmax.py``, whose
interpreted tests are ``tests/test_ops_softmax.py``)."""

import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlr_tpu.models.linear import BinaryLR, SoftmaxRegression
from distlr_tpu.ops import PanelPlan, lr_grad_panels, pad_columns, panel_plan
from distlr_tpu.ops import pallas_lr


def _problem(rows, dim, seed=0, masked=0):
    rng = np.random.default_rng(seed)
    # about 40 non-zeros a row, as a densified click log has: sums short
    # enough that two float32 orders of summation agree to 1e-6
    X = (rng.standard_normal((rows, dim))
         * (rng.random((rows, dim)) < 40 / dim)).astype(np.float32)
    y = rng.integers(0, 2, rows).astype(np.int32)
    mask = np.ones(rows, np.float32)
    if masked:
        mask[-masked:] = 0
    w = (rng.standard_normal(dim) * 0.1).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (w, X, y, mask))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _float32_model(dim):
    return BinaryLR(dim, compute_dtype="float32")


def _limit_for(rows, dim, chunk_tiles, slots):
    """The least VMEM limit under which ``slots`` chunk slots fit."""
    lo, hi = 0, pallas_lr.VMEM_LIMIT_BYTES
    while lo + 1 < hi:
        mid = (lo + hi) // 2
        plan = panel_plan(rows, dim, vmem_limit=mid, chunk_tiles=chunk_tiles)
        if plan is not None and plan.slots >= slots:
            hi = mid
        else:
            lo = mid
    return hi


def _plan(rows, dim, chunk_tiles=256, slots=None):
    """The plan with ``slots`` chunk slots of VMEM and no more (None: the
    default limit, which at these sizes is a whole second bank): fewer
    than a panel's chunks hold a part of it and keep the ring of two,
    what is over them is the look-ahead's."""
    limit = (pallas_lr.VMEM_LIMIT_BYTES if slots is None
             else _limit_for(rows, dim, chunk_tiles, slots))
    plan = panel_plan(rows, dim, vmem_limit=limit, chunk_tiles=chunk_tiles)
    if slots is None:
        slots = 2 * plan.chunks
    assert plan.slots == slots and plan.vmem_bytes <= limit
    if slots < plan.chunks:
        assert (plan.held, plan.ahead) == (slots - 2, 0)
    else:
        assert (plan.held, plan.ahead) == (plan.chunks, slots - plan.chunks)
    return plan


# rows, dim, chunk_tiles, slots of VMEM (None: the default limit)
SHAPES = [
    pytest.param(16, 1000, 256, None, id="D1000-one-chunk-and-a-bank-ahead"),
    pytest.param(16, 1000, 256, 1, id="D1000-one-chunk-none-ahead"),
    pytest.param(24, 16384 + 64, 8, None, id="D16448-17-chunks-a-bank-ahead"),
    pytest.param(24, 16384 + 64, 8, 22, id="D16448-17-chunks-5-ahead"),
    pytest.param(24, 16384 + 64, 8, 17, id="D16448-17-chunks-none-ahead"),
    pytest.param(24, 16384 + 64, 8, 10, id="D16448-8-of-17-held"),
    pytest.param(40, 3000, 4, 10, id="D3000-five-panels-4-of-6-ahead"),
    pytest.param(40, 3000, 4, 7, id="D3000-five-panels-1-of-6-ahead"),
    pytest.param(16, 3000, 4, 3, id="D3000-1-of-6-held"),
    pytest.param(8, 3000, 5, None, id="D3000-one-panel-a-bank-ahead"),
    pytest.param(8, 3000, 5, 4, id="D3000-one-panel-2-of-5-held"),
]

# a panel of four chunks: none ahead, two, a whole second bank
AHEAD = [pytest.param(4, id="none-ahead"), pytest.param(6, id="2-of-4-ahead"),
         pytest.param(None, id="a-bank-ahead")]


def _four_chunks(rows, slots):
    plan = _plan(rows, 1000, 2, slots)
    assert (plan.chunks, plan.held_share) == (4, 1.0)
    return plan


@pytest.mark.parametrize("rows,dim,chunk_tiles,slots", SHAPES)
def test_equals_binary_lr_grad_in_float32(rows, dim, chunk_tiles, slots):
    plan = _plan(rows, dim, chunk_tiles, slots)
    assert plan.dim_padded % 128 == 0 and 0 <= plan.dim_padded - dim
    w, X, y, mask = _problem(rows, dim, masked=3)
    cfg = types.SimpleNamespace(l2_c=0.0, l2_scale_by_batch=False)
    model = _float32_model(dim)
    with jax.default_matmul_precision("highest"):
        want = model.grad(w, (X, y, mask), cfg)
    got = model.grad_panels(w, (pad_columns(X, plan), y, mask), cfg, plan,
                            interpret=True)
    assert got.shape == (dim,) and got.dtype == jnp.float32
    assert _rel(got, want) < 1e-6


@pytest.mark.parametrize("slots", AHEAD)
@pytest.mark.parametrize("l2_c,by_batch", [(0.5, False), (0.5, True)])
def test_l2_term_is_the_models(l2_c, by_batch, slots):
    rows, dim = 16, 1000
    plan = _four_chunks(rows, slots)
    w, X, y, mask = _problem(rows, dim, seed=1, masked=5)
    cfg = types.SimpleNamespace(l2_c=l2_c, l2_scale_by_batch=by_batch)
    model = _float32_model(dim)
    with jax.default_matmul_precision("highest"):
        want = model.grad(w, (X, y, mask), cfg)
        bare = model.grad(w, (X, y, mask),
                          types.SimpleNamespace(l2_c=0.0,
                                                l2_scale_by_batch=False))
    got = model.grad_panels(w, (pad_columns(X, plan), y, mask), cfg, plan,
                            interpret=True)
    assert _rel(got, want) < 1e-6
    assert _rel(want, bare) > 1e-2   # the term is there to be missed


@pytest.mark.parametrize("slots", AHEAD)
def test_masked_rows_contribute_nothing(slots):
    rows, dim = 16, 1000
    plan = _four_chunks(rows, slots)
    w, X, y, mask = _problem(rows, dim, seed=2, masked=6)
    garbage = X.at[-6:].set(1e6)
    a = lr_grad_panels(w, pad_columns(X, plan), y, mask, plan, interpret=True)
    b = lr_grad_panels(w, pad_columns(garbage, plan), y, mask, plan,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("slots", AHEAD)
def test_pad_columns_never_reach_the_gradient(slots):
    """Whatever stands in the pad columns of X moves nothing in
    ``g[:D]``: they meet zero weights forward and are cut backward."""
    rows, dim = 16, 1000
    plan = _four_chunks(rows, slots)
    w, X, y, mask = _problem(rows, dim, seed=3)
    Xp = pad_columns(X, plan)
    assert Xp.shape == (rows, plan.dim_padded)
    assert not np.asarray(Xp[:, dim:]).any()
    dirty = Xp.at[:, dim:].set(7.0)
    a = lr_grad_panels(w, Xp, y, mask, plan, interpret=True)
    b = lr_grad_panels(w, dirty, y, mask, plan, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("slots", AHEAD)
def test_feature_scale_is_the_models(slots):
    rows, dim = 16, 1000
    plan = _four_chunks(rows, slots)
    w, X, y, mask = _problem(rows, dim, seed=4)
    cfg = types.SimpleNamespace(l2_c=0.1, l2_scale_by_batch=False)
    model = BinaryLR(dim, compute_dtype="float32", feature_scale=0.25)
    with jax.default_matmul_precision("highest"):
        want = model.grad(w, (X, y, mask), cfg)
    got = model.grad_panels(w, (pad_columns(X, plan), y, mask), cfg, plan,
                            interpret=True)
    assert _rel(got, want) < 1e-6


def test_the_plan_follows_the_shape_and_the_limit():
    cell = panel_plan(384, 1_000_000)
    assert cell == PanelPlan(384, 1_000_000, 253, 31, 31, 31,
                             pallas_lr.VMEM_LIMIT_BYTES)
    assert cell.held_share == cell.ahead_share == 1.0 and cell.slots == 62
    assert cell.dim_padded == 31 * 253 * 128 == 1_003_904
    # two banks of 31 slots of 8 x 32,384 floats; beside them the eight
    # partials, the gradient a tile a row, the weights, the slack
    assert cell.slot_bytes == 8 * 253 * 128 * 4
    assert cell.fixed_bytes == (8 * 1_003_904 * 4 + 7848 * 128 * 4
                                + 31 * 256 * 128 * 4 + (4 << 20))
    assert cell.vmem_bytes == cell.fixed_bytes + 62 * cell.slot_bytes
    # 103.6 MiB of the 120
    assert cell.vmem_bytes == 108_650_496 < pallas_lr.VMEM_LIMIT_BYTES
    # the rows do not move the plan: a window's, the four-chip cell's
    for rows in (128, 1152):
        assert panel_plan(rows, 1_000_000) == dataclasses.replace(
            cell, rows=rows)
    # a limit between one bank and two: the slots that fit, all ahead
    some = panel_plan(384, 1_000_000, vmem_limit=96 << 20)
    assert some.held == 31 and 0 < some.ahead < 31
    assert some.vmem_bytes <= 96 << 20 < some.vmem_bytes + some.slot_bytes
    # half of VMEM: the partials, the weights and a part of a panel,
    # nothing ahead, the ring of two as before
    half = panel_plan(384, 1_000_000, vmem_limit=64 << 20)
    assert 0 < half.held_share < 1 and half.slots == half.held + 2
    assert half.ahead == 0 and half.ahead_share == 0.0
    # no room beside the partials, or rows that are no sublane groups
    assert panel_plan(384, 1_000_000, vmem_limit=40 << 20) is None
    assert panel_plan(380, 1_000_000) is None
    assert panel_plan(384, 4_000_000) is None   # 128 MB of partials


# -- a window of a taller resident matrix, from a first row -----------------
# R, B, D, chunk_tiles, slots of VMEM: a panel held and a bank, a part of a
# bank or nothing ahead of it; a part of a panel held
WINDOWS = [
    pytest.param(48, 16, 1000, 256, None, id="16-of-48-D1000"),
    pytest.param(72, 24, 16384 + 64, 8, None,
                 id="24-of-72-D16448-a-bank-ahead"),
    pytest.param(72, 24, 16384 + 64, 8, 22, id="24-of-72-D16448-5-ahead"),
    pytest.param(72, 24, 16384 + 64, 8, 17, id="24-of-72-D16448-none-ahead"),
    pytest.param(72, 24, 16384 + 64, 8, 10, id="24-of-72-D16448-8-of-17-held"),
]


@pytest.mark.parametrize("where", ["0", "8", "R-B"])
@pytest.mark.parametrize("R,B,dim,chunk_tiles,slots", WINDOWS)
def test_a_window_from_a_first_row_is_the_models_grad_on_the_sliced_rows(
        R, B, dim, chunk_tiles, slots, where):
    first = {"0": 0, "8": 8, "R-B": R - B}[where]
    plan = _plan(B, dim, chunk_tiles, slots)
    assert plan.rows == B
    w, X, y, mask = _problem(R, dim, seed=5, masked=3)
    cfg = types.SimpleNamespace(l2_c=0.2, l2_scale_by_batch=False)
    model = _float32_model(dim)
    rows = slice(first, first + B)
    with jax.default_matmul_precision("highest"):
        want = model.grad(w, (X[rows], y[rows], mask[rows]), cfg)
    got = model.grad_panels(w, (pad_columns(X, plan), y, mask), cfg, plan,
                            first=jnp.int32(first), interpret=True)
    assert got.shape == (dim,) and got.dtype == jnp.float32
    assert _rel(got, want) < 1e-6


def test_every_window_runs_the_one_executable():
    """The first row is an operand: a jitted step traced once serves
    every window, and one that would run past the last row starts where
    it still fits, as ``dynamic_slice`` has it."""
    R, B, dim = 48, 16, 1000
    plan = panel_plan(B, dim)
    w, X, y, mask = _problem(R, dim, seed=6)
    Xp = pad_columns(X, plan)
    step = jax.jit(lambda first: lr_grad_panels(
        w, Xp, jax.lax.dynamic_slice(y, (first,), (B,)),
        jax.lax.dynamic_slice(mask, (first,), (B,)), plan, first=first,
        interpret=True))
    got = {first: np.asarray(step(np.int32(first))) for first in (0, 16, 32, 40)}
    assert step._cache_size() == 1
    np.testing.assert_array_equal(got[32], got[40])
    assert _rel(got[0], got[16]) > 1e-2 and _rel(got[16], got[32]) > 1e-2
    whole = lr_grad_panels(w, Xp[16:32], y[16:32], mask[16:32], plan,
                           interpret=True)
    np.testing.assert_array_equal(got[16], np.asarray(whole))


def test_pad_columns_adds_the_rows_a_short_last_window_needs():
    plan = panel_plan(16, 1000)
    _w, X, _y, _mask = _problem(40, 1000)
    Xp = pad_columns(X, plan, rows=48)
    assert Xp.shape == (48, plan.dim_padded) and not np.asarray(Xp[40:]).any()
    np.testing.assert_array_equal(np.asarray(Xp[:40, :1000]), np.asarray(X))


def test_a_window_of_a_matrix_that_does_not_hold_it_is_refused():
    plan = panel_plan(16, 1000)
    w, X, y, mask = _problem(8, 1000)
    with pytest.raises(ValueError, match="window"):
        lr_grad_panels(w, pad_columns(X, plan), y, mask, plan,
                       first=jnp.int32(0), interpret=True)


def test_a_matrix_that_was_not_padded_is_refused():
    plan = panel_plan(16, 1000)
    w, X, y, mask = _problem(16, 1000)
    with pytest.raises(ValueError, match="pad_columns"):
        lr_grad_panels(w, X, y, mask, plan, interpret=True)


@pytest.fixture(scope="module")
def chips():
    """A described v5e 2x2's four chips, one sharding each."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return [SingleDeviceSharding(d) for d in topo.devices]


def _no_partials_leave_the_kernel(text, plan):
    """The eight sublane partials are summed in VMEM: the step's program
    holds no ``f32[8, Dp]`` result, so no reduction over one either, and
    the kernel's result is the gradient once, a tile a row."""
    assert f"f32[8,{plan.dim_padded}]" not in text
    call, = (ln for ln in text.splitlines() if "tpu_custom_call" in ln)
    assert f" = f32[{plan.dim_padded // 128},128]" in call, call
    for line in text.splitlines():
        if re.match(r"\s*(ROOT )?%\S+ = \S+ reduce\(", line):
            # what is reduced is a scalar's worth: the mask's count
            assert re.search(r"= [fs]32\[\]\S* reduce\(", line), line


@pytest.mark.parametrize("limit,rows,chip", [
    (None, 384, 0), (64 << 20, 384, 0), (None, 1152, 2)],
    ids=["all-held", "part-held", "four-chip-cells-shard-on-chip-2"])
def test_compiles_for_a_v5e_at_the_cells_size(chips, limit, rows, chip):
    """Mosaic takes the kernel at 384 x 1,000,000 float32 under the
    default limit and under one that holds part of a panel, and at the
    1,152 rows a worker of the four-chip cell keeps, for a chip that is
    not the first; XLA hands it the resident shard as it lies: no copy,
    transpose or reshape of the operand in the step's program, and gets
    the gradient back once."""
    one_chip, dim = chips[chip], 1_000_000
    plan = (panel_plan(rows, dim) if limit is None
            else panel_plan(rows, dim, vmem_limit=limit))
    cfg = types.SimpleNamespace(l2_c=1.0, l2_scale_by_batch=False)
    model = BinaryLR(dim)

    def ps_grad_step(w, Xp, y, mask):
        return model.grad_panels(w, (Xp, y, mask), cfg, plan)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(ps_grad_step).lower(
        spec((dim,), jnp.float32), spec((rows, plan.dim_padded), jnp.float32),
        spec((rows,), jnp.int32), spec((rows,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    big = f"f32[{rows},{plan.dim_padded}]"
    for line in text.splitlines():
        if (big in line and re.match(r"\s*(ROOT )?%\S+ = ", line)
                and "custom-call(" not in line):
            assert " parameter(" in line, line
    _no_partials_leave_the_kernel(text, plan)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def test_a_window_compiles_for_a_v5e_as_one_read_of_the_rows_where_they_lie(
        chips):
    """The minibatch cell's step: 128 of a resident shard's 384 rows from
    a traced first row.  Mosaic takes it; XLA hands the kernel the shard
    as it lies (no ``dynamic-slice``, copy or reshape of the matrix, so
    no second crossing of the window's 514 MB), and the plan is the
    whole-shard one but for its rows."""
    R, B, dim = 384, 128, 1_000_000
    plan = panel_plan(B, dim)
    assert dataclasses.replace(plan, rows=R) == panel_plan(R, dim)
    assert (plan.held_share, plan.chunks) == (1.0, 31)
    cfg = types.SimpleNamespace(l2_c=0.0, l2_scale_by_batch=False)
    model = BinaryLR(dim)

    def ps_grad_step(w, Xp, y, mask, first):
        return model.grad_panels(w, (Xp, y, mask), cfg, plan, first=first)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chips[0])

    compiled = jax.jit(ps_grad_step).lower(
        spec((dim,), jnp.float32), spec((R, plan.dim_padded), jnp.float32),
        spec((R,), jnp.int32), spec((R,), jnp.bool_),
        spec((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    big = f"f32[{R},{plan.dim_padded}]"
    for line in text.splitlines():
        if (big in line and re.match(r"\s*(ROOT )?%\S+ = ", line)
                and "custom-call(" not in line):
            assert " parameter(" in line, line
    assert f"f32[{B},{plan.dim_padded}]" not in text
    _no_partials_leave_the_kernel(text, plan)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# -- the forward alone: a PS worker's eval over its resident test rows -------
@pytest.mark.parametrize("rows,dim", [(16, 1000), (24, 16384 + 64), (8, 300)])
def test_the_forward_over_padded_rows_is_the_models_logits(rows, dim):
    w, X, _y, _mask = _problem(rows, dim, seed=3)
    plan = panel_plan(rows, dim)
    z = pallas_lr.lr_logits_rows(w, pad_columns(X, plan), plan)
    want = np.asarray(X, np.float64) @ np.asarray(w, np.float64)
    assert z.shape == (rows,) and z.dtype == jnp.float32
    assert _rel(z, want) <= 1e-6
    model = BinaryLR(dim, feature_scale=0.5)
    assert _rel(model.logits_panels(w, pad_columns(X, plan), plan),
                0.5 * want) <= 1e-6


def test_the_forward_refuses_a_matrix_that_was_not_padded():
    plan = panel_plan(16, 1000)
    w, X, _y, _mask = _problem(16, 1000)
    with pytest.raises(ValueError, match="pad_columns"):
        pallas_lr.lr_logits_rows(w, X, plan)


def _readers_of_the_parameter(entry, shape):
    """The lines of an entry computation that read its parameter of
    ``shape`` (the resident rows)."""
    held = re.search(rf"%(\S+) = {re.escape(shape)}\S* parameter\(", entry)
    return [ln for ln in entry.splitlines()
            if re.search(rf"%{re.escape(held.group(1))}\b", ln)
            and " parameter(" not in ln]


def test_the_eval_compiles_for_a_v5e_as_one_read_of_the_resident_rows(chips):
    """The eval program at the cell's size (256 x 1,000,000 float32 test
    rows held row-major and padded): the logits are one fusion over the
    resident matrix as it lies (no copy, transpose or convert of it, no
    second pass for the logloss), and both scalars come off them."""
    rows, dim = 256, 1_000_000
    plan, model = panel_plan(rows, dim), BinaryLR(dim)

    def ps_eval(w, Xp, y, mask):
        return model.eval_from_logits(model.logits_panels(w, Xp, plan), y,
                                      mask)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chips[0])

    compiled = jax.jit(ps_eval).lower(
        spec((dim,), jnp.float32), spec((rows, plan.dim_padded), jnp.float32),
        spec((rows,), jnp.int32), spec((rows,), jnp.bool_)).compile()
    big = f"f32[{rows},{plan.dim_padded}]"
    entry = compiled.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    readers = _readers_of_the_parameter(entry, big)
    assert len(readers) == 1 and " fusion(" in readers[0], readers
    # and nothing of the entry computation produces a matrix of that size
    assert len(re.findall(rf"= {re.escape(big)}", entry)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def test_the_softmax_step_compiles_for_a_v5e_flat_in_and_flat_out(chips):
    """``jit_ps_grad_step`` at the multiclass cell's size (3,968 x 62,061
    float32 rows, 20 classes), as ``ps_trainer`` builds it: operand 0 and
    the result are the rank-1 ``f32[1241220]`` the wire carries, a
    straight copy over the host link, and the model's shape is the
    compiler's to restore; the resident shard is read by the two products
    as it lies, with no copy of it."""
    from distlr_tpu.train import ps_trainer

    rows, dim, classes = 3968, 62061, 20
    model = SoftmaxRegression(dim, classes, compute_dtype="float32")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chips[0])

    compiled = ps_trainer._compiled_fns(model, 0.0, False).lower(
        spec((dim * classes,), jnp.float32), spec((rows, dim), jnp.float32),
        spec((rows,), jnp.int32), spec((rows,), jnp.float32)).compile()
    entry = compiled.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    flat = f"f32[{dim * classes}]"
    assert re.search(rf"%\S+ = {re.escape(flat)}\S* parameter\(0\)", entry)
    assert re.search(rf"ROOT %\S+ = {re.escape(flat)}\S* reshape\(", entry)
    big = f"f32[{rows},{dim}]"
    readers = _readers_of_the_parameter(entry, big)
    assert len(readers) == 2 and all(" fusion(" in ln for ln in readers)
    assert len(re.findall(rf"= {re.escape(big)}", entry)) == 1


def test_the_one_read_softmax_step_compiles_for_a_v5e_at_the_cells_size(chips):
    """``jit_ps_grad_step`` with the softmax kernel's plan, as a worker
    of the multiclass cell runs it: Mosaic takes the kernel at
    3,968 x 62,208 float32 under the plan's own count of VMEM; XLA hands
    it the resident row-major shard as it lies (no copy, transpose or
    reshape of the operand); operand 0 and the result stay the wire's
    flat ``f32[1241220]``; and the parts of the weights are rounded by
    ``reduce-precision``, which XLA's simplifier leaves in (a conversion
    to bfloat16 and back it removes, and with it two of the three
    parts)."""
    from distlr_tpu.ops.pallas_softmax import softmax_panel_plan
    from distlr_tpu.train import ps_trainer

    rows, dim, classes = 3968, 62061, 20
    model = SoftmaxRegression(dim, classes, compute_dtype="float32")
    plan = softmax_panel_plan(rows, dim, classes)
    assert plan.vmem_bytes <= plan.vmem_limit

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chips[0])

    compiled = ps_trainer._compiled_fns(model, 0.0, False).lower(
        spec((dim * classes,), jnp.float32),
        spec((rows, plan.dim_padded), jnp.float32),
        spec((rows,), jnp.int32), spec((rows,), jnp.float32),
        panels=plan).compile()
    text = compiled.as_text()
    entry = text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    flat = f"f32[{dim * classes}]"
    assert re.search(rf"%\S+ = {re.escape(flat)}\S* parameter\(0\)", entry)
    assert re.search(rf"ROOT %\S+ = {re.escape(flat)}\S* reshape\(", entry)
    big = f"f32[{rows},{plan.dim_padded}]"
    readers = _readers_of_the_parameter(entry, big)
    assert len(readers) == 1 and "tpu_custom_call" in readers[0], readers
    assert len(re.findall(rf"= {re.escape(big)}", entry)) == 1
    assert len(re.findall(r" reduce-precision\(", text)) >= 2
    assert "dot(" not in text and "convolution(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("l2_c", [0.0, 0.5], ids=["no-l2", "l2"])
def test_the_keyed_step_compiles_for_a_v5e_at_the_cells_size(chips, l2_c):
    """``jit_ps_keyed_grad_step`` with the lookups' plan, as a worker of
    the keyed cell runs it (16,384 x 39 entries a window in 4,992 lines,
    90,112 keys, three of 240 windows resident): Mosaic takes the kernel
    under the plan's own count of VMEM; the resident entries go to it as
    they lie (no copy, gather or scatter of them in the program: the two
    scalar loops are gone) and the gradient leaves as the wire's flat
    ``f32[90112]``."""
    from distlr_tpu.ops.pallas_keyed import keyed_plan
    from distlr_tpu.train import ps_trainer

    rows, lines, keys, bits, windows = 16384, 4992, 90112, 14, 3
    plan = keyed_plan(rows, lines, keys, bits)
    assert plan.vmem_bytes <= plan.vmem_limit

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chips[0])

    compiled = ps_trainer._compiled_keyed_fns(l2_c, False).lower(
        spec((keys,), jnp.float32), spec((windows * lines, 128), jnp.int32),
        spec((windows * lines, 128), jnp.float32),
        spec((windows * rows,), jnp.float32),
        spec((windows * rows,), jnp.float32),
        spec((windows, lines // 8), jnp.int32), spec((), jnp.int32),
        rows=rows, row_bits=bits, plan=plan).compile()
    text = compiled.as_text()
    entry = text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    assert "tpu_custom_call" in text
    assert not re.search(r" (gather|scatter)\(", text)
    for big in (f"s32[{windows * lines},128]", f"f32[{windows * lines},128]"):
        readers = _readers_of_the_parameter(entry, big)
        assert len(readers) == 1 and "custom-call(" in readers[0], readers
    assert re.search(rf"ROOT %\S+ = f32\[{keys}\]", entry)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20
