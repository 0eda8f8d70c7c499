"""The float32 reference against the product's model at small sizes on
the CPU, the generator's arrays against the product's parser, and the
lower-precision control that the limits must reject."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import datagen, manifest, reference
from chipbench.drivers import train_stream

DIM, ROWS, STEPS = 8192, 512, 3
ROWS_KW = dict(fields="criteo-kaggle", num_buckets=DIM, label_scale=0.5,
               label_bias=-1.0)


def _limits(config_name, rehearsal=False):
    with open(os.path.join(manifest.HERE, "configs", f"{config_name}.json")) as f:
        conf = json.load(f)
    return {**conf["limits"],
            **(conf["rehearsal"].get("limits", {}) if rehearsal else {})}


@pytest.fixture(scope="module")
def rows():
    train = datagen.make_rows(3, "train", ROWS * STEPS, **ROWS_KW)
    test = datagen.make_rows(3, "test", 256, **ROWS_KW)
    batches = [tuple(a[k * ROWS:(k + 1) * ROWS] for a in train)
               for k in range(STEPS)]
    return batches, test, train_stream.initial_weights(3, DIM)


def _product_steps(model_name, batches, w0, lr, **cfg_kw):
    """The product's model functions, driven step by step."""
    from distlr_tpu.config import Config
    from distlr_tpu.models import get_model

    cfg = Config(model=model_name, num_feature_dim=DIM, l2_c=0.0,
                 learning_rate=lr, **cfg_kw)
    model = get_model(cfg)
    stored = {"bfloat16": jnp.bfloat16, "int8_dot": jnp.int8}.get(
        cfg.feature_dtype)
    w, losses, weights = jnp.asarray(w0), [], []
    for cols, vals, y in batches:
        if model_name == "sparse_lr":
            feats = (jnp.asarray(cols), jnp.asarray(vals))
        else:
            X = np.zeros((len(y), DIM), np.float32)
            np.add.at(X, (np.arange(len(y))[:, None], cols), vals)
            feats = (jnp.asarray(X).astype(stored),)
        batch = (*feats, jnp.asarray(y), jnp.ones(len(y), jnp.float32))
        losses.append(float(model.loss(w, batch, cfg)))
        w = w - lr * model.grad(w, batch, cfg)
        weights.append(np.asarray(w))
    return losses, weights


def _compare(rows, family, prog_losses, prog_w, limits, lr, prog_test_ll=None):
    batches, test, w0 = rows
    ref_losses, ref_w = reference.follow_steps(family, w0, batches, lr=lr, l2=0.0)
    ref_ll = reference.logloss(family, ref_w[-1], *test)
    return train_stream.compare(
        w0, prog_losses, prog_w, ref_losses, ref_w,
        ref_ll if prog_test_ll is None else prog_test_ll, ref_ll, lr, limits)


def test_sparse_reference_agrees_with_the_product_model(rows):
    losses, weights = _product_steps("sparse_lr", rows[0], rows[2], 1.0)
    out = _compare(rows, "sparse", losses, weights,
                   _limits("criteo-sparse-1m"), 1.0)
    assert all(r["ok"] for r in out), out


def test_dense_reference_agrees_with_the_product_model_in_bf16(rows):
    losses, weights = _product_steps(
        "binary_lr", rows[0], rows[2], 0.5, feature_dtype="bfloat16")
    # the CPU rounds to bfloat16 where the TPU does not: rehearsal limits
    out = _compare(rows, "dense", losses, weights,
                   _limits("criteo-dense-1m", rehearsal=True), 0.5)
    assert all(r["ok"] for r in out), out


def test_dense_and_sparse_references_are_the_same_mathematics(rows):
    batches, test, w0 = rows
    ls, ws = reference.follow_steps("sparse", w0, batches, lr=0.5, l2=1e-3)
    ld, wd = reference.follow_steps("dense", w0, batches, lr=0.5, l2=1e-3)
    np.testing.assert_allclose(ls, ld, rtol=1e-6)
    np.testing.assert_allclose(ws[-1], wd[-1], rtol=1e-5, atol=1e-7)
    assert reference.logloss("sparse", ws[-1], *test) == pytest.approx(
        reference.logloss("dense", wd[-1], *test), rel=1e-6)


def test_sparse_reference_is_the_densified_mathematics_in_float64(rows):
    """The gather and the segment sum against the (rows, D) matrix
    written out in NumPy float64."""
    batches, test, w0 = rows
    lr, l2 = 0.5, 1e-3
    losses, weights = reference.follow_steps("sparse", w0, batches, lr=lr, l2=l2)
    w = w0.astype(np.float64)
    for k, (cols, vals, y) in enumerate(batches):
        X = np.zeros((len(y), DIM))
        np.add.at(X, (np.arange(len(y))[:, None], cols), vals)
        z = X @ w
        loss = np.mean(np.logaddexp(0.0, z) - y * z) + 0.5 * l2 * w @ w
        assert losses[k] == pytest.approx(loss, rel=1e-6)
        w = w - lr * (X.T @ (1.0 / (1.0 + np.exp(-z)) - y) / len(y) + l2 * w)
        np.testing.assert_allclose(weights[k], w, rtol=1e-4, atol=1e-7)
    cols, vals, y = test
    z = (w[cols] * vals).sum(axis=1)
    assert reference.logloss("sparse", weights[-1], *test) == pytest.approx(
        np.mean(np.logaddexp(0.0, z) - y * z), rel=1e-6)


def test_generator_arrays_are_what_the_products_parser_returns(tmp_path):
    """The driver hands both splits to the trainer as arrays: the same
    rows written as reference-layout text and read by the program's own
    loader give these arrays back."""
    from distlr_tpu.train.trainer import GlobalShardedData

    cols, vals, y = datagen.make_rows(5, "train", 1024, **ROWS_KW)
    datagen.write_libsvm(str(tmp_path / "train" / "part-001"), cols, vals, y)
    data = GlobalShardedData.from_data_dir(str(tmp_path), "train", 1, DIM,
                                           sparse=True)
    got_cols, got_vals = (leaf[0] for leaf in data._feats)
    width = got_cols.shape[1]
    assert width <= cols.shape[1] and not vals[:, width:].any()
    np.testing.assert_array_equal(got_cols, cols[:, :width])
    np.testing.assert_array_equal(got_vals, vals[:, :width])
    np.testing.assert_array_equal(data.y[0], y)


def test_a_family_is_found_by_name_and_an_unknown_one_refused():
    assert callable(reference.family("sparse").step)
    assert callable(reference.family("dense").step)
    with pytest.raises(ValueError, match="families"):
        reference.family("blocked")
    with pytest.raises(ValueError):
        reference.family("../sparse")
    with pytest.raises(ValueError):
        reference.follow_steps("sparse", np.zeros(4, np.float32), [(
            np.zeros((1, 1), np.int32), np.ones((1, 1), np.float32),
            np.zeros(1, np.int32))], lr=1.0, l2=0.0, precision="int4")


def test_reference_gradient_is_the_gradient_of_its_loss(rows):
    import jax

    (cols, vals, y), w0 = rows[0][0], rows[2]

    def loss(w):
        z = jnp.sum(w[cols] * vals, axis=-1)
        return jnp.mean(jax.nn.softplus(z) - y * z)

    _, ws = reference.follow_steps("sparse", w0, [rows[0][0]], lr=1.0, l2=0.0)
    np.testing.assert_allclose(w0 - ws[0], jax.grad(loss)(jnp.asarray(w0)),
                               rtol=1e-4, atol=1e-8)


def test_sparse_limits_reject_the_bfloat16_control(rows):
    """bfloat16 weights and gathers in the program's place: the nearest
    precision below the float32 the configuration states."""
    batches, test, w0 = rows
    losses, weights = reference.follow_steps(
        "sparse", w0, batches, lr=1.0, l2=0.0, precision="bfloat16")
    ll = reference.logloss("sparse", weights[-1], *test, precision="bfloat16")
    out = _compare(rows, "sparse", losses, weights,
                   _limits("criteo-sparse-1m"), 1.0, prog_test_ll=ll)
    assert not all(r["ok"] for r in out), out


def test_dense_limits_reject_the_int8_control(rows):
    """The program's own int8_dot path in the bfloat16 path's place."""
    losses, weights = _product_steps(
        "binary_lr", rows[0], rows[2], 0.5, feature_dtype="int8_dot")
    out = _compare(rows, "dense", losses, weights,
                   _limits("criteo-dense-1m"), 0.5)
    assert not all(r["ok"] for r in out), out


def test_unchanged_state_is_caught_whatever_the_precision(rows):
    batches, test, w0 = rows
    ref_losses, _ = reference.follow_steps("sparse", w0, batches, lr=1.0, l2=0.0)
    out = _compare(rows, "sparse", ref_losses, [w0, w0, w0],
                   _limits("criteo-sparse-1m"), 1.0)
    bad = {r["name"] for r in out if not r["ok"]}
    assert "update_missing" in bad and "grad1_norm_rel_gap" in bad
