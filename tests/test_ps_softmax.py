"""The multiclass PS job: a softmax ``PSWorker`` over loopback servers with
its shard resident, held against the benchmark's plain reference; the
precision its float32 products state, read off the lowered programs (the
guard a CPU can give for a fault only a TPU shows: there a float32 ``dot``
that states nothing is one bfloat16 pass); and the form its parameters
cross the host link in: the flat vector the wire carries, the model's
shape restored inside the jitted programs alone."""

import os
import re
import threading
import types

import jax
import numpy as np
import pytest

from chipbench import newsgen
from chipbench.families import dense_ps_softmax as reference
from distlr_tpu import Config
from distlr_tpu.data.sharding import part_name
from distlr_tpu.models.linear import BinaryLR, SoftmaxRegression
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker
from distlr_tpu.train import ps_trainer

D, K, ROWS, WORKERS = 2048, 20, 64, 2


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """Two softmax workers against two loopback servers, their shards
    loaded (resident: 2048 x 20 x 64 is over the numpy threshold) and the
    group seeded, and the rows as the generator made them."""
    tmp = str(tmp_path_factory.mktemp("ps-softmax"))
    train = newsgen.make_rows(41, "train", WORKERS * ROWS, vocab=D, classes=K,
                              nnz=80)
    test = newsgen.make_rows(41, "test", ROWS, vocab=D, classes=K, nnz=80)
    shards = [tuple(a[r * ROWS:(r + 1) * ROWS] for a in train)
              for r in range(WORKERS)]
    for r, shard in enumerate(shards):
        newsgen.write_libsvm(os.path.join(tmp, "train", part_name(r)), *shard)
    newsgen.write_libsvm(os.path.join(tmp, "test", part_name(0)), *test)
    cfg = Config(data_dir=tmp, test_interval=0, model="softmax", num_classes=K,
                 num_feature_dim=D, feature_dtype="float32",
                 compute_dtype="float32", sync_mode=False, num_workers=WORKERS,
                 num_servers=2, batch_size=-1, learning_rate=0.2, l2_c=0.0)
    group = ps_trainer.server_group(cfg).start()
    workers, probe = [], None
    try:
        probe = KVWorker(group.hosts, D * K, client_id=0xFC00)
        w0 = (np.random.default_rng(5).standard_normal(D * K) * 0.05).astype(
            np.float32)
        probe.wait(probe.push_init(w0))
        workers = [ps_trainer.PSWorker(cfg, r, group.hosts)
                   for r in range(WORKERS)]
        for w in workers:
            w.load_data()
        yield {"cfg": cfg, "workers": workers, "probe": probe, "w0": w0,
               "shards": shards, "test": test, "hosts": group.hosts}
    finally:
        for w in workers:
            w.close(wait=False)
        if probe is not None:
            probe.close()
        group.stop()


def _rel(got, want):
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def test_a_resident_workers_gradient_is_the_references(job):
    w = job["w0"]
    for worker, shard in zip(job["workers"], job["shards"]):
        assert worker._resident is not None and not worker._windowed
        got = worker.grad_step(w, worker._resident)
        want = np.asarray(reference.gradient(w, *shard, K))
        assert got.shape == (D * K,) and got.dtype == np.float32
        assert _rel(got, want) < 2e-6
        # every class column is computed
        assert np.any(got.reshape(D, K) != 0, axis=0).all()


def test_a_streamed_batch_gives_the_resident_shards_gradient(job):
    worker = job["workers"][0]
    train = worker._train
    train.reset()
    streamed = worker.grad_step(job["w0"], train.next_batch())
    resident = worker.grad_step(job["w0"], worker._resident)
    assert _rel(streamed, resident) < 1e-6


def test_the_eval_is_the_references_off_one_forward_pass(job):
    acc, ll = job["workers"][0].evaluate(job["w0"])
    want_ll, want_acc = reference.evaluate(job["w0"], *job["test"], K)
    assert ll == pytest.approx(want_ll, rel=1e-6)
    assert acc == pytest.approx(want_acc)


def test_the_series_say_twenty_classes_and_the_default_layout(job):
    reg = get_registry()
    classes = dict(reg.get("distlr_ps_step_classes").children())
    layout = dict(reg.get("distlr_ps_resident_layout").children())
    for r in map(str, range(WORKERS)):
        assert classes[(r,)].value == K
        assert layout[(r, "default")].value == 1
        assert layout[(r, "row_major")].value == 0
    resident = dict(reg.get("distlr_ps_resident_bytes").children())
    assert resident[("0",)].value >= ROWS * D * 4


def test_a_fit_moves_the_whole_class_axis_over_the_wire(job):
    """Two rounds a worker through the product's own loop: the servers'
    weights move in every class column by what the reference says the
    first pushes were, and each push carries 20 float32 columns."""
    workers, probe = job["workers"], job["probe"]
    sent = get_registry().get("distlr_ps_client_bytes_total")

    def pushed():
        return sum(c.value for labels, c in sent.children()
                   if labels == ("push_pull", "sent"))

    before, bytes_before = probe.pull(), pushed()
    errors = []

    def one(w):
        try:
            w.start()
            w.fit(epochs=2)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=one, args=(w,)) for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    after = probe.pull()
    moved = (after - before).reshape(D, K)
    assert np.any(moved != 0, axis=0).all()
    assert pushed() - bytes_before >= WORKERS * 2 * D * K * 4
    # the first round's gradients are at the seeded weights, whatever the
    # order the pushes landed in; the second round's move it a little more
    first = sum(np.asarray(reference.gradient(before, *s, K))
                for s in job["shards"])
    assert _rel(moved.reshape(-1), -0.2 * 2 * first) < 0.2


# -- the precision the lowered programs state ------------------------------
def _shapes(model, rows=16):
    """The programs' operands: the parameters flat, as the wire has them."""
    w = jax.ShapeDtypeStruct((int(np.prod(model.param_shape)),), np.float32)
    X = jax.ShapeDtypeStruct((rows, model.num_features), np.float32)
    y = jax.ShapeDtypeStruct((rows,), np.int32)
    mask = jax.ShapeDtypeStruct((rows,), np.bool_)
    return w, X, y, mask


def _lowered(model, rows=16):
    shapes = _shapes(model, rows)
    step = ps_trainer._compiled_fns(model, 0.0, False).lower(*shapes)
    ev = ps_trainer._compiled_acc(model).lower(*shapes)
    return step.as_text(), ev.as_text()


def _dots(text):
    return [ln for ln in text.splitlines() if "dot_general" in ln]


@pytest.mark.parametrize("program", ["jit_ps_grad_step", "jit_ps_eval"])
def test_a_float32_softmax_states_highest_on_every_product(program):
    step, ev = _lowered(SoftmaxRegression(96, K, compute_dtype="float32"))
    text = step if program == "jit_ps_grad_step" else ev
    dots = _dots(text)
    assert len(dots) == (2 if program == "jit_ps_grad_step" else 1)
    for ln in dots:
        assert re.search(r"precision = \[HIGHEST, HIGHEST\]", ln), ln
    assert program.removeprefix("jit_") in text


@pytest.mark.parametrize("program", ["jit_ps_grad_step", "jit_ps_eval"])
def test_a_bfloat16_softmax_states_none(program):
    step, ev = _lowered(SoftmaxRegression(96, K, compute_dtype="bfloat16"))
    text = step if program == "jit_ps_grad_step" else ev
    dots = _dots(text)
    assert dots and "HIGHEST" not in text and "HIGH" not in text.replace(
        "HIGHEST", "")
    assert all("bf16" in ln for ln in dots)


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_a_binary_models_programs_state_no_precision(compute_dtype):
    """``BinaryLR``'s one-column products are float32 multiply-reduces on
    the VPU and need none; its programs are what they were."""
    step, ev = _lowered(BinaryLR(96, compute_dtype=compute_dtype))
    for text in (step, ev):
        assert _dots(text)
        assert "HIGHEST" not in text and "precision = [HIGH" not in text


# -- the parameters flat, the model's shape inside the program -------------
GCFG = types.SimpleNamespace(l2_c=0.01, l2_scale_by_batch=True)
FLAT_ROWS, FIRST, WINDOW = 48, 16, 24


def _flat_case(compute_dtype):
    model = SoftmaxRegression(96, K, compute_dtype=compute_dtype)
    rng = np.random.default_rng(3)
    w = (rng.standard_normal(96 * K) * 0.3).astype(np.float32)
    X = rng.standard_normal((FLAT_ROWS, 96)).astype(np.float32)
    y = rng.integers(0, K, FLAT_ROWS).astype(np.int32)
    mask = (rng.random(FLAT_ROWS) < 0.9).astype(np.float32)
    return model, w, (X, y, mask)


@pytest.mark.parametrize("rows", ["whole", "window"])
@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_flat_step_is_the_models_gradient_bit_for_bit(compute_dtype, rows):
    """``SoftmaxRegression.grad`` is called as it is: what the program
    adds is two reshapes, and on the CPU they move no bit."""
    model, w, batch = _flat_case(compute_dtype)
    step = ps_trainer._compiled_fns(model, GCFG.l2_c, GCFG.l2_scale_by_batch)
    if rows == "window":
        got = step(w, *batch, first=np.int32(FIRST), window=WINDOW)
        batch = tuple(a[FIRST:FIRST + WINDOW] for a in batch)
    else:
        got = step(w, *batch)
    want = jax.jit(lambda W, b: model.grad(W, b, GCFG))(
        w.reshape(model.param_shape), batch)
    got = np.asarray(got)
    assert got.shape == (96 * K,) and got.dtype == np.float32
    assert np.array_equal(got, np.asarray(want).reshape(-1))
    assert np.count_nonzero(got)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_flat_eval_is_the_models_forward_bit_for_bit(compute_dtype):
    model, w, batch = _flat_case(compute_dtype)
    got = ps_trainer._compiled_acc(model)(w, *batch)
    want = jax.jit(lambda W, X, y, mask: model.eval_from_logits(
        model.logits(W, X), y, mask))(w.reshape(model.param_shape), *batch)
    assert [float(v) for v in got] == [float(v) for v in want]


def _signature(text):
    """Operand and result types of a lowered module's ``main``."""
    main = re.search(r"func\.func public @main\((.*?)\) -> \((.*?)\) \{",
                     text, re.S)
    operands = re.findall(r"%arg\d+: (tensor<[^>]*>)", main.group(1))
    results = re.findall(r"tensor<[^>]*>", main.group(2))
    return operands, results


@pytest.mark.parametrize("program", ["jit_ps_grad_step", "jit_ps_eval"])
def test_a_softmax_program_takes_its_weights_flat(program):
    """Operand 0 is ``f32[D*K]``, rank 1: what ``device_put`` is handed and
    the device lays out by itself is the wire's vector, a straight copy;
    the step's result is rank 1 too.  The products still state
    ``HIGHEST`` (the case above reads the same text)."""
    step, ev = _lowered(SoftmaxRegression(96, K, compute_dtype="float32"))
    operands, results = _signature(step if program == "jit_ps_grad_step"
                                   else ev)
    assert operands[0] == f"tensor<{96 * K}xf32>"
    if program == "jit_ps_grad_step":
        assert results == [f"tensor<{96 * K}xf32>"]
    else:
        assert results == ["tensor<f32>", "tensor<f32>"]


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("program", ["jit_ps_grad_step", "jit_ps_eval"])
def test_a_binary_models_programs_are_the_models_own(program, compute_dtype):
    """Rank-1 parameters are their own shape: both reshapes trace to
    nothing, and the lowered text is that of ``model.grad`` (``logits``
    and ``eval_from_logits``) jitted directly under the same name."""
    model = BinaryLR(96, compute_dtype=compute_dtype)
    step, ev = _lowered(model)

    def ps_grad_step(w, X, y, mask):
        return model.grad(w, (X, y, mask), types.SimpleNamespace(
            l2_c=0.0, l2_scale_by_batch=False))

    def ps_eval(w, X, y, mask):
        return model.eval_from_logits(model.logits(w, X), y, mask)

    if program == "jit_ps_grad_step":
        assert step == jax.jit(ps_grad_step).lower(*_shapes(model)).as_text()
    else:
        assert ev == jax.jit(ps_eval).lower(*_shapes(model)).as_text()
