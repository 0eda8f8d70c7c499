"""The six workload shapes ROADMAP's north star lists, each trained at a
rehearsal size through its normal entry point (``Trainer.fit`` on the
mesh, ``run_ps_local`` over the native PS): the loop's ``StepTimer``
reports a rate, the test logloss ends below its start, every accuracy
lies in [0, 1].  One judge for both planes, the sync trainer's eval step,
so a PS run's weights are scored by code the PS worker does not share.
"""

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.hashing import write_ctr_shards, write_raw_ctr_shards
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.registry import family_total, get_registry
from distlr_tpu.train import Trainer
from distlr_tpu.train.ps_trainer import run_ps_local


def _synthetic(n, d, parts, **kw):
    return lambda tmp: write_synthetic_shards(tmp, n, d, parts, seed=42, **kw)


# (id, plane, writer of the data dir, Config keywords)
WORKLOADS = [
    ("dense-binary-sync", "mesh", _synthetic(4000, 123, 1),
     dict(num_feature_dim=123, num_iteration=40, learning_rate=0.5)),
    ("dense-async-native-ps", "ps", _synthetic(4000, 123, 4),
     dict(num_feature_dim=123, num_iteration=5, learning_rate=0.1,
          sync_mode=False, num_workers=4, num_servers=2, batch_size=256)),
    ("hashed-to-dense", "mesh",
     lambda tmp: write_ctr_shards(tmp, 4000, 8, 5000, 512, 1, seed=1),
     dict(num_feature_dim=512, num_iteration=30, learning_rate=1.0)),
    ("sparse", "mesh",
     lambda tmp: write_ctr_shards(tmp, 4000, 21, 5000, 1 << 12, 1, seed=1),
     dict(num_feature_dim=1 << 12, num_iteration=30, learning_rate=0.5,
          model="sparse_lr")),
    ("softmax", "mesh", _synthetic(4096, 784, 1, num_classes=10),
     dict(num_feature_dim=784, num_classes=10, model="softmax",
          num_iteration=30, learning_rate=0.3)),
    ("blocked-ctr-keyed-ps", "ps",
     lambda tmp: write_raw_ctr_shards(tmp, 2000, 21, 50, num_parts=2, seed=3,
                                      num_distinct_tuples=64),
     dict(num_feature_dim=4096, num_iteration=3, learning_rate=0.5,
          model="blocked_lr", block_size=8, sync_mode=False, num_workers=2,
          num_servers=1, batch_size=256, ps_timeout_ms=60_000)),
]


def _timer_totals():
    """Samples and seconds the loops' ``StepTimer``s have counted."""
    seconds = get_registry().get("distlr_train_step_seconds")
    return (family_total("distlr_train_samples_total"),
            sum(c.sum for _, c in seconds.children()) if seconds else 0.0)


@pytest.mark.parametrize(
    "plane,write,kw", [pytest.param(*w[1:], id=w[0]) for w in WORKLOADS])
def test_workload_trains_through_its_entry_point(tmp_path, plane, write, kw):
    data_dir = str(tmp_path)
    write(data_dir)
    cfg = Config(data_dir=data_dir, l2_c=0.0, test_interval=1, **kw)
    accs = []
    if plane == "mesh":
        judge = Trainer(cfg).load_data()
        judge.init_weights()
        start = judge.evaluate_metrics()
        judge.fit(eval_fn=lambda _epoch, acc: accs.append(acc))
        rate = judge.timer.samples_per_sec
    else:
        judge = Trainer(cfg).load_data(test_only=True)
        w0 = judge.init_weights()
        start = judge.evaluate_metrics()
        n0, s0 = _timer_totals()
        ws = run_ps_local(cfg, eval_fn=lambda _epoch, acc: accs.append(acc))
        n1, s1 = _timer_totals()
        rate = (n1 - n0) / (s1 - s0)
        judge.weights = judge._shard_weights(
            np.asarray(ws[0], np.float32).reshape(w0.shape))
    end = judge.evaluate_metrics()

    assert rate > 0
    assert len(accs) == cfg.num_iteration
    assert all(0.0 <= a <= 1.0 for a in accs)
    assert 0.0 <= end["accuracy"] <= 1.0
    assert end["logloss"] < start["logloss"], (start, end)
    # the loop's last eval and the judge agree (PS peers may push after it)
    assert accs[-1] == pytest.approx(
        end["accuracy"], abs=0.05 if plane == "ps" else 1e-6)
