from distlr_tpu.parallel.mesh import (  # noqa: F401
    make_mesh,
    batch_sharding,
    replicated_sharding,
    feature_sharding,
)
from distlr_tpu.parallel.data_parallel import make_sync_train_step, make_eval_step  # noqa: F401
