"""Loader: seconds inside the program's ``load_cast`` span, recorded only for a
quantized ``feature_dtype``: the float32 splits cast (``_quantize_features``).
All of it set-up and a child of ``load_data``, read from the registry as
``load_s`` is.  Nothing where the program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("load_cast")
