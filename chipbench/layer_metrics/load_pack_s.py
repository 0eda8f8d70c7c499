"""Loader: seconds inside the program's ``load_pack`` spans, one a split: the
parts redistributed onto the mesh's slots and copied into the padded
``(W, n_pad, ...)`` arrays.
All of it set-up and a child of ``load_data``, read from the registry as
``load_s`` is.  Nothing where the program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("load_pack")
