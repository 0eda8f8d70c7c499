"""Loader: seconds inside the program's spans that put a file's parsed
rows into the layout its model family takes, one a file: ``load_densify``
(a float32 ``(N, D)`` matrix) in a dense family, ``load_coo`` (padded
COO) in a sparse one.  All of it set-up and a child of ``load_data``,
read from the registry as ``load_s`` is.  Nothing where the program
records neither span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    dense = phase_seconds("load_densify")
    return dense if dense is not None else phase_seconds("load_coo")
