"""Loader: seconds inside the program's ``load_parse`` spans, one a file: the
read and the tokenizer (``parse_libsvm_file`` to CSR arrays).
All of it set-up and a child of ``load_data``, read from the registry as
``load_s`` is.  Nothing where the program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("load_parse")
