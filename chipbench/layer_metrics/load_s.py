"""Loader: seconds inside the program's ``load_data`` span, all of it
set-up.  The driver resets the tracer before the window, so this reads
the sum of the ``distlr_phase_seconds{phase="load_data"}`` series from
the process's metrics registry, which keeps set-up's spans.  Nothing
where the program records no such span."""

from distlr_tpu.obs import registry


def phase_seconds(phase):
    """The seconds the process has spent in spans of this name, or
    nothing where it has recorded none."""
    family = registry.get_registry().get("distlr_phase_seconds")
    if family is None:
        return None
    for labels, series in family.children():
        if labels == (phase,) and series.count:
            return series.sum
    return None


def read(run):
    return phase_seconds("load_data")
