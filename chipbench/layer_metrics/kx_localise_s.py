"""Loader, the keyed job's: seconds inside the program's ``localise``
spans, one a worker: every window's sorted unique keys and each entry's
place among them, worked out once at load.  All of it set-up, read from
the registry as ``load_s`` is.  Nothing where the run carries no such
side or the program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("localise") if run.get("kx") else None
