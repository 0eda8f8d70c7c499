"""PS server, a push's life phase by phase: milliseconds from a push's
header read to its keys and values read and decoded, a push, over all
servers: kStats ``recv_seconds`` over ``total_pushes``: the request's
bytes through the socket, as the server sees them.

The servers' counters reach a reader through the process's registry:
every kStats read mirrors its reply as ``distlr_ps_server_stat{rank,
stat}``, so this reads the gauges as the driver's last read left them:
THE JOB'S TOTALS up to the window's end (set-up's 76 rounds are about 3%
of a window's 2,400), not the window's rise; a ``benchmark`` PR that
hands the readers the two kStats dictionaries whole makes it the rise
(ROADMAP S6 a).  Nothing where the registry holds no such counter (a
program whose servers do not count it, or whose reads do not mirror)."""

from distlr_tpu.obs import registry

FAMILY = "distlr_ps_server_stat"


def stat_sum(stat):
    """The counter summed over the server ranks the registry has it for,
    or nothing where it has it for none."""
    family = registry.get_registry().get(FAMILY)
    if family is None:
        return None
    values = [series.value for labels, series in family.children()
              if labels[1] == stat]
    return sum(values) if values else None


def ms_a(stat, over):
    """Milliseconds of the seconds ``stat`` for each of ``over``, both
    summed over the servers; nothing where either is missing or the
    servers counted none of ``over``."""
    seconds, n = stat_sum(stat), stat_sum(over)
    if seconds is None or not n:
        return None
    return 1e3 * seconds / n


def read(run):
    return ms_a("recv_seconds", "total_pushes") if run.get("ps") else None
