"""PS worker round, a worker to a chip, seen from the device: the part
of a worker's ``compute`` annotation that its own run of the gradient
program ON ITS OWN CHIP'S PLANE does not cover: the dispatch before the
run and the wake-up after it, averaged over the workers and the traced
rounds, in milliseconds.  No other worker's program is on that chip, so
this is what a launch costs with no queue; on one chip
(``bsp_launch_wait_ms``) it is mostly the queue.

Read as two durations (the mark's less the run's), each on its own
clock, and not from the mark's start to the run's as the one-chip
readers do: on the four-chip host the device planes' clock leads the
host's annotations by 1.2 to 2.0 ms (PERF.md section 7), which a
start-to-start reading carries whole (it read -1.2 ms).  The driver
reads each annotation's ``rank`` and names each rank's plane; nothing
where the run has neither."""

from chipbench.drivers.ps_bsp_epochs_chips import own_pairs


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("plane_of_rank"):
        return None
    waits = [(m[1] - m[0]) - (o[1] - o[0])
             for pairs in own_pairs(tr).values() for m, o in pairs]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
