"""Experiment: from what size a value payload is cheaper in the mapping.

A same-host frame's float32 values cross in a mapping the client and the
server share where they are ``kMappedMinBytes`` or more
(``distlr_tpu/ps/native/kv_protocol.h`` "values in a mapping"); smaller
ones stay on the socket.  This reads where that constant belongs: fused
push-pulls of n values a server, against a native server that advertises
the mapping and against one that does not (``compress=False``: the
legacy hello, so every frame stays on the socket; the same binary, the
same host, the same seconds), one async worker and four lock-step ones.
The carrier each leg ran is read back from kStats ``mapped_frames``.

Sizes under the constant in force ride the socket on both legs: to read
below it, lower ``kMappedMinBytes`` (and its mirror in ``ps/wire.py``)
in the working tree for the call.  No accelerator is used and none is
needed: the exchange is host code; run it on the chip's host all the
same, since that host's loopback and cores are what the cells pay for.

    python benchmarks/exp_mapped_payload.py [--reps 300]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distlr_tpu.ps import KVWorker, ServerGroup, wire  # noqa: E402

SIZES_KIB = (4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048)


def _leg(n: int, workers: int, advertise: bool, reps: int):
    """Median and p90 ms of a fused push-pull of ``n`` values to one
    server, ``workers`` at once (lock-step where more than one), and the
    share of its value-carrying frames kStats saw in the mapping."""
    sync = workers > 1
    times = [[] for _ in range(workers)]
    with ServerGroup(1, workers, n, sync=sync, learning_rate=0.01,
                     compress=advertise) as sg:
        kvs = [KVWorker(sg.direct_hosts, n, client_id=r, timeout_ms=60_000,
                        sync_group=sync) for r in range(workers)]
        kvs[0].push_init(np.zeros(n, np.float32))
        g = np.full(n, 1e-3, np.float32)
        before = kvs[0].stats(0)

        def loop(r):
            kv = kvs[r]
            for i in range(reps + 20):
                t0 = time.perf_counter()
                kv.push_pull(g)
                if i >= 20:
                    times[r].append(time.perf_counter() - t0)

        threads = [threading.Thread(target=loop, args=(r,))
                   for r in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        after = kvs[0].stats(0)
        for kv in kvs:
            kv.close()
    ops = (after["total_pushes"] + after["total_pulls"]
           - before["total_pushes"] - before["total_pulls"])
    mapped = after.get("mapped_frames", 0) - before.get("mapped_frames", 0)
    flat = sorted(t for ts in times for t in ts)
    return (1e3 * statistics.median(flat), 1e3 * flat[int(0.9 * len(flat))],
            mapped / ops)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=300)
    args = ap.parse_args()
    print(f"kMappedMinBytes in force: {wire.MAPPED_MIN_BYTES}")
    rows = []
    for workers in (1, 4):
        for kib in SIZES_KIB:
            n = kib * 1024 // 4
            off = _leg(n, workers, False, args.reps)
            on = _leg(n, workers, True, args.reps)
            row = {"workers": workers, "payload_kib": kib,
                   "socket_ms_p50": off[0], "socket_ms_p90": off[1],
                   "mapping_ms_p50": on[0], "mapping_ms_p90": on[1],
                   "mapped_share": on[2], "socket_leg_mapped_share": off[2]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    print("RESULT " + json.dumps(rows))


if __name__ == "__main__":
    main()
