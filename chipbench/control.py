"""Read what `correct` compares, for the program and for its control.

    python3 -m chipbench.control --workload <name> --seeds 1,2,3 [--controls 3]

For each seed, in one process: the cell's trainer through its first
steps at the cell's own size, the reference through the same steps, and
(for the first ``--controls`` seeds) the control in the program's place:
the nearest precision below the one the configuration states, as its
file says under ``control``: the reference computed in that
``precision``, or the ``program`` with a lower-precision path of its own
switched on.  Prints every
number compared for both, then the largest a sound run gave and the
smallest the control gave: the two readings a limit is set from
(PERF.md, "How correct is decided").  Needs the chip at the real size;
``--rehearse`` runs the tiny sizes anywhere.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from chipbench import manifest
from chipbench import run as harness
from chipbench.drivers import train_stream as ts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = ts.effective_config(cell, args.rehearse)
    prog, family, control = conf["program"], conf["family"], conf["control"]
    keep = int(cell.traffic["checked_steps"])
    lr, l2 = float(prog["learning_rate"]), float(prog["l2_c"])
    say = harness.Context.say
    sound: dict[str, list] = {}
    broken: dict[str, list] = {}
    limits: dict[str, float] = {}

    def note(into, tag, seed, rows):
        for r in rows:
            into.setdefault(r["name"], []).append(r["value"])
            limits[r["name"]] = r["limit"]
        say(f"{tag} seed={seed} " + " ".join(
            f"{r['name']}={r['value']:.4g}" for r in rows))

    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        p = ts.prepare(conf, cell.chips, seed, say)
        got = ts.first_steps(p, keep)
        p.trainer = None
        gc.collect()
        ref = ts.reference_steps(p, family, keep, lr, l2)
        note(sound, "program", seed, ts.compare_runs(p, got, ref, lr, conf["limits"]))
        if n < args.controls:
            if "program" in control:  # the program's own lower path
                q = ts.prepare(conf, cell.chips, seed, say,
                               program_over=control["program"])
                low = ts.first_steps(q, keep)
                del q
                gc.collect()
            else:
                low = ts.reference_steps(p, family, keep, lr, l2,
                                         precision=control["precision"])
            note(broken, "control", seed,
                 ts.compare_runs(p, low, ref, lr, conf["limits"]))
        del p, got, ref
        gc.collect()
    summary = {name: {"sound_max": max(vals),
                      "control_min": min(broken.get(name, [float("nan")])),
                      "limit": limits[name]}
               for name, vals in sound.items()}
    print("CONTROL " + json.dumps({"cell": cell.name, "seeds": args.seeds,
                                   "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
