"""Run one cell once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Fails without printing a result unless JAX finds a TPU with at least the
cell's chips; there is no fallback.  Prints what it does as it goes and,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``.

``--rehearse`` drives the same code anywhere at the tiny sizes the
configuration's file gives under ``rehearsal``; it prints ``REHEARSAL``
and never a result line.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from chipbench import manifest, trace_reduce  # noqa: E402
from chipbench.compiles import Compiles  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


@dataclasses.dataclass
class Context:
    cell: manifest.Cell
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    devices: list
    compiles: Compiles
    t_start: float

    @staticmethod
    def say(text: str) -> None:
        print(f"chipbench {text}", flush=True)


def place_compile_cache() -> str:
    """The persistent cache: where the environment says, else at a fixed
    path inside the checkout (the path is part of the cache's key).
    Every program is kept, however quickly it compiled, so that a second
    run finds them all."""
    import jax

    placed = os.environ.get(CACHE_ENV)
    if not placed:
        placed = os.path.join(manifest.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def take_devices(chips: int, rehearsal: bool) -> list:
    import jax

    devices = jax.devices()
    if rehearsal:
        if len(devices) < chips:
            raise SystemExit(f"rehearsal needs {chips} devices, "
                             f"JAX has {len(devices)}")
        return devices
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"chipbench measures the TPU: the cell needs {chips} chip(s), "
            f"JAX found platform={devices[0].platform} "
            f"devices={len(devices)}")
    return devices


def layer_metrics(cell: manifest.Cell, run: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.layer_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(run: dict) -> dict | None:
    tr = run.get("trace")
    if not tr:
        return None
    return {
        "device_ops": trace_reduce.top_ops(tr["xtrace"], tr["window"], 8),
        "idle_gaps": trace_reduce.idle_gaps(
            tr["xtrace"], tr["host_spans"], tr["clock_offset"],
            tr["window"])[:10],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform, prints REHEARSAL")
    args = ap.parse_args(argv)

    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, args.workload)
    if not args.rehearse:
        place_compile_cache()
    devices = take_devices(cell.chips, args.rehearse)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), rehearsal=args.rehearse,
                  devices=devices, compiles=Compiles(), t_start=_T_START)
    ctx.say(f"cell={cell.name} config={cell.entry['config']} "
            f"traffic={cell.entry['traffic']} chips={cell.chips} "
            f"seed={args.seed} seconds={args.seconds} trace={args.trace} "
            f"platform={devices[0].platform} devices={len(devices)}")
    res = cell.driver.run(ctx)

    if args.rehearse:
        print("REHEARSAL " + json.dumps({
            "cell": cell.name, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "compared": res["compared"],
            "layer_metrics": sorted(layer_metrics(cell, res["run"])),
        }), flush=True)
        return 0

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    if args.trace:
        tr = res["run"]["trace"]
        b = trace_reduce.busy(tr["xtrace"], tr["window"])
        device["busy_s"], device["window_s"] = b["busy_s"], b["window_s"]
        metrics = layer_metrics(cell, res["run"])
    else:
        metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = breakdown(res["run"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
