#!/usr/bin/env python3
"""The readings a limit is set from, in one process: boot the cell's hub
once, drive a short window at the cell's own load on each of ``--seeds``,
free the hub, then compare each seed's sample with the plain reference (the
lower readings) and put the control in the program's place on the first
``--control-seeds`` of them (the upper readings), and plant the reference
module's ``fault`` in what was served on the same seeds. Not part of a benchmark
run; the builder of a limit runs it on the chip.

    python3 benchmark/readings.py --workload <cell> --seeds 11,12,13 --seconds 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import cells, run  # noqa: E402


def control_only(cell, bench, seeds) -> int:
    """The control's readings need no program: every pool photo of each seed,
    the reference against itself a precision step down."""
    from benchmark import weights
    from benchmark.photos import photo_jpeg, pool_sizes

    if cell.family != "clip":
        raise SystemExit("--control-only needs no served tokens, so it is for image cells")
    run.hub.apply_env(cell.config)
    bench.device = run.device_info(cell.chips, bench.rehearse)
    bench.names = {f: weights.ensure_model_dir(run.CACHE, cell.config["name"], f, m)
                   for f, m in cell.config["models"].items() if f == cell.family}
    sizes = pool_sizes(cell.traffic["photo_pool"])
    for seed in seeds:
        sample = {"jpegs": [photo_jpeg(seed, i, s, cell.traffic["jpeg_quality"], cell.traffic["noise"])
                            for i, s in enumerate(sizes)], "served": None}
        model_dir = os.path.join(run.CACHE, "models", bench.names["clip"])
        for activations in (True, False):
            numbers = cell.reference().compare(sample, cell.config["models"]["clip"], model_dir,
                                               cell.config["precision"]["clip"], True, activations)
            print(json.dumps({"seed": seed, "control": numbers, "activations": activations}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control-only", action="store_true",
                    help="no hub: the control against the reference on each seed's photos (image cells only)")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = cells.Cell(args.workload, rehearse=args.rehearse)
    bench = run.Bench(cell, args.rehearse)
    if args.control_only:
        return control_only(cell, bench, seeds)
    samples = []
    try:
        bench.setup(seeds[0])
        for seed in seeds:
            bench.prepare(seed)
            result = bench.window(args.seconds)
            client = result["client"]
            run.log(f"seed {seed}: attempted {client['attempted']} failed {client['failed']} "
                    f"peak {result['memory_peak_bytes']} errors {client['errors'][:2]}")
            samples.append((seed, bench.sample(result), client["failed"], client["attempted"]))
    finally:
        bench.teardown()
    out = {"workload": cell.name, "device": bench.device, "seconds": args.seconds, "program": {}, "control": {},
           "fault": {}}
    for i, (seed, sample, failed, attempted) in enumerate(samples):
        numbers = bench.compare(sample)
        out["program"][seed] = {**numbers, "failed": failed, "attempted": attempted}
        print(json.dumps({"seed": seed, "program": numbers}), flush=True)
        if i < args.control_seeds:
            numbers = bench.compare(sample, control=True)
            out["control"][seed] = numbers
            print(json.dumps({"seed": seed, "control": numbers}), flush=True)
            numbers = bench.compare(cell.reference().fault(sample))
            out["fault"][seed] = numbers
            print(json.dumps({"seed": seed, "fault": numbers}), flush=True)
    names = [k for k in next(iter(out["program"].values())) if not k.startswith("_") and k not in ("failed", "attempted")]
    out["summary"] = {
        n: {"lower_max_program": max(v[n] for v in out["program"].values()),
            "upper_min_control": min((v[n] for v in out["control"].values()), default=None),
            "min_fault": min((v[n] for v in out["fault"].values()), default=None)}
        for n in names
    }
    print(json.dumps(out["summary"]), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
