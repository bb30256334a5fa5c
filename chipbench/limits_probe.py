"""Read, on the chip and at a cell's own size, what the limits of
``correct`` are set from.

    python3 -m chipbench.limits_probe --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --out chiprun_out/<file>.json

For every seed, in one process: the program's first steps (the same
``Trainer`` the benchmark's runs build) against the plain float32
reference; for the control seeds also the reference computed in fp8, put in
the program's place. Prints the gap of each compared number per seed, the
largest that sound runs gave and the smallest that the control gave. The
benchmark's own runs never run the control. Needs no measured window: the
numbers are read from the first steps alone.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--benchmark", default=None)
    parser.add_argument("--out", default=None)
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--leaves", action="store_true",
                        help="keep every leaf's norms in --out, to look "
                             "for a steadier number to compare")
    parser.add_argument("--control-only", action="store_true",
                        help="the reference and its fp8 control alone, on "
                             "one device whatever the cell's chips: they "
                             "need no mesh")
    args = parser.parse_args(argv)

    from chipbench import cell as cells
    from chipbench import check, run

    cell = cells.Spec(args.benchmark).cell(args.workload)
    if args.control_only:
        import dataclasses
        cell = dataclasses.replace(cell, chips=1)
    devices, _ = run.take_devices(cell, args.rehearse_cpu)

    import horovod_tpu as hvd

    run.place_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    family, config, traffic = cell.family, cell.config, cell.traffic
    rows = []
    hvd.init()
    try:
        mesh = hvd.parallel.data_parallel_mesh(devices)
        for seed in seeds:
            t = time.perf_counter()
            keys = cells.seed_keys(seed, 2)
            row = {"seed": seed, "losses": []}
            if not args.control_only:
                trainer = run.Trainer(cell, mesh, seed)
                program = trainer.first_steps()
                trainer.free()
            reference = family.reference_run(config, traffic, keys,
                                             check.STEPS)
            if not args.control_only:
                row["sound"] = check.compare(program, reference)
                row["losses"].append(program["losses"])
            row["losses"].append(reference["losses"])
            if seed in control_seeds or args.control_only:
                control = family.reference_run(
                    config, traffic, keys, check.STEPS, precision="fp8")
                row["control"] = check.compare(control, reference)
                row["losses"].append(control["losses"])
            row["seconds"] = time.perf_counter() - t
            print(json.dumps(row), flush=True)
            if args.leaves:
                row["reference"] = reference
                if not args.control_only:
                    row["program"] = program
                if "control" in row:
                    row["control_readings"] = control
            rows.append(row)
    finally:
        hvd.shutdown()

    summary = {}
    for number in check.COMPARED:
        sound = [r["sound"][number][0] for r in rows if "sound" in r]
        control = [r["control"][number][0] for r in rows if "control" in r]
        summary[number] = {"sound_largest": max(sound, default=None),
                           "sound_all": sound,
                           "control_smallest": min(control, default=None),
                           "control_all": control}
        print(f"{number}: sound largest "
              f"{max(sound, default=float('nan')):.6g} over {len(sound)} "
              f"seeds; control smallest "
              f"{min(control, default=float('nan')):.6g} over "
              f"{len(control)}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump({"cell": cell.name, "rows": rows, "summary": summary},
                      fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
