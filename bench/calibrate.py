"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 21,22,23 --out <file.json>

For each ``--seeds`` seed: the program, built and driven exactly as a run's
set-up drives it (three ``run(1)`` updates), against the plain reference:
the lower readings. For each ``--control-seeds`` seed: the reference in the
next precision below the configuration's (bfloat16) put in the program's
place, and each fault of the family's ``FAULTS`` that the cell can have
planted in the reference put in the program's place (half of the batch;
on a cell of several lanes, one lane's batch alone, as without the
exchange between chips; every sampled action altered where it is drawn);
a state left unchanged reads 1 on ``change_gap`` and is computed the same
way. These are the upper readings. Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import cells, chip, harness  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    harness.use_compile_cache()
    cell = cells.load_cell(args.workload)
    devices = chip.require(cell.workload["chips"])
    out = {"workload": args.workload, "device": chip.describe(devices),
           "program": {}, "control": {}, "faults": {}}
    for seed in args.seeds:
        t0 = time.perf_counter()
        entry = cell.entry.Entry(cell.config, cell.workload, seed, devices)
        harness.guard_widths(entry.settings, cell.config)
        prog = harness.checked_updates(entry)
        entry.close()
        out["program"][seed] = harness.reference_readings(cell, seed, prog)
        print(f"program seed {seed}: {out['program'][seed]} "
              f"({time.perf_counter() - t0:.1f} s)", file=sys.stderr,
              flush=True)
    # one lane's batch alone is the exchange left out: only across lanes
    faults = [f for f in cell.family.FAULTS
              if f != "one_lane" or cell.workload.get("lanes", 1) > 1]
    for seed in args.control_seeds:
        ctrl = harness.reference_as_program(cell, seed, dtype=jnp.bfloat16)
        out["control"][seed] = harness.reference_readings(cell, seed, ctrl)
        print(f"control seed {seed}: {out['control'][seed]}",
              file=sys.stderr, flush=True)
        for fault in faults:
            planted = harness.reference_as_program(cell, seed, fault=fault)
            out["faults"].setdefault(fault, {})[seed] = \
                harness.reference_readings(cell, seed, planted)
        unchanged = harness.reference_as_program(cell, seed)
        unchanged["params"] = unchanged["params0"]
        out["faults"].setdefault("unchanged_state", {})[seed] = \
            harness.reference_readings(cell, seed, unchanged)
        print(f"faults seed {seed}: "
              + json.dumps({f: out['faults'][f][seed] for f in out['faults']}),
              file=sys.stderr, flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"workload": args.workload, "seconds":
                      time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
