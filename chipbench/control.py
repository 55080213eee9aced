"""``python3 -m chipbench.control --workload <cell> --seeds a,b,c
[--seconds s]``: on the chip, at the cell's own size, one short window a
seed; prints what the comparison reads for the program and for the cell's
control put in the program's place.  The limits of ``correct`` were set from
these readings (PERF.md); the benchmark's own runs never run this."""

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import harness
    from spark_rapids_jni_tpu.utils import metrics
    cell = harness.Cell(args.workload)
    if harness.find_chip(cell) is None:
        return 1
    metrics.set_enabled(True)
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        rec = harness.Recorder()
        state = cell.driver.setup(dict(cell.config), cell.traffic, seed, rec)
        state.errors = []
        setup_s = time.time() - t0
        lat, _, _, failed, _ = harness.drive(cell, state, rec, args.seconds)
        got = cell.driver.answers(state)
        program = cell.driver.compare(state, got)
        control = cell.driver.compare(
            state, cell.driver.control_answers(state, got))
        caught = any(c["value"] > c["limit"] for c in control.values())
        sound = all(c["value"] <= c["limit"] for c in program.values())
        held = held and caught and sound and not failed
        print(json.dumps({"cell": cell.name, "seed": seed,
                          "calls": len(lat), "setup_s": setup_s,
                          "program": program, "control": control,
                          "program_passes": sound,
                          "control_fails": caught}), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
