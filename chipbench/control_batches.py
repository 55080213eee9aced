"""``python3 -m chipbench.control_batches --workload <cell> --seeds a,b,c
[--seconds s]``: ``chipbench.control`` for a driver with several controls
(``CONTROLS``: name -> (answers in the program's place, the compared number
it has to move)).  On the chip, at the cell's own size, one short window a
seed; prints what the comparison reads for the program and for each control,
and holds only if the program passes and every control moves its number and
no other.  The benchmark's own runs never run this."""

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.control_batches")
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
        sound = all(c["value"] <= c["limit"] for c in program.values())
        line = {"cell": cell.name, "seed": seed, "calls": len(lat),
                "setup_s": setup_s, "program": program,
                "program_passes": sound, "controls": {}}
        held = held and sound and not failed
        for name, (answers, moves) in cell.driver.CONTROLS.items():
            read = cell.driver.compare(state, answers(state, got))
            moved = [k for k, c in read.items() if c["value"] > c["limit"]]
            line["controls"][name] = {"compared": read, "moved": moved,
                                      "fails_as_meant": moved == [moves]}
            held = held and moved == [moves]
        print(json.dumps(line), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
