"""``python3 -m chipbench --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in one process on the machine's chip."""

import time

_T0 = time.time()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402


def _process_start() -> float:
    """When this process was started (epoch seconds), so that ``setup_s``
    counts the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
        return started if 0 <= _T0 - started < 60 else _T0
    except (OSError, ValueError, IndexError):
        return _T0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = _process_start()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.path.isdir(os.path.join(root, "spark_rapids_jni_tpu")):
        print("chipbench: the program (spark_rapids_jni_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 1
    sys.path.insert(0, root)
    from chipbench import harness
    cell = harness.Cell(args.workload)

    device = harness.find_chip(cell)
    if device is None:
        return 1

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start, device)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    # a run that printed its line has run to its end: whether it is correct
    # is on the line, not in the exit code
    return 0


if __name__ == "__main__":
    sys.exit(main())
