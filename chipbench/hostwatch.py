"""``python3 -m chipbench.hostwatch [--tick-ms 5] [--over-ms 50]``: a bare
loop on the host, beside a run and apart from it.  It touches neither JAX
nor the chip: it sleeps a tick at a time and prints, as one JSON line, every
tick that came back more than ``--over-ms`` late, with the wall-clock time.
Set beside a run's ``window_started_at`` and ``slowest_calls``, it says
whether a stalled call was the machine's (this loop stalls too) or the
process's (it does not).  Ends on SIGTERM or SIGINT."""

import argparse
import json
import signal
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.hostwatch")
    ap.add_argument("--tick-ms", type=float, default=5.0)
    ap.add_argument("--over-ms", type=float, default=50.0)
    args = ap.parse_args(argv)
    stop = []
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.append(1))
    tick = args.tick_ms / 1e3
    last, worst, n = time.monotonic(), 0.0, 0
    t0 = time.time()
    while not stop:
        time.sleep(tick)
        now = time.monotonic()
        late_ms = (now - last - tick) * 1e3
        worst, n = max(worst, late_ms), n + 1
        if late_ms > args.over_ms:
            print(json.dumps({"at": time.time(), "late_ms": late_ms}),
                  flush=True)
        last = now
    print(json.dumps({"from": t0, "to": time.time(), "ticks": n,
                      "worst_late_ms": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
