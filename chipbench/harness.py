"""One run of one cell: find its files, set up, warm up, drive the closed
loop for the window, read the metrics, decide ``correct``.

Driven by data: ``BENCHMARK.json`` names the cell's configuration and
traffic; ``configs/<config>.json`` holds the deployment's sizes,
``traffic/<traffic>.json`` the driver, its callers, the rate metric and,
where the mix has a steady one, the tail metric;
a per-layer metric is ``metrics/<name>.json`` naming a reader in
``readers/``.  A cell reports a per-layer metric when the metric's file
lists the cell or ``workloads/<cell>.json`` lists the metric.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import statistics
import sys
import threading
import time

from . import guards, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, "chipbench_out", "trace")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """A cell's files, resolved by the names in ``BENCHMARK.json``."""

    def __init__(self, name: str, root: str = ROOT):
        bench = _json(root, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"no cell {name!r} in BENCHMARK.json")
        here = os.path.join(root, "chipbench")
        self.name, self.chips = name, entry["chips"]
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        self.config = _json(root, conf["file"])
        self.traffic = _json(here, "traffic", entry["traffic"] + ".json")
        cell_file = os.path.join(here, "workloads", name + ".json")
        named = (_json(cell_file).get("metrics", [])
                 if os.path.exists(cell_file) else [])
        self.per_layer = {}
        for fn in sorted(os.listdir(os.path.join(here, "metrics"))):
            metric = _json(here, "metrics", fn)
            mname = fn[:-len(".json")]
            if name in metric.get("workloads", []) or mname in named:
                self.per_layer[mname] = metric
        self.driver = importlib.import_module(
            "chipbench.drivers." + self.traffic["driver"])


class Recorder:
    """chipbench's own spans: host clock, and in a traced run also a
    ``TraceAnnotation`` so the span sits on the profiler's clock."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.spans: dict[str, list[float]] = {}
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        note = contextlib.nullcontext()
        if self.traced:
            import jax
            note = jax.profiler.TraceAnnotation(trace.PREFIX + name)
        t0 = time.perf_counter()
        with note:
            yield
        dt = time.perf_counter() - t0
        with self._lock:
            self.spans.setdefault(name, []).append(dt)

    def clear(self):
        self.spans = {}


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values."""
    ordered = sorted(values)
    rank = max(-(-len(ordered) * q // 100), 1)
    return float(ordered[int(min(rank, len(ordered))) - 1])


def drive(cell: Cell, state, rec: Recorder, seconds: float):
    """The closed loop: every caller sends its next call when the last one
    has answered, until the window's end; a call in flight then is waited
    for and counts.  Where a caller's sequence is ``cycle_calls`` long (a
    stream's pass over its query set), it ends on a whole pass, so the
    work counted has the mix's proportions whatever the end cuts.  Returns
    ``(latencies, starts, work, failed, elapsed)``: the rate is all the work
    over all the time to the last answer; ``starts`` are the calls' starts,
    seconds into the window, in the order of ``latencies``."""
    callers = int(cell.traffic.get("callers", 1))
    cycle = int(cell.traffic.get("cycle_calls", 1))
    lat, starts, work, failed, ends = [], [], [0.0], [0], []
    lock = threading.Lock()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def loop(caller: int):
        i = 0
        while True:
            a = time.perf_counter()
            if a >= deadline and i % cycle == 0:
                break
            try:
                done = cell.driver.call(state, caller, i, rec)
            except Exception as e:  # noqa: BLE001 — a failed call is counted, and fails the run
                import traceback
                traceback.print_exc()
                with lock:
                    failed[0] += 1
                    state.errors.append(repr(e))
                break
            b = time.perf_counter()
            with lock:
                lat.append(b - a)
                starts.append(a - t0)
                work[0] += done
            i += 1
        with lock:
            ends.append(time.perf_counter())

    threads = [threading.Thread(target=loop, args=(c,), name=f"caller{c}")
               for c in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return lat, starts, work[0], failed[0], max(ends) - t0


def find_chip(cell: Cell) -> dict | None:
    """The look for a chip: the device as JAX reports it, or ``None`` (said
    on stderr) without the TPU chips the cell asks for or without the
    native library.  Points the compile cache where the program's one rule
    puts it."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX reports {device}", file=sys.stderr)
        return None
    from spark_rapids_jni_tpu import native
    from spark_rapids_jni_tpu.utils import compile_cache
    if native.load() is None:
        print(f"chipbench: libsrjt.so did not build or load: "
              f"{native.build_error}", file=sys.stderr)
        return None
    compile_cache.configure(min_compile_secs=0.0)
    return device


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, device: dict | None = None,
             config: dict | None = None) -> dict:
    """Everything of a run after the look for a chip.  ``config`` overrides
    the cell's sizes (tests run a tiny copy)."""
    import jax
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    guards.watch_xla_compiles()
    config = dict(cell.config if config is None else config)
    rec = Recorder(traced)
    fb0 = guards.fallbacks()
    state = cell.driver.setup(config, cell.traffic, seed, rec)
    state.errors = []
    rec.clear()
    # what set-up left on the heap is not the window's to collect
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t_start

    comp0 = guards.compiles()
    mono0, started_at = time.monotonic(), time.time()
    prof = trace.capture(TRACE_DIR) if traced else contextlib.nullcontext()
    with prof:
        with rec.span("window"):
            lat, starts, work, failed, elapsed = drive(cell, state, rec,
                                                       seconds)
    gc.unfreeze()
    comp1, fb1 = guards.compiles(), guards.fallbacks()

    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.local_devices())
    rate, tail = cell.traffic["rate"], cell.traffic.get("tail")
    end_to_end = {rate["metric"]: {"value": work / rate["per"] / elapsed,
                                   "unit": rate["unit"]}}
    if tail:
        end_to_end[tail["metric"]] = {
            "value": percentile(lat, tail["percentile"]) * 1e3
            if lat else None, "unit": "ms"}
    end_to_end["setup_s"] = {"value": setup_s, "unit": "s"}
    out_device = dict(device or {})
    out_device["memory_peak_bytes"] = int(peak_bytes)
    result = {"correct": False, "attempted": len(lat) + failed,
              "failed": failed, "metrics": end_to_end, "device": out_device}

    if traced:
        path = trace.find_xplane(TRACE_DIR)
        reduced = trace.reduce_trace(path) if path else None
        ctx = {"config": config, "facts": state.facts, "spans": rec.spans,
               "calls": len(lat), "window_start_monotonic": mono0,
               "trace": reduced, "device_kind": out_device.get("kind"),
               "program_metrics": metrics}
        per_layer = {}
        for name, metric in cell.per_layer.items():
            reader = importlib.import_module(
                "chipbench.readers." + metric["reader"])
            value = reader.read(ctx, metric.get("params", {}))
            if value is not None:
                per_layer[name] = {"value": value, "unit": metric["unit"]}
        result["metrics"] = per_layer
        result["end_to_end_traced"] = end_to_end
        if reduced and reduced["busy_s"]:
            out_device["busy_s"] = reduced["busy_s"]
            out_device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}

    for error in state.errors:
        print("chipbench: a call failed:", error, file=sys.stderr)
    compared = cell.driver.compare(state, cell.driver.answers(state))
    compared["fallbacks_moved"] = {"value": guards.moved(fb0, fb1),
                                   "limit": 0}
    compared["compiles_in_window"] = {"value": guards.moved(comp0, comp1),
                                      "limit": 0}
    compared["failed_calls"] = {"value": failed, "limit": 0}
    result["correct"] = bool(lat) and all(
        c["value"] <= c["limit"] for c in compared.values())
    result["calls"] = len(lat)
    result["median_call_ms"] = statistics.median(lat) * 1e3 if lat else None
    # where a rate dips and the tail does not: the few longest calls, each
    # [seconds into the window, ms], and the window's start on the wall clock
    result["window_started_at"] = started_at
    result["slowest_calls"] = [
        [round(starts[i], 3), round(lat[i] * 1e3, 3)]
        for i in sorted(range(len(lat)), key=lambda i: -lat[i])[:5]]
    result["compared"] = compared          # comes last on the line
    return result
