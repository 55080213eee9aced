#!/usr/bin/env python
"""One-shot ops report for the serving runtime.

Renders, in one terminal page, the state an on-call operator asks for
first: live request state (queue depth, in-flight bytes, workers,
quarantine), SLO watchdog status per query class, the latency-attribution
breakdown (where a request's end-to-end time went, stage by stage), and
the flight recorder's recent ring.  Three sources, same report:

* **in-process** — ``report(sched)`` on a live ``QueryScheduler``
  (importable; what a serving harness calls on SIGUSR1 or a debug
  endpoint).
* **Prometheus scrape** — ``--url http://host:PORT/metrics`` against a
  runtime started with ``SRJT_METRICS_PORT``: renders the counter /
  gauge / histogram families (no live queue state — the scrape surface
  is the registry, not the scheduler).
* **incident snapshot** — ``ops_report.py incident-<kind>-*.json``:
  renders a flight-recorder dump cold, lifecycle events of the breaching
  request first.

Usage:
  python tools/ops_report.py <incident.json>           # post-mortem
  python tools/ops_report.py --url http://host:9f/metrics   # live scrape
"""

from __future__ import annotations

import json
import sys

STAGES = ("queue", "coalesce", "admission", "dispatch", "ready")


def _fmt_bytes(n) -> str:
    if n is None:
        return "unlimited"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.0f} {unit}" if unit == "B" else f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} GiB"


def _attribution_lines(hists: dict) -> list[str]:
    """The stage-attribution table from ``exec.stage.*_ms`` histograms
    (metrics-snapshot dict shape: count/total/min/max)."""
    rows = []
    for st in STAGES:
        h = hists.get(f"exec.stage.{st}_ms")
        if h and h.get("count"):
            rows.append((st, h["count"], h["total"] / h["count"], h["max"]))
    if not rows:
        return ["  (no exec.stage.* observations)"]
    total_mean = sum(r[2] for r in rows)
    out = [f"  {'stage':<10} {'count':>7} {'mean ms':>10} "
           f"{'max ms':>10} {'share':>7}"]
    for st, cnt, mean, mx in rows:
        share = mean / total_mean * 100 if total_mean else 0.0
        out.append(f"  {st:<10} {cnt:>7} {mean:>10.3f} {mx:>10.3f} "
                   f"{share:>6.1f}%")
    return out


# byte-path ingest counters (round 6): where scan bytes went before any
# query ran — slab staging, fused row filter, pallas kernel dispatch
_INGEST = (
    ("parquet.stage.slab_bytes", "slab bytes shipped", _fmt_bytes),
    ("parquet.stage.transfers", "slab transfers", None),
    ("parquet.stage.overlap_ms", "walk/stage overlap ms", None),
    ("parquet.scan.donated_bytes", "decode-donated bytes", _fmt_bytes),
    ("parquet.rowfilter.fused_scans", "fused-filter scans", None),
    ("parquet.rowfilter.rows_kept", "fused-filter rows kept", None),
    ("rowconv.pallas.hits", "pallas kernel hits", None),
    ("rowconv.pallas.fallbacks", "pallas lax fallbacks", None),
)


def _ingest_lines(counters: dict, events: list) -> list[str]:
    """Ingest attribution: the staging-tier counters, plus the per-load
    deltas the prefetcher stamped on its ``exec.prefetch.ingest`` events
    (how much of each prefetch load was byte-path work)."""
    out = []
    for name, label, fmt in _INGEST:
        v = counters.get(name)
        if v:
            out.append(f"  {label:<26} {fmt(v) if fmt else f'{v:.0f}'}")
    for ev in [e for e in events
               if e.get("kind") == "exec.prefetch.ingest"][-5:]:
        out.append(
            f"  prefetch[{ev.get('key')}]: "
            f"{_fmt_bytes(ev.get('slab_bytes', 0))} staged in "
            f"{ev.get('transfers', 0):.0f} transfers, "
            f"overlap {ev.get('overlap_ms', 0):.0f} ms")
    return out or ["  (no byte-path ingest activity recorded)"]


def _ledger_lines(ledger: dict) -> list[str]:
    """The compile-cost ledger table: per plan fingerprint, where the
    compile budget went (capture/trace ms, recompiles, cache hits), with
    cold/warm attribution — cold = the one-time capture + trace +
    first-dispatch cost this process paid, rehydrates = plans adopted
    from the AOT artifact store (``exec/artifacts.py``) whose capture
    cost was paid by an EARLIER process instead."""
    if not ledger:
        return ["  (no compiled plans this process)"]
    out = []
    # the programs JAX compiled: one line of sums, then the five costliest
    xla = {k: e for k, e in ledger.items() if k.startswith("xla:")}
    if xla:
        def tot(field):
            return sum(e.get(field, 0) for e in xla.values())
        out.append(
            f"  xla: {len(xla)} programs  compiles {tot('compiles'):.0f} "
            f"({tot('compile_ms'):.1f} ms, {tot('cache_hits'):.0f} fetched "
            f"from the persistent cache)  trace+lower "
            f"{tot('trace_ms') + tot('lower_ms'):.1f} ms")
        for plan in sorted(xla, key=lambda k: -xla[k].get("compile_ms", 0))[:5]:
            e = xla[plan]
            out.append(
                f"    {plan}  compiles {e.get('compiles', 0):.0f} "
                f"({e.get('compile_ms', 0):.1f} ms)  cache hits "
                f"{e.get('cache_hits', 0):.0f}")
    for plan in sorted(k for k in ledger if k not in xla):
        e = ledger[plan]
        traces = e.get("traces", 0)
        cold_ms = (e.get("capture_ms", 0) + e.get("trace_ms", 0)
                   + e.get("first_dispatch_ms", 0))
        out.append(
            f"  {plan}")
        out.append(
            f"    captures {e.get('captures', 0):.0f} "
            f"({e.get('capture_ms', 0):.1f} ms)  "
            f"traces {traces:.0f} ({e.get('trace_ms', 0):.1f} ms, "
            f"{max(traces - 1, 0):.0f} recompile)  "
            f"first-dispatch {e.get('first_dispatch_ms', 0):.1f} ms")
        out.append(
            f"    cold {cold_ms:.1f} ms  "
            f"rehydrates {e.get('rehydrates', 0):.0f} (AOT, zero-capture)  "
            f"warm runs {e.get('runs', 0):.0f}")
        out.append(
            f"    cache hit/size/miss "
            f"{e.get('cache_hits', 0):.0f}/"
            f"{e.get('cache_size_hits', 0):.0f}/"
            f"{e.get('cache_misses', 0):.0f}")
    return out


def _profile_lines(last: int = 3) -> list[str]:
    """Recent query profiles (``plan/profile.py`` retention ring): the
    top self-time nodes of each, one line per node."""
    from spark_rapids_jni_tpu.plan import profile
    profs = profile.completed(last=last)
    if not profs:
        return ["  (no completed query profiles — run with SRJT_PROFILE=1)"]
    out = []
    for p in profs:
        mis = len(p.mispredictions())
        out.append(f"  {p.name}: wall {p.wall_ms:.1f} ms, "
                   f"{sum(1 for _ in p.nodes())} nodes, "
                   f"{mis} mispredicted")
        top = sorted(p.nodes(), key=lambda n: -n.self_ms())[:4]
        for n in top:
            flag = "  MISPREDICT" if n.mispredicted() else ""
            out.append(f"    {n.self_ms():>8.2f} ms  rows={n.out_rows}  "
                       f"{n.line}{flag}")
    return out


def _probe_profile_lines(v) -> list[str]:
    """Render the ``plan.active_profile`` flight probe: per stuck thread,
    the open node stack (innermost last) of the in-flight query."""
    out = []
    for tid, prof in sorted((v or {}).items()):
        out.append(f"    thread {tid}: {prof.get('name')} "
                   f"({len(prof.get('nodes') or [])} nodes closed)")
        for line in prof.get("open") or []:
            out.append(f"      open: {line}")
    return out


def _slo_lines(slo: dict) -> list[str]:
    th = slo.get("thresholds") or {}
    if not th:
        return ["  (no SLO objectives configured — set SRJT_SLO_* )"]
    out = [f"  objectives: {th}  window: {slo.get('window_s')}s"]
    for cls, st in sorted((slo.get("classes") or {}).items()):
        if st is None:
            out.append(f"  {cls:<12} (below min window population)")
            continue
        mark = "BREACHED" if st.get("breached") else "ok"
        out.append(
            f"  {cls:<12} n={st['n']:<5} p50={st['p50_ms']:.1f}ms "
            f"p95={st['p95_ms']:.1f}ms p99={st['p99_ms']:.1f}ms "
            f"err={st['error_rate']:.3f} degr={st['degrade_rate']:.3f} "
            f"[{mark}]")
        for obj, v in (st.get("objectives") or {}).items():
            if v.get("breached"):
                out.append(f"      !! {obj}: observed {v['observed']} "
                           f"> limit {v['limit']}")
    return out


def report(sched) -> str:
    """The live report for an in-process ``QueryScheduler``."""
    from spark_rapids_jni_tpu.utils import flight, metrics
    st = sched.ops_state()
    snap = metrics.snapshot()
    lines = ["== serving state =="]
    lines.append(
        f"  queue depth {st['queue_depth']}  workers {st['workers']}  "
        f"in-flight {_fmt_bytes(st['inflight_bytes'])} / "
        f"{_fmt_bytes(st['inflight_cap'])}  "
        f"quarantined {st['quarantined']}")
    pc = st["plan_cache"]
    lines.append(
        f"  plan cache: {pc['entries']}/{pc['cap']} entries, "
        f"hit {pc['hit']:.0f} miss {pc['miss']:.0f} "
        f"size_hit {pc['size_hit']:.0f} stale {pc['stale']:.0f}")
    lines.append("== SLO watchdog ==")
    lines.extend(_slo_lines(st["slo"]))
    lines.append("== latency attribution ==")
    lines.extend(_attribution_lines(snap["histograms"]))
    lines.append("== ingest attribution ==")
    lines.extend(_ingest_lines(snap.get("counters") or {}, flight.events()))
    lines.append("== compile ledger ==")
    lines.extend(_ledger_lines(snap.get("ledger") or {}))
    lines.append("== query profiles ==")
    lines.extend(_profile_lines())
    lines.append("== flight ring (newest last) ==")
    for ev in flight.events(last=15):
        extra = {k: v for k, v in ev.items()
                 if k not in ("ts", "tid", "kind")}
        lines.append(f"  {ev['kind']:<24} {extra}")
    return "\n".join(lines)


def report_incident(path: str) -> str:
    """Render an incident snapshot file: the breaching request's own
    lifecycle first, then the serving state the snapshot froze."""
    with open(path) as f:
        snap = json.load(f)
    rid = snap.get("request_id")
    batch = snap.get("batch") or []
    lines = [f"== incident: {snap.get('kind')} ==",
             f"  request {rid}  batch {batch or '-'}",
             f"  fields: {snap.get('fields')}"]
    evs = snap.get("events") or []
    mine = [e for e in evs
            if rid and (e.get("rid") == rid or rid in (e.get("batch") or ()))]
    lines.append(f"== lifecycle of {rid} "
                 f"({len(mine)} of {len(evs)} ring events) ==")
    for ev in mine or evs[-15:]:
        extra = {k: v for k, v in ev.items()
                 if k not in ("ts", "tid", "kind")}
        lines.append(f"  {ev['kind']:<24} {extra}")
    probes = snap.get("probes") or {}
    if probes:
        lines.append("== probes at incident time ==")
        for k, v in sorted(probes.items()):
            if k == "plan.active_profile" and isinstance(v, dict):
                lines.append(f"  {k}: (in-flight node profiles)")
                lines.extend(_probe_profile_lines(v))
            else:
                lines.append(f"  {k}: {v}")
    hists = (snap.get("metrics") or {}).get("histograms") or {}
    lines.append("== latency attribution ==")
    lines.extend(_attribution_lines(hists))
    lines.append("== ingest attribution ==")
    lines.extend(_ingest_lines(
        (snap.get("metrics") or {}).get("counters") or {}, evs))
    ledger = (snap.get("metrics") or {}).get("ledger")
    if ledger:
        lines.append("== compile ledger ==")
        lines.extend(_ledger_lines(ledger))
    return "\n".join(lines)


def report_scrape(url: str) -> str:
    """Render a ``/metrics`` scrape: the srjt counter/gauge/histogram
    families grouped, histogram mean from ``_sum``/``_count``."""
    from urllib.request import urlopen
    text = urlopen(url, timeout=5).read().decode()
    counters, gauges, hists = {}, {}, {}
    ledger: dict = {}
    types = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
            continue
        if not line or line.startswith("#"):
            continue
        name, _, val = line.partition(" ")
        base = name.split("{")[0]
        if base == "srjt_compile_ledger":
            # labeled family: srjt_compile_ledger{plan="...",kind="..."}
            import re
            m = re.search(r'plan="([^"]*)".*kind="([^"]*)"', name)
            if m:
                ledger.setdefault(m.group(1), {})[m.group(2)] = float(val)
            continue
        if base.endswith("_sum") and types.get(base[:-4]) == "histogram":
            hists.setdefault(base[:-4], {})["sum"] = float(val)
        elif base.endswith("_count") and types.get(base[:-6]) == "histogram":
            hists.setdefault(base[:-6], {})["count"] = float(val)
        elif types.get(base) == "gauge":
            gauges[base] = float(val)
        elif types.get(base) == "counter":
            counters[base] = float(val)
    lines = [f"== scrape {url} ==", "== counters =="]
    for k, v in sorted(counters.items()):
        lines.append(f"  {k:<44} {v:.0f}")
    lines.append("== gauges ==")
    for k, v in sorted(gauges.items()):
        lines.append(f"  {k:<44} {v:.0f}")
    lines.append("== histograms (mean ms where applicable) ==")
    for k, h in sorted(hists.items()):
        if h.get("count"):
            lines.append(f"  {k:<44} n={h['count']:.0f} "
                         f"mean={h['sum'] / h['count']:.3f}")
    if ledger:
        lines.append("== compile ledger ==")
        lines.extend(_ledger_lines(ledger))
    return "\n".join(lines)


def main(argv: list[str]) -> int:
    if len(argv) == 2 and argv[0] == "--url":
        print(report_scrape(argv[1]))
        return 0
    if len(argv) == 1 and not argv[0].startswith("-"):
        print(report_incident(argv[0]))
        return 0
    print(__doc__)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
