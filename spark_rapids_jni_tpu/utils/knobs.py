"""Central registry of every ``SRJT_*`` environment knob.

Before this module, knob reads were scattered ``os.environ.get("SRJT_...")``
calls with the default, the parse semantics, and the documentation living
at each call site — three copies per knob that drift independently, and no
single place an operator (or the README generator, or the lint gate) can
enumerate.  This registry is that place: one :class:`Knob` per name with
its default, parser, and a one-line doc.  The static-analysis knob pass
(``analysis/knobpass.py``, rule ``knob-env``) fails CI on any direct
``SRJT_*`` environ read outside this file, and rule ``knob-undoc`` fails
on registered knobs missing from the README table (regenerated with
``python tools/srjt_lint.py --knob-table``).

Behavior contract: :func:`get` re-reads the environment on every call —
exactly what the scattered call sites did — so runtime toggles
(``metrics.set_enabled(None)`` style) keep working.  Parsers reproduce
each site's historical semantics bit-for-bit (e.g. the serving gates
treat ``0``/``off``/``false``/empty as off, while
``SRJT_STREAM_ALLOW_APPROX`` is opt-IN on ``1``/``true``/``on`` only).

This module is deliberately dependency-free (stdlib ``os`` only) so the
lint tool can load it standalone, without importing the package (and its
jax dependency).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional

__all__ = ["Knob", "REGISTRY", "register", "get", "markdown_table",
           "parse_bytes"]


# --- parsers ----------------------------------------------------------------
# Each returns the value the historical call site computed from the raw
# environment string.  ``raw`` may be None only when the knob's default is
# None (unset-means-unset knobs).


def _int(raw: str) -> int:
    return int(raw)


def _float(raw: str) -> float:
    return float(raw)


def _str(raw: Optional[str]) -> Optional[str]:
    return raw


def _on_unless_off(raw: str) -> bool:
    """The package's standard gate: anything except 0/off/false/empty."""
    return raw.lower() not in ("0", "off", "false", "")


def _on_unless_0_off(raw: str) -> bool:
    """Gate variant used by the scan/dict/xpack paths: 0/off disable."""
    return raw.lower() not in ("0", "off")


def _opt_in(raw: str) -> bool:
    """Opt-in gate: only 1/true/on enable (``SRJT_STREAM_ALLOW_APPROX``)."""
    return raw.lower() in ("1", "true", "on")


def _is_1(raw: str) -> bool:
    return raw == "1"


def _not_0(raw: str) -> bool:
    return raw != "0"


def _opt_float(raw: Optional[str]) -> Optional[float]:
    """None/empty/whitespace → None, else float (SLO objectives)."""
    if raw is None or not raw.strip():
        return None
    return float(raw)


def _opt_int(raw: Optional[str]) -> Optional[int]:
    """None/empty → None, else int (ports, dynamic-default counts)."""
    if raw is None or not raw:
        return None
    return int(raw)


def _opt_str(raw: Optional[str]) -> Optional[str]:
    """None/empty → None, else the string (paths, rule lists)."""
    return raw or None


def parse_bytes(raw) -> Optional[int]:
    """``"512m"`` / ``"2g"`` / ``"65536"`` → bytes; None/empty/``none``/
    ``unlimited``/``off`` → None (no limit).  Mirror of
    ``memory.budget.parse_bytes`` (kept here too so this module stays
    loadable without the package)."""
    if raw is None:
        return None
    if isinstance(raw, (int, float)):
        return int(raw)
    t = raw.strip().lower()
    if t in ("", "none", "unlimited", "off"):
        return None
    mult = 1
    if t[-1] in "kmgt":
        mult = 1 << (10 * ("kmgt".index(t[-1]) + 1))
        t = t[:-1]
    return int(float(t) * mult)


class Knob:
    """One registered environment knob: name, raw default, parser, doc."""

    __slots__ = ("name", "default", "parse", "doc", "section")

    def __init__(self, name: str, default: Optional[str],
                 parse: Callable[[Optional[str]], Any], doc: str,
                 section: str):
        self.name = name
        self.default = default       # raw string default; None = unset
        self.parse = parse
        self.doc = doc
        self.section = section

    def value(self) -> Any:
        """Parsed current value: environment override, else the default."""
        return self.parse(os.environ.get(self.name, self.default))


REGISTRY: dict[str, Knob] = {}


def register(name: str, default: Optional[str], parse, doc: str,
             section: str = "general") -> Knob:
    k = Knob(name, default, parse, doc, section)
    REGISTRY[name] = k
    return k


def get(name: str) -> Any:
    """The parsed value of registered knob ``name`` (re-reads the
    environment on every call).  Raises ``KeyError`` for unregistered
    names — register in this file first; the lint gate enforces it."""
    return REGISTRY[name].value()


def is_registered(name: str) -> bool:
    return name in REGISTRY


# --- the registry -----------------------------------------------------------
# Grouped by subsystem; ``section`` drives the README table's grouping.

# serving runtime (exec/)
register("SRJT_EXEC", "0", _on_unless_off,
         "serving-runtime gate for deployments (`exec.enabled()`)",
         "exec")
register("SRJT_EXEC_WORKERS", "4", _int,
         "worker threads pulling from the request queue", "exec")
register("SRJT_EXEC_QUEUE_DEPTH", "32", _int,
         "bounded queue depth; past it `submit` raises `ExecQueueFull`",
         "exec")
register("SRJT_EXEC_COALESCE_MS", "4", _float,
         "cross-request coalesce window (ms); `0` disables batching",
         "exec")
register("SRJT_EXEC_COALESCE_MAX", "16", _int,
         "max requests per coalesced batch", "exec")
register("SRJT_EXEC_DEADLINE", None, _opt_float,
         "default end-to-end timeout (s) for requests submitted without "
         "one", "exec")
register("SRJT_EXEC_INFLIGHT_BYTES", None, parse_bytes,
         "per-device in-flight admission cap (`512m` forms; unset = no "
         "gate)", "exec")
register("SRJT_EXEC_PREFETCH_DEPTH", "2", _int,
         "staged working sets held ahead of execution", "exec")
register("SRJT_EXEC_PLAN_CACHE_CAP", "32", _int,
         "compiled-plan LRU entry cap", "exec")
register("SRJT_EXEC_PLAN_SIZE_FP", "1", _on_unless_off,
         "size-fingerprint plan sharing across refreshed same-shape data",
         "exec")
register("SRJT_EXEC_DEVICES", "1", _int,
         "replicas (one per local device); `>1` enables multi-device "
         "serving", "exec")
register("SRJT_EXEC_RECOVERY", "1", _on_unless_off,
         "quarantine→probe→recovery lifecycle; `0` pins the legacy "
         "terminal-quarantine contract", "exec")
register("SRJT_EXEC_PROBE_BASE_S", "0.05", _float,
         "first recovery-probe delay (doubles per failure, jittered)",
         "exec")
register("SRJT_EXEC_PROBE_MAX_S", "2.0", _float,
         "probe backoff ceiling", "exec")
register("SRJT_EXEC_EJECT_AFTER", "3", _int,
         "consecutive failed canaries before permanent ejection", "exec")
register("SRJT_EXEC_RELOCATE_MAX", None, _opt_int,
         "max failover hops per request before it errors (default: the "
         "device count)", "exec")

# AOT plan-artifact store (exec/artifacts.py)
register("SRJT_AOT_DIR", None, _opt_str,
         "root of the persistent plan-artifact store (capture tapes + "
         "warm-up manifest); unset disables AOT persistence", "aot")
register("SRJT_AOT_GEOM_BUCKETS", "1", _on_unless_off,
         "pow2-bucket input geometry in artifact keys so nearby dataset "
         "sizes share one artifact; `0` keys on exact shapes", "aot")
register("SRJT_AOT_WARMUP", "8", _int,
         "manifest entries (ranked by compile-ledger cost) the scheduler "
         "pre-hydrates in the background at startup; `0` disables the "
         "warm-up thread", "aot")
register("SRJT_AOT_XLA_CACHE", "1", _on_unless_off,
         "turn JAX's persistent compilation cache on for serving (the "
         "directory follows `utils/compile_cache.py`); `0` leaves the "
         "JAX config untouched", "aot")

# SLO watchdog (exec/slo.py)
register("SRJT_SLO_P50_MS", None, _opt_float,
         "rolling-window p50 latency objective per query class", "slo")
register("SRJT_SLO_P95_MS", None, _opt_float,
         "rolling-window p95 latency objective per query class", "slo")
register("SRJT_SLO_P99_MS", None, _opt_float,
         "rolling-window p99 latency objective per query class", "slo")
register("SRJT_SLO_ERROR_RATE", None, _opt_float,
         "error-rate objective in [0, 1]", "slo")
register("SRJT_SLO_DEADLINE_RATE", None, _opt_float,
         "deadline-breach-rate objective in [0, 1]", "slo")
register("SRJT_SLO_DEFER_RATE", None, _opt_float,
         "admission-defer-rate objective in [0, 1]", "slo")
register("SRJT_SLO_DEGRADE_RATE", None, _opt_float,
         "degraded-admission-rate objective in [0, 1]", "slo")
register("SRJT_SLO_RELOCATE_RATE", None, _opt_float,
         "failover-relocation-rate objective in [0, 1]", "slo")
register("SRJT_SLO_WINDOW_S", "60", _float,
         "rolling window length (s)", "slo")
register("SRJT_SLO_MIN_N", "8", _int,
         "minimum window population before any verdict", "slo")
register("SRJT_SLO_COOLDOWN_S", "30", _float,
         "per-(class, objective) re-alarm holdoff (s)", "slo")

# memory arena (memory/)
register("SRJT_HBM_ARENA", "0", _on_unless_off,
         "master gate for the arena subsystem", "memory")
register("SRJT_HBM_BUDGET", None, _str,
         "process/query byte limit (`512m`, `2g`, plain bytes); setting "
         "it also enables the arena", "memory")
register("SRJT_INDEX_CACHE_CAP", "512m", _str,
         "build-index cache LRU byte cap "
         "(`join.build_index.evictions` counts)", "memory")
register("SRJT_ARENA_ZEROS_CAP", "16m", _str,
         "pooled-zeros cache cap (`0` disables pooling)", "memory")
register("SRJT_HOSTCACHE_CAP", "256m", _str,
         "host-mirror cache LRU byte cap "
         "(`arena.hostcache.evictions` counts)", "memory")

# observability (utils/)
register("SRJT_METRICS_WINDOW_N", "1024", _int,
         "bounded per-histogram sample tail feeding rolling percentiles",
         "observability")
register("SRJT_METRICS_PORT", None, _opt_str,
         "serve `metrics.to_prometheus()` on "
         "`http://0.0.0.0:<port>/metrics`", "observability")
register("SRJT_FLIGHT", "1", _on_unless_off,
         "flight-recorder master gate (leave on: steady-state cost "
         "budget <2%)", "observability")
register("SRJT_FLIGHT_N", "512", _int,
         "flight-recorder ring capacity in events", "observability")
register("SRJT_INCIDENT_DIR", None, _opt_str,
         "where incident snapshots land; unset = incidents counted + "
         "ring-recorded, not written", "observability")
register("SRJT_INCIDENT_PER_KIND", "5", _int,
         "per-kind snapshot cap per process (breach storms must not "
         "fill the disk)", "observability")
register("SRJT_SANITIZE", "0", _str,
         "runtime sanitizers: `1` files flight incidents on lock-order "
         "inversions and hot-path retraces, `strict` raises instead "
         "(`tests/conftest.py` runs all of tier-1 strict)",
         "observability")
register("SRJT_PROFILE", "0", _on_unless_off,
         "per-plan-node runtime profiling (`plan/profile.py`): rows/"
         "bytes/time per executed node, `explain_analyze()` rendering; "
         "off = one bool check on the executor path", "observability")
register("SRJT_PROFILE_DEVICE_TIME", "1", _on_unless_0_off,
         "fence each profiled node's output (`block_until_ready`) to "
         "attribute device time; `0`/`off` records host wall only",
         "observability")
register("SRJT_PROFILE_VALIDITY", "0", _opt_in,
         "per-node validity density in profiles (adds one scalar sync "
         "per nullable column per node, recorded on the capture/replay "
         "tape — keep the knob stable across a compiled plan's "
         "lifetime)", "observability")
register("SRJT_PROFILE_DIR", None, _opt_str,
         "directory where per-query profile JSON artifacts land on "
         "profile close; unset = profiles kept in memory only",
         "observability")

# ops / joins
register("SRJT_JOIN_ENGINE", None, _str,
         "force the join engine: `dense` or `sorted` (default: planner "
         "choice)", "ops")

# rowconv
register("SRJT_RAGGED_DMA", "auto", _on_unless_0_off,
         "Pallas ragged DMA path on TPU backends; `0`/`off` forces the "
         "XLA gather fallback", "rowconv")
register("SRJT_XPACK", "1", _on_unless_0_off,
         "native xpack fast path for row conversion; `0`/`off` falls "
         "back to the reference composer", "rowconv")
register("SRJT_PALLAS_PACKWIN", "0", _str,
         "Pallas `pack_windows` kernel for the var-width row combine: "
         "`1`/`on` on TPU, `interpret` forces interpreter mode (CI "
         "parity), default off → lax window combine", "rowconv")
register("SRJT_PALLAS_EXTRACT", "0", _str,
         "Pallas `extract_group_windows` kernel for var-width char "
         "extraction: `1`/`on` on TPU, `interpret` forces interpreter "
         "mode (CI parity), default off → lax slab gather", "rowconv")

# plan optimizer
register("SRJT_PLAN_OPT", "1", _not_0,
         "`0` disables all plan rewrites (lower the raw tree)", "plan")
register("SRJT_PLAN_RULES", None, _opt_str,
         "comma-separated allowlist of optimizer rule names", "plan")
register("SRJT_PLAN_MAX_PASSES", "10", _int,
         "optimizer fixpoint pass bound", "plan")
register("SRJT_PLAN_STATS_CAP", "4096", _int,
         "cardinality-stats LRU entry cap", "plan")
register("SRJT_PLAN_STATS_PATH", None, _opt_str,
         "JSON sidecar for cardinality stats: loaded at first use for "
         "warm priors, saved atomically at exit", "plan")
register("SRJT_AQE", "0", _opt_in,
         "adaptive query execution: stage-wise replanning on observed "
         "cardinalities (join reorder, engine flips, skew salting)",
         "plan")
register("SRJT_AQE_SKEW_FACTOR", "4.0", _float,
         "hot-key skew ratio (hottest/mean) at or above which AQE salts "
         "the repartition join", "plan")
register("SRJT_AQE_REPLAN_MIN_ROWS", "64", _int,
         "AQE skips join reorder when every pending input is smaller "
         "than this (replan overhead not worth it)", "plan")

# SQL front-end
register("SRJT_SQL_CACHE", "1", _on_unless_0_off,
         "memoize SQL text → optimized plan tree per (text, params, "
         "schema) so repeat submissions skip parse+bind+optimize; "
         "`0`/`off` reparses every call (bench baseline)", "sql")
register("SRJT_SQL_CACHE_CAP", "256", _int,
         "parsed-plan memo entry cap (LRU)", "sql")
register("SRJT_SQL_MAX_LEN", "262144", _int,
         "reject SQL text longer than this many characters before "
         "tokenizing (serving-surface input bound)", "sql")

# parquet scan
register("SRJT_DICT_STRINGS", "1", _on_unless_0_off,
         "dictionary-encoded string fast path; `0`/`off` reverts to "
         "eager materialization for differential testing", "parquet")
register("SRJT_FUSED_SCAN", "1", _on_unless_0_off,
         "fused multi-row-group scan assembly; `0`/`off` decodes row "
         "groups independently", "parquet")
register("SRJT_STAGE_SLABS", "1", _on_unless_0_off,
         "coalesced h2d staging: a row group's raw pages/levels/"
         "dictionaries upload as a few large slabs instead of per-buffer "
         "`device_put`s; `0`/`off` reverts to per-buffer uploads",
         "parquet")
register("SRJT_STAGE_SLAB_BYTES", "64m", parse_bytes,
         "slab size cap for the coalescing stager (`64m` forms); a flush "
         "splits into multiple transfers past it", "parquet")
register("SRJT_STAGE_PIPELINE", "1", _on_unless_0_off,
         "double-buffered row-group pipeline: walk/decompress row group "
         "k+1 on host while k's slabs transfer; `0`/`off` stages "
         "synchronously", "parquet")
register("SRJT_STAGE_PIPELINE_DEPTH", "2", _int,
         "row groups walked ahead of the transfer stage (pipeline "
         "buffer bound)", "parquet")
register("SRJT_SCAN_DONATE", "auto", _str,
         "donate staged input slabs to the fused decode program (XLA "
         "reuses the buffers for outputs): `auto` = non-CPU backends, "
         "`1`/`on` forces, `0`/`off` disables", "parquet")
register("SRJT_FUSED_FILTER", "1", _on_unless_0_off,
         "fused scan→filter: planner row predicates prune rows on the "
         "staged host metadata (dictionary entries evaluated once, codes "
         "masked) before strings/wide columns materialize; `0`/`off` "
         "decodes all rows and filters after", "parquet")
register("SRJT_PALLAS_DICT_GATHER", "0", _str,
         "Pallas dictionary-index gather in the scan decode: `1`/`on` on "
         "TPU, `interpret` forces interpreter mode (CI parity), default "
         "off → lax gather", "parquet")
register("SRJT_PALLAS_TRANSPOSE", "0", _str,
         "Pallas byte→word transpose for PLAIN payload decode: `1`/`on` "
         "on TPU, `interpret` forces interpreter mode (CI parity), "
         "default off → strided lax transpose", "parquet")

# ml handoff (ml/)
register("SRJT_ML_PACK", "rowconv", _str,
         "feature-pack engine: `rowconv` reinterprets the JCUDF fixed-width "
         "row stream as the feature matrix (zero-copy), `stack` is the "
         "reference lane-stack A/B", "ml")
register("SRJT_ML_BATCH", "256", _int,
         "default minibatch size for `ml.pipeline.BatchPipeline`", "ml")
register("SRJT_ML_SEED", "0", _int,
         "default PRNG seed for the device-side epoch shuffle", "ml")
register("SRJT_ML_SHUFFLE", "feistel", _str,
         "epoch-shuffle engine: `feistel` is the sort-free O(n) Feistel "
         "bijection, `sort` is `jax.random.permutation` (single-threaded "
         "O(n log n) sort on XLA:CPU) kept as the cross-check", "ml")
register("SRJT_ML_EPOCH_FUSE", "1", _on_unless_0_off,
         "fuse each training epoch into one jitted `lax.scan` dispatch; "
         "`0`/`off` dispatches per-batch steps", "ml")
register("SRJT_ML_DONATE", "auto", _str,
         "donate minibatch buffers into the jitted train step/epoch "
         "(`1`/`on`, `0`/`off`, `auto` = on for non-CPU backends where "
         "XLA implements donation)", "ml")

# streaming
register("SRJT_STREAM_ALLOW_APPROX", "0", _opt_in,
         "allow approximate incremental states (`1`/`true`/`on` only)",
         "stream")


# --- README table -----------------------------------------------------------

_SECTION_TITLES = {
    "exec": "Serving runtime (`exec/`)",
    "aot": "AOT artifact store (`exec/artifacts.py`)",
    "slo": "SLO watchdog (`exec/slo.py`)",
    "memory": "Memory arena (`memory/`)",
    "observability": "Observability (`utils/`)",
    "ops": "Joins (`ops/`)",
    "rowconv": "Row conversion (`rowconv/`)",
    "plan": "Plan optimizer (`plan/`)",
    "sql": "SQL front-end (`sql/`)",
    "parquet": "Parquet scan (`parquet/`)",
    "ml": "ML handoff (`ml/`)",
    "stream": "Streaming (`stream/`)",
    "general": "General",
}


def markdown_table() -> str:
    """The full knob catalog as grouped markdown tables — the generator
    behind the README's "Knob registry" section (`tools/srjt_lint.py
    --knob-table` refreshes it in place)."""
    out = []
    seen_sections = []
    for k in REGISTRY.values():
        if k.section not in seen_sections:
            seen_sections.append(k.section)
    for sec in seen_sections:
        out.append(f"**{_SECTION_TITLES.get(sec, sec)}**\n")
        out.append("| knob | default | meaning |")
        out.append("|---|---|---|")
        for k in REGISTRY.values():
            if k.section != sec:
                continue
            default = "unset" if k.default is None else f"`{k.default}`"
            out.append(f"| `{k.name}` | {default} | {k.doc} |")
        out.append("")
    return "\n".join(out)


if __name__ == "__main__":
    print(markdown_table())
