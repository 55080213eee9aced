"""The compile path as the program records it (utils/metrics.py): JAX's own
trace, lowering and backend-compile events become ``jax.trace`` /
``jax.lower`` / ``jax.compile`` spans under the open span, and sums under
the compile ledger's ``xla:<fun>`` entry."""

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu.utils import metrics, syncs, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("jax.trace", "jax.lower", "jax.compile")


@pytest.fixture(autouse=True)
def _metrics_on():
    metrics.set_enabled(True)
    metrics.reset()
    yield
    metrics.reset()
    metrics.set_enabled(None)      # back to the env default (off)


def _fresh(name: str, k: float):
    """A jitted function no cache has seen: its own name and constant."""
    def fn(x):
        return x * 3.0 + k
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _flat(trees):
    out, todo = [], list(trees)
    while todo:
        node = todo.pop()
        out.append(node)
        todo.extend(node.get("children", ()))
    return out


def _x():
    return np.arange(8, dtype=np.float32)      # no eager op to make it


_CHILD = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)

    def probe_cache_fn(x):
        return (x * 5.0 + 2.0).sum()

    jax.jit(probe_cache_fn)(np.arange(8, dtype=np.float32))
    spans = [s for s in metrics.span_roots() if s["name"] == "jax.compile"
             and s["attrs"]["fun"] == "probe_cache_fn"]
    print(json.dumps({"ledger": metrics.ledger_snapshot()["xla:probe_cache_fn"],
                      "hits": [s["attrs"]["cache_hit"] for s in spans]}))
""")


def test_a_fresh_cache_compiles_and_a_second_process_fetches(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)

    def child():
        p = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=ROOT,
                           capture_output=True, text=True, timeout=240)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    cold, warm = child(), child()
    assert (cold["ledger"]["compiles"], cold["ledger"]["cache_hits"]) == (1, 0)
    assert (warm["ledger"]["compiles"], warm["ledger"]["cache_hits"]) == (1, 1)
    assert cold["hits"] == [False] and warm["hits"] == [True]
    # the fetch still traced and lowered: the cache key needs the module
    assert warm["ledger"]["traces"] == warm["ledger"]["lowers"] == 1


def test_compile_spans_hang_under_the_open_traced_span():
    f = _fresh("probe_under_traced", 1.25)

    @tracing.traced("probe.caller")
    def caller(x):
        return f(x)

    np.testing.assert_allclose(np.asarray(caller(_x())), _x() * 3.0 + 1.25)
    (root,) = [r for r in metrics.span_roots() if r["name"] == "probe.caller"]
    mine = [c for c in root.get("children", ())
            if c["attrs"].get("fun") == "probe_under_traced"]
    assert [c["name"] for c in mine] == list(PHASES)
    for c in mine:
        assert c["rid"] == root["rid"] and c["tid"] == root["tid"]
        assert root["start_ms"] <= c["start_ms"]
        assert c["start_ms"] + c["dur_ms"] <= (
            root["start_ms"] + root["dur_ms"] + 1e-3)
    assert isinstance(mine[-1]["attrs"]["cache_hit"], bool)
    entry = metrics.ledger_snapshot()["xla:probe_under_traced"]
    assert set(entry) == {"traces", "trace_ms", "lowers", "lower_ms",
                          "compiles", "compile_ms", "cache_hits"}
    assert (entry["traces"], entry["lowers"], entry["compiles"]) == (1, 1, 1)
    assert entry["compile_ms"] > 0
    # a second call compiles nothing and records nothing
    metrics.reset()
    f(_x())
    assert metrics.ledger_snapshot() == {} and metrics.span_roots() == []


def test_nested_traces_count_once():
    inner = _fresh("probe_inner", 0.5)

    def probe_outer(x):
        return inner(x) + inner(x * 2.0)

    jax.jit(probe_outer)(_x())
    traces = [s for s in _flat(metrics.span_roots()) if s["name"] == "jax.trace"]
    assert [s["attrs"]["fun"] for s in traces] == ["probe_outer"]
    assert "children" not in traces[0]      # inner, multiply, add: no spans
    ledger = metrics.ledger_snapshot()
    assert ledger["xla:probe_outer"]["traces"] == 1
    assert "xla:probe_inner" not in ledger


def test_nothing_is_recorded_with_metrics_off():
    metrics.set_enabled(False)
    jax.jit(_fresh("probe_off", 2.0))(_x())
    assert metrics.current_span() is None
    metrics.set_enabled(True)
    assert metrics.ledger_snapshot() == {} and metrics.span_roots() == []


def test_a_compile_under_replay_is_recorded():
    with syncs.replay([]):
        assert not metrics.recording()
        _fresh("probe_replay", 3.0)(_x())
    entry = metrics.ledger_snapshot()["xla:probe_replay"]
    assert entry["compiles"] == 1 and entry["traces"] == 1
    names = [s["name"] for s in _flat(metrics.span_roots())
             if s.get("attrs", {}).get("fun") == "probe_replay"]
    assert sorted(names) == sorted(PHASES)


def test_a_failing_listener_does_not_break_the_call(monkeypatch):
    def boom(_):
        raise RuntimeError("listener fault")
    monkeypatch.setattr(metrics, "_program_name", boom)
    out = _fresh("probe_boom", 4.0)(_x())
    np.testing.assert_allclose(np.asarray(out), _x() * 3.0 + 4.0)
    assert metrics.current_span() is None
    assert metrics.ledger_snapshot() == {}
    assert metrics.counter_value("metrics.compile_listener.faults") >= 3


def test_attrs_and_fields_are_scalars_only():
    f = _fresh("probe_scalars", 5.0)

    @tracing.traced("probe.scalars")
    def caller(x):
        return f(x).sum()

    caller(_x())
    spans = [s for s in _flat(metrics.span_roots()) if s["name"] in PHASES]
    assert spans
    for s in spans:
        assert all(type(v) in (str, bool, int, float)
                   for v in s["attrs"].values()), s
    # and in the live objects, not only their dict form
    with metrics._lock:
        live = list(metrics._roots)
    todo = list(live)
    while todo:
        s = todo.pop()
        todo.extend(s.children)
        if s.name in PHASES:
            assert all(type(v) in (str, bool, int, float)
                       for v in s.attrs.values())
    for plan, entry in metrics.ledger_snapshot().items():
        if plan.startswith("xla:"):
            assert all(type(v) in (int, float) for v in entry.values())


def test_program_names_are_one_for_trace_and_compile():
    assert metrics._program_name("jit(f)") == "f"
    assert metrics._program_name("f") == "f"
    assert metrics._program_name("jit(jit(<lambda>))") == "<lambda>"
    assert metrics._program_name("pmap(step)") == "step"


def test_installing_twice_registers_once():
    from jax._src import monitoring
    metrics.install_compile_listeners()
    metrics.install_compile_listeners()
    assert monitoring.get_scalar_listeners().count(
        metrics._on_phase_start) == 1
    assert monitoring.get_event_time_span_listeners().count(
        metrics._on_phase_end) == 1
    assert monitoring.get_event_listeners().count(metrics._on_event) == 1


def test_a_phase_with_no_start_event_is_recorded_whole():
    event = "/jax/core/compile/backend_compile_duration"
    with metrics.span("probe.outer") as outer:
        time.sleep(0.02)
        end = time.time()
        metrics._on_event("/jax/compilation_cache/cache_hits")
        metrics._on_phase_end(event, end - 0.01, end, fun_name="jit(nostart)")
    (root,) = metrics.span_roots()
    (c,) = root["children"]
    assert (c["name"], c["attrs"]) == ("jax.compile",
                                       {"fun": "nostart", "cache_hit": True})
    assert c["rid"] == outer.rid and abs(c["dur_ms"] - 10.0) < 1e-6
    assert root["start_ms"] <= c["start_ms"] <= root["start_ms"] + root["dur_ms"]
    assert metrics.ledger_snapshot()["xla:nostart"] == {
        "compiles": 1, "compile_ms": pytest.approx(10.0), "cache_hits": 1}


def test_ops_report_sums_the_programs_apart_from_the_plans():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ops_report", os.path.join(ROOT, "tools", "ops_report.py"))
    ops_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ops_report)
    lines = ops_report._ledger_lines({
        "xla:f": {"traces": 1, "trace_ms": 2.0, "lowers": 1, "lower_ms": 3.0,
                  "compiles": 1, "compile_ms": 400.0, "cache_hits": 0},
        "xla:g": {"traces": 2, "trace_ms": 1.0, "lowers": 2, "lower_ms": 1.0,
                  "compiles": 2, "compile_ms": 6.0, "cache_hits": 2},
        "plan1": {"captures": 1, "capture_ms": 9.0, "traces": 1}})
    assert lines[0] == ("  xla: 2 programs  compiles 3 (406.0 ms, 2 fetched "
                        "from the persistent cache)  trace+lower 7.0 ms")
    assert lines[1].startswith("    xla:f  compiles 1 (400.0 ms)")
    assert sum("captures" in ln for ln in lines) == 1     # the plan's alone
