"""Static-analysis + runtime-sanitizer tests.

Two layers, matching ``spark_rapids_jni_tpu/analysis/``:

* the AST passes — seeded fixture trees prove each rule fires with the
  right (file, line, rule id), and a self-clean check proves the REAL
  tree lints to zero findings modulo ``ci/lint_baseline.json`` (the
  premerge gate ``ci/lint_smoke.sh`` enforces the same invariant).
* the runtime sanitizers — the lock-order watchdog detects a real
  inversion taken by two call sites (incident mode records it, strict
  mode raises), and the retrace tripwire fires on a second trace of the
  same plan key unless wrapped in ``allow_retrace``.

Plus the regressions for the genuine findings this linter surfaced:
the ``utils.syncs`` global counter and the ``exec.placement.Replica``
counters are hammered from threads and must not lose updates.
"""

import os
import subprocess
import sys
import textwrap
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "spark_rapids_jni_tpu"

from spark_rapids_jni_tpu.analysis import (  # noqa: E402
    concurrency, core, knobpass, sanitize, tracepass)


# --------------------------------------------------------------------------
# fixture helpers: build a tiny package tree and lint it
# --------------------------------------------------------------------------

def _write(tmp_path, rel, body):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return rel


def _lint(tmp_path):
    sources = core.collect_sources(str(tmp_path), subdirs=(PKG,))
    return sources, (concurrency.run(sources) + tracepass.run(sources))


def _findings(findings, rule):
    return [f for f in findings if f.rule == rule]


# --------------------------------------------------------------------------
# concurrency pass
# --------------------------------------------------------------------------

def test_lock_order_inversion_detected(tmp_path):
    rel = _write(tmp_path, f"{PKG}/memory/fix.py", """\
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def ab():
            with A:
                with B:
                    pass

        def ba():
            with B:
                with A:
                    pass
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "conc-lock-order")
    assert len(hits) == 1, findings
    f = hits[0]
    assert f.path == rel
    # anchored at the lexically first edge in the cycle: the inner
    # `with B:` of ab() on line 8
    assert f.line == 8
    assert "memory.fix.A" in f.message and "memory.fix.B" in f.message


def test_lock_order_inversion_through_calls(tmp_path):
    # the inversion only exists inter-procedurally: f holds A and calls
    # g (which takes B); h nests B->A directly
    _write(tmp_path, f"{PKG}/exec/fix2.py", """\
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def g():
            with B:
                pass

        def f():
            with A:
                g()

        def h():
            with B:
                with A:
                    pass
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "conc-lock-order")
    assert len(hits) == 1, findings
    assert "exec.fix2.A" in hits[0].message


def test_lock_order_clean_tree_has_no_cycle(tmp_path):
    _write(tmp_path, f"{PKG}/memory/ok.py", """\
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def one():
            with A:
                with B:
                    pass

        def two():
            with A:
                with B:
                    pass
    """)
    _, findings = _lint(tmp_path)
    assert not _findings(findings, "conc-lock-order"), findings


def test_mixed_guard_detected(tmp_path):
    rel = _write(tmp_path, f"{PKG}/exec/fix3.py", """\
        import threading

        class Counter:
            def __init__(self):
                self._mu = threading.Lock()
                self.n = 0

            def bump(self):
                with self._mu:
                    self.n += 1

            def racy_reset(self):
                self.n = 0
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "conc-mixed-guard")
    assert len(hits) == 1, findings
    f = hits[0]
    assert f.path == rel and f.line == 13
    assert "self.n" in f.message and "racy_reset" in f.message


def test_global_augassign_detected(tmp_path):
    rel = _write(tmp_path, f"{PKG}/utils/fix4.py", """\
        import threading

        _count = 0
        _mu = threading.Lock()

        def bump_racy():
            global _count
            _count += 1

        def bump_ok():
            global _count
            with _mu:
                _count += 1
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "conc-global-augassign")
    assert len(hits) == 1, findings
    assert hits[0].path == rel and hits[0].line == 8
    assert "_count" in hits[0].message


# --------------------------------------------------------------------------
# retrace/host-sync pass
# --------------------------------------------------------------------------

def test_item_in_traced_scope_detected(tmp_path):
    rel = _write(tmp_path, f"{PKG}/ops/fix5.py", """\
        import jax.numpy as jnp

        def total_width(col):
            return col.item()
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "trace-host-sync")
    assert len(hits) == 1, findings
    assert hits[0].path == rel and hits[0].line == 4
    assert ".item()" in hits[0].message


def test_int_over_device_expr_detected_and_scalar_sanctioned(tmp_path):
    _write(tmp_path, f"{PKG}/rowconv/fix6.py", """\
        import jax.numpy as jnp
        from ..utils import syncs

        def bad(col):
            return int(jnp.max(col))

        def good(col):
            return syncs.scalar(jnp.max(col))

        def host_ok(offs_np):
            return int(offs_np.max(initial=0))
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "trace-host-sync")
    assert len(hits) == 1, findings          # only `bad` fires
    assert hits[0].line == 5


def test_branch_on_device_expr_detected(tmp_path):
    _write(tmp_path, f"{PKG}/ops/fix7.py", """\
        import jax.numpy as jnp

        def clamp(col):
            if jnp.any(col < 0):
                return jnp.abs(col)
            return col
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "trace-branch")
    assert len(hits) == 1 and hits[0].line == 4, findings


def test_set_iteration_in_fingerprint_detected(tmp_path):
    _write(tmp_path, f"{PKG}/plan/fix8.py", """\
        def plan_fingerprint(cols):
            parts = []
            for name in {c.name for c in cols}:
                parts.append(name)
            return tuple(parts)

        def not_a_key_fn(cols):
            for name in {c.name for c in cols}:
                pass
    """)
    _, findings = _lint(tmp_path)
    hits = _findings(findings, "trace-iter")
    assert len(hits) == 1 and hits[0].line == 3, findings
    assert "plan_fingerprint" in hits[0].message


def test_inline_suppression_silences_finding(tmp_path):
    _write(tmp_path, f"{PKG}/ops/fix9.py", """\
        def pull(x):
            return x.item()  # srjt-lint: disable=trace-host-sync
    """)
    sources, findings = _lint(tmp_path)
    by_rel = {s.rel: s for s in sources}
    kept = core.filter_findings(findings, by_rel, baseline=None)
    assert not _findings(kept, "trace-host-sync"), kept


# --------------------------------------------------------------------------
# knob pass + registry
# --------------------------------------------------------------------------

def test_raw_environ_read_detected(tmp_path):
    rel = _write(tmp_path, f"{PKG}/exec/fix10.py", """\
        import os

        def enabled():
            return os.environ.get("SRJT_FIXTURE_KNOB", "0") == "1"
    """)
    sources = core.collect_sources(str(tmp_path), subdirs=(PKG,))
    findings = knobpass.run(sources, registered=set())
    hits = _findings(findings, "knob-env")
    assert len(hits) == 1, findings
    assert hits[0].path == rel and hits[0].line == 4
    assert "SRJT_FIXTURE_KNOB" in hits[0].message


def test_unregistered_knob_detected(tmp_path):
    rel = _write(tmp_path, f"{PKG}/exec/fix11.py", """\
        from ..utils import knobs

        def depth():
            return knobs.get("SRJT_NOT_A_REAL_KNOB")
    """)
    sources = core.collect_sources(str(tmp_path), subdirs=(PKG,))
    registered = set(knobpass.load_registry(REPO))
    findings = knobpass.run(sources, registered)
    hits = _findings(findings, "knob-unregistered")
    assert len(hits) == 1, findings
    assert hits[0].path == rel and hits[0].line == 4
    assert "SRJT_NOT_A_REAL_KNOB" in hits[0].message


def test_undocumented_knob_detected():
    sources = []
    findings = knobpass.run(sources, registered={"SRJT_GHOST_KNOB"},
                            readme_text="no table here")
    hits = _findings(findings, "knob-undoc")
    assert len(hits) == 1 and hits[0].path == "README.md", findings


def test_registry_semantics(monkeypatch):
    from spark_rapids_jni_tpu.utils import knobs
    monkeypatch.delenv("SRJT_EXEC_PREFETCH_DEPTH", raising=False)
    assert knobs.get("SRJT_EXEC_PREFETCH_DEPTH") == 2   # default
    monkeypatch.setenv("SRJT_EXEC_PREFETCH_DEPTH", "5")
    assert knobs.get("SRJT_EXEC_PREFETCH_DEPTH") == 5   # re-read per call
    # on-unless-off boolean family
    monkeypatch.delenv("SRJT_FLIGHT", raising=False)
    assert knobs.get("SRJT_FLIGHT") is True
    monkeypatch.setenv("SRJT_FLIGHT", "off")
    assert knobs.get("SRJT_FLIGHT") is False
    # optional float: unset -> None
    monkeypatch.delenv("SRJT_EXEC_DEADLINE", raising=False)
    assert knobs.get("SRJT_EXEC_DEADLINE") is None
    monkeypatch.setenv("SRJT_EXEC_DEADLINE", "1.5")
    assert knobs.get("SRJT_EXEC_DEADLINE") == 1.5
    with pytest.raises(KeyError):
        knobs.get("SRJT_NEVER_REGISTERED")
    assert knobs.is_registered("SRJT_EXEC")
    assert not knobs.is_registered("SRJT_NEVER_REGISTERED")


def test_every_registered_knob_documented():
    from spark_rapids_jni_tpu.utils import knobs
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    missing = [k for k in knobs.REGISTRY if k not in readme]
    assert not missing, f"knobs missing from README: {missing}"


def test_every_registered_knob_is_read_by_the_package():
    # a knob that only a script outside the package reads is that script's
    # option, not the program's: it does not belong in the registry
    text = "\n".join(
        src.text for src in core.collect_sources(
            REPO, subdirs=("spark_rapids_jni_tpu",))
        if src.rel != knobpass._KNOBS_REL)
    unread = [k for k in knobpass.load_registry(REPO)
              if f'"{k}"' not in text]
    assert not unread, f"registered, read by no file of the package: {unread}"


# --------------------------------------------------------------------------
# self-clean: the real tree lints to zero modulo the checked-in baseline
# --------------------------------------------------------------------------

def test_real_tree_is_clean_modulo_baseline():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "srjt_lint.py"),
         "--root", REPO,
         "--baseline", os.path.join(REPO, "ci", "lint_baseline.json")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, \
        f"non-baselined findings:\n{proc.stdout}\n{proc.stderr}"


# --------------------------------------------------------------------------
# runtime sanitizer: lock-order watchdog
# --------------------------------------------------------------------------

@pytest.fixture
def sanitizer(monkeypatch):
    monkeypatch.setenv("SRJT_SANITIZE", "1")
    sanitize.reset()
    yield sanitize
    sanitize.reset()


def test_watchdog_records_inversion(sanitizer):
    a = sanitize.tracked_lock("test.wd.a")
    b = sanitize.tracked_lock("test.wd.b")
    with a:
        with b:
            pass
    with b:
        with a:                      # inversion: established order is a->b
            pass
    vio = sanitize.violations()
    assert len(vio) == 1, vio
    assert vio[0]["acquiring"] == "test.wd.a"
    assert vio[0]["while_holding"] == "test.wd.b"
    assert "test.wd" in vio[0]["prior_stack"] or vio[0]["prior_stack"]


def test_watchdog_strict_raises(monkeypatch):
    monkeypatch.setenv("SRJT_SANITIZE", "strict")
    sanitize.reset()
    try:
        a = sanitize.tracked_lock("test.strict.a")
        b = sanitize.tracked_lock("test.strict.b")
        with a:
            with b:
                pass
        with pytest.raises(sanitize.LockOrderError):
            with b:
                with a:
                    pass
        # the failed acquisition must not leak into the held stack
        with a:
            with b:
                pass
    finally:
        sanitize.reset()


def test_watchdog_reentrant_and_consistent_order_ok(sanitizer):
    r = sanitize.tracked_rlock("test.wd.r")
    inner = sanitize.tracked_lock("test.wd.inner")
    for _ in range(3):
        with r:
            with r:                  # reentrant: no edge
                with inner:
                    pass
    assert not sanitize.violations()


def test_watchdog_off_returns_plain_primitives(monkeypatch):
    monkeypatch.setenv("SRJT_SANITIZE", "0")
    lk = sanitize.tracked_lock("test.off")
    assert type(lk) is threading.Lock().__class__


def test_watchdog_cross_thread_edges(sanitizer):
    # thread 1 establishes a->b; thread 2 takes b->a: classic deadlock
    # candidate that never actually deadlocks in the test
    a = sanitize.tracked_lock("test.xt.a")
    b = sanitize.tracked_lock("test.xt.b")

    def t1():
        with a:
            with b:
                pass

    th = threading.Thread(target=t1)
    th.start()
    th.join()
    with b:
        with a:
            pass
    assert len(sanitize.violations()) == 1


# --------------------------------------------------------------------------
# runtime sanitizer: retrace tripwire
# --------------------------------------------------------------------------

def test_retrace_tripwire(sanitizer):
    sanitize.note_trace("plan#t1")              # warmup
    assert not sanitize.retrace_events()
    sanitize.note_trace("plan#t1")              # unexpected retrace
    events = sanitize.retrace_events()
    assert len(events) == 1 and events[0]["key"] == "plan#t1"
    assert events[0]["count"] == 2


def test_retrace_allowed_inside_scope(sanitizer):
    sanitize.note_trace("plan#t2")
    with sanitize.allow_retrace():
        sanitize.note_trace("plan#t2")          # vmap-build style: fine
    assert not sanitize.retrace_events()
    sanitize.note_trace("plan#t2")              # outside the scope: trips
    assert len(sanitize.retrace_events()) == 1


def test_retrace_strict_raises(monkeypatch):
    monkeypatch.setenv("SRJT_SANITIZE", "strict")
    sanitize.reset()
    try:
        sanitize.note_trace("plan#t3")
        with pytest.raises(sanitize.RetraceError):
            sanitize.note_trace("plan#t3")
    finally:
        sanitize.reset()


def test_compiled_query_warm_replay_does_not_trip(monkeypatch):
    monkeypatch.setenv("SRJT_SANITIZE", "strict")
    sanitize.reset()
    try:
        import jax.numpy as jnp
        from spark_rapids_jni_tpu.models import compiled as C

        def q(tbls):
            return jnp.sum(tbls["x"] * 2)

        tables = {"x": jnp.arange(8, dtype=jnp.int32)}
        cq = C.compile_query(q, tables)
        first = cq.run(tables)                  # warmup trace
        for _ in range(3):                      # steady loop: no retrace
            assert int(cq.run_unchecked(tables)) == int(first)
    finally:
        sanitize.reset()


# --------------------------------------------------------------------------
# regressions for the genuine findings fixed alongside the linter
# --------------------------------------------------------------------------

def test_sync_count_thread_safe():
    from spark_rapids_jni_tpu.utils import syncs
    syncs.reset_sync_count()
    n_threads, n_iter = 8, 500
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for _ in range(n_iter):
            syncs.scalar(7)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert syncs.reset_sync_count() == n_threads * n_iter


def test_replica_counters_thread_safe():
    from spark_rapids_jni_tpu.exec.placement import Replica

    class FakeDevice:
        platform, id = "cpu", 0

    rep = Replica(0, FakeDevice())
    n_threads, n_iter = 8, 400
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait()
        for _ in range(n_iter):
            rep.note_active()
            rep.note_completed()
            rep.note_active(-1)
            rep.note_probe_failed()
            rep.note_probe_ok()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rep.active == 0
    assert rep.completed == n_threads * n_iter
    assert rep.fail_streak == 0
