"""AOT plan-artifact store (exec/artifacts.py): zero-compile cold start.

The store's contract is "never wrong, only slower": a persisted artifact
either rehydrates a plan with ZERO capture runs and bit-identical
results, or degrades to the ordinary live capture — corrupted files,
version skew, and stale tapes are all misses, never errors.  These tests
hold every leg:

* round-trip — tape serialize/deserialize bit-identity (including >2^32
  sizes), atomic files, manifest ranking.
* geometry — pow2 bucketing folds nearby dataset sizes onto one key,
  exact mode keeps them apart, opaque objects make the key unstable and
  unpersistable.
* fallback — corrupted artifact and env/version skew fall back to live
  capture with an ``aot.reject`` count; a stale tape (same bucket,
  different resolved sizes) raises through the checked run into a
  recapture whose write-back overwrites the artifact.
* integration — a populated store serves a fresh PlanCache (and a full
  QueryScheduler) with ``compiled.capture == 0``; the scheduler warm-up
  thread pre-hydrates manifest entries at startup.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu import types as T
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.exec import artifacts
from spark_rapids_jni_tpu.exec.plan_cache import PlanCache
from spark_rapids_jni_tpu.ops import filter as F
from spark_rapids_jni_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _metrics_on():
    metrics.set_enabled(True)
    metrics.reset()
    yield
    metrics.reset()
    metrics.set_enabled(None)


@pytest.fixture
def aot_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "aot")
    monkeypatch.setenv("SRJT_AOT_DIR", d)
    return d


def _mktab(n, seed=7):
    rng = np.random.default_rng(seed)
    return {"t": Table([
        Column(T.DType(T.TypeId.INT32),
               jnp.asarray(rng.integers(0, 50, n).astype(np.int32))),
        Column(T.DType(T.TypeId.FLOAT32),
               jnp.asarray(rng.standard_normal(n).astype(np.float32)))])}


def _q_filter(tbls):
    # tape-bearing query: the compaction count resolves through the
    # syncs funnel, so the capture tape is non-empty and data-determined
    t = tbls["t"]
    return F.apply_boolean_mask(t, t.columns[0].data < 25)


def _canon(result):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(result)]


def _same(a, b):
    return len(a) == len(b) and all(
        x.shape == y.shape and np.array_equal(x, y)
        for x, y in zip(a, b))


# --- round-trip --------------------------------------------------------------


def test_tape_roundtrip_bit_identity(aot_dir):
    store = artifacts.get_store()
    geom = artifacts.geometry_key(_mktab(100))
    tape = (0, 1, 3, 2**40 + 17, 7)     # >2^32: JSON ints stay exact
    assert store.put("planA", "v1", geom, tape, name="qa", cost_ms=9.5)
    assert store.lookup("planA", "v1", geom) == tape
    # the on-disk document is plain versioned JSON, bit-exact through a
    # cold read (drop the in-memory copy first)
    store._mem.clear()
    assert store.lookup("planA", "v1", geom) == tape
    with open(store.path_for("planA", "v1", geom)) as f:
        doc = json.load(f)
    assert doc["version"] == artifacts.STORE_VERSION
    assert tuple(doc["tape"]) == tape
    assert doc["env"] == artifacts.env_fingerprint()


def test_manifest_ranked_by_cost(aot_dir):
    store = artifacts.get_store()
    geom = artifacts.geometry_key(_mktab(100))
    store.put("cheap", "", geom, (1,), cost_ms=2.0)
    store.put("dear", "", geom, (2,), cost_ms=50.0)
    store.put("mid", "", geom, (3,), cost_ms=10.0)
    assert [e["plan"] for _, e in store.manifest_entries()] == \
        ["dear", "mid", "cheap"]


def test_variant_and_key_isolation(aot_dir):
    store = artifacts.get_store()
    geom = artifacts.geometry_key(_mktab(100))
    store.put("p", "", geom, (1, 2))
    assert store.lookup("p", "sorted", geom) is None
    assert store.lookup("other", "", geom) is None
    assert store.lookup("p", "", geom) == (1, 2)


# --- geometry keys -----------------------------------------------------------


def test_geometry_pow2_bucketing():
    a, b = _mktab(900), _mktab(1000)
    # both bucket to 1024 → shared artifact key
    assert artifacts.geometry_key(a, buckets=True) == \
        artifacts.geometry_key(b, buckets=True)
    # exact mode keeps them apart
    assert artifacts.geometry_key(a, buckets=False) != \
        artifacts.geometry_key(b, buckets=False)
    # a true bucket boundary still separates (1024 → 1024, 1025 → 2048)
    assert artifacts.geometry_key(_mktab(1024), buckets=True) != \
        artifacts.geometry_key(_mktab(1025), buckets=True)
    # dtype is part of the geometry even inside one bucket
    c = _mktab(1000)
    c["t"].columns[0].data = c["t"].columns[0].data.astype(jnp.int64)
    assert artifacts.geometry_key(c, buckets=True) != \
        artifacts.geometry_key(b, buckets=True)


def test_geometry_unstable_for_opaque_objects():
    class Opaque:
        pass
    tables = {"t": _mktab(64)["t"], "cfg": Opaque()}
    # id()-keyed entries are process-local: no stable cross-process key
    assert artifacts.geometry_key(tables) is None
    assert metrics.counter_value("aot.unstable_key") >= 1


# --- fallback: corrupt / skew / stale ---------------------------------------


def test_corrupt_artifact_degrades_to_capture(aot_dir):
    store = artifacts.get_store()
    tables = _mktab(500)
    pc = PlanCache()
    out = _canon(pc.run("qf", _q_filter, tables))
    geom = artifacts.geometry_key(tables)
    path = store.path_for("qf", "", geom)
    assert os.path.exists(path)
    with open(path, "w") as f:
        f.write('{"version": 1, "tape": [1, 2')     # torn write simulation
    store._mem.clear()
    metrics.reset()
    out2 = _canon(PlanCache().run("qf", _q_filter, tables))
    assert _same(out, out2)
    assert metrics.counter_value("compiled.capture") == 1   # live fallback
    assert metrics.counter_value("compiled.rehydrate") == 0
    assert metrics.counter_value("aot.reject") >= 1
    # the recapture's write-back healed the artifact in place
    store._mem.clear()
    assert store.lookup("qf", "", geom) is not None


def test_version_skew_rejected(aot_dir):
    store = artifacts.get_store()
    geom = artifacts.geometry_key(_mktab(100))
    store.put("p", "", geom, (5, 6))
    path = store.path_for("p", "", geom)
    with open(path) as f:
        doc = json.load(f)
    doc["env"] = "store1;jax0.0.0;pkg0.0.0"
    with open(path, "w") as f:
        json.dump(doc, f)
    store._mem.clear()
    assert store.lookup("p", "", geom) is None
    assert metrics.counter_value("aot.reject") >= 1
    doc["env"] = artifacts.env_fingerprint()
    doc["version"] = artifacts.STORE_VERSION + 1
    with open(path, "w") as f:
        json.dump(doc, f)
    assert store.lookup("p", "", geom) is None


def test_stale_tape_rehydrate_recaptures(aot_dir):
    # an artifact whose tape disagrees with the live data's resolved
    # sizes must degrade to a live capture with identical results — and
    # its write-back overwrites the stale artifact for the next process
    store = artifacts.get_store()
    tables = _mktab(500)
    geom = artifacts.geometry_key(tables)
    store.put("qf", "", geom, (3,))             # wrong resolved size
    out = _canon(PlanCache().run("qf", _q_filter, tables))
    assert _same(out, _canon(_q_filter(tables)))
    assert metrics.counter_value("compiled.rehydrate") == 1
    assert metrics.counter_value("exec.plan_cache.stale") == 1
    assert metrics.counter_value("compiled.capture") == 1
    # healed: a fresh cache now rehydrates with zero captures
    metrics.reset()
    store._mem.clear()
    out2 = _canon(PlanCache().run("qf", _q_filter, tables))
    assert _same(out, out2)
    assert metrics.counter_value("compiled.capture") == 0
    assert metrics.counter_value("compiled.rehydrate") == 1


def test_stale_wrong_length_tape_recaptures(aot_dir):
    # replay RuntimeErrors (tape too short/long for the plan's resolution
    # sites) must surface as StaleTapeError → recapture, not crash
    tables = _mktab(500)
    geom = artifacts.geometry_key(tables)
    store = artifacts.get_store()
    store.put("qf", "", geom, ())               # empty tape, plan has syncs
    out = _canon(PlanCache().run("qf", _q_filter, tables))
    assert _same(out, _canon(_q_filter(tables)))
    assert metrics.counter_value("exec.plan_cache.stale") == 1
    assert metrics.counter_value("compiled.capture") == 1


# --- integration: plan cache + scheduler ------------------------------------


def test_plan_cache_zero_capture_from_store(aot_dir):
    tables = _mktab(500)
    oracle = _canon(PlanCache().run("qf", _q_filter, tables))
    assert metrics.counter_value("compiled.capture") == 1
    assert metrics.counter_value("aot.write") == 1
    # fresh cache, populated store: the cold-start contract is ZERO
    # capture runs and bit-identical results
    metrics.reset()
    pc = PlanCache()
    out = _canon(pc.run("qf", _q_filter, tables))
    assert _same(oracle, out)
    assert metrics.counter_value("compiled.capture") == 0
    assert metrics.counter_value("compiled.rehydrate") == 1
    assert metrics.counter_value("exec.plan_cache.aot_hit") == 1
    assert metrics.counter_value("aot.hit") >= 1      # the store served it
    # the rehydrated plan's ledger carries cold-start attribution
    # (CompiledQuery keys the ledger on the query function's name)
    led = metrics.ledger_snapshot().get("_q_filter", {})
    assert led.get("rehydrates") == 1
    assert "captures" not in led


_FRESH = """
import json, sys
sys.path.insert(0, {tests!r})
import test_artifacts as t
t.metrics.set_enabled(True)
out = t._canon(t.PlanCache().run("qf", t._q_filter, t._mktab(500)))
print(json.dumps({{"out": [a.tolist() for a in out],
                  "capture": t.metrics.counter_value("compiled.capture"),
                  "rehydrate": t.metrics.counter_value("compiled.rehydrate"),
                  "aot_hit": t.metrics.counter_value("exec.plan_cache.aot_hit"),
                  "store_hit": t.metrics.counter_value("aot.hit")}}))
"""


def test_fresh_interpreter_serves_zero_capture_from_store(aot_dir):
    # the store's keys must not depend on anything of the process that
    # wrote them (ids, hash seeds): a second interpreter finds the artifact
    here = os.path.dirname(os.path.abspath(__file__))
    oracle = _canon(PlanCache().run("qf", _q_filter, _mktab(500)))
    assert metrics.counter_value("aot.write") == 1
    proc = subprocess.run(
        [sys.executable, "-c", _FRESH.format(tests=here)],
        cwd=os.path.dirname(here), env=dict(os.environ, SRJT_AOT_DIR=aot_dir),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert (got["capture"], got["rehydrate"], got["aot_hit"]) == (0, 1, 1)
    assert got["store_hit"] >= 1
    assert _same(oracle, [np.asarray(a, o.dtype)
                          for a, o in zip(got["out"], oracle)])


def test_scheduler_serves_zero_capture_and_warms_up(aot_dir, monkeypatch):
    from spark_rapids_jni_tpu import exec as xc
    monkeypatch.setenv("SRJT_AOT_WARMUP", "4")
    tables = _mktab(800)
    with xc.QueryScheduler(workers=2) as sched:
        oracle = _canon(sched.run("qf", _q_filter, tables))
    assert metrics.counter_value("compiled.capture") == 1
    metrics.reset()
    artifacts.get_store()._mem.clear()
    with xc.QueryScheduler(workers=2) as sched:
        # the startup warm-up thread pre-hydrates the manifest entries
        assert sched._warmup_thread is not None
        sched._warmup_thread.join(timeout=30)
        assert metrics.counter_value("aot.preloaded") >= 1
        out = _canon(sched.run("qf", _q_filter, tables))
    assert _same(oracle, out)
    assert metrics.counter_value("compiled.capture") == 0
    assert metrics.counter_value("compiled.rehydrate") == 1


def test_disabled_store_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("SRJT_AOT_DIR", raising=False)
    assert not artifacts.enabled()
    assert artifacts.get_store() is None
    tables = _mktab(300)
    out = _canon(PlanCache().run("qf", _q_filter, tables))
    assert _same(out, _canon(_q_filter(tables)))
    assert metrics.counter_value("aot.write") == 0
    assert list(tmp_path.iterdir()) == []
