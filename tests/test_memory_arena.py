"""HBM arena subsystem tests: slab pool, budgets, spill, differential.

Covers the ISSUE 3 acceptance surface: slab reuse + size-class alignment,
typed budget exhaustion (:class:`HbmBudgetExceeded`), bit-exact
spill→fault-back round trips (raw payloads AND through the join
build-index cache), and differential runs of TPC-DS queries under a tiny
``SRJT_HBM_BUDGET`` — budgeted results must match unbudgeted bit-for-bit
while recording at least one spill.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import tpcds_data
from spark_rapids_jni_tpu.memory import (HbmBudgetExceeded, arena, budget,
                                         spill)
from spark_rapids_jni_tpu.models import tpcds
from spark_rapids_jni_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _arena_sandbox():
    """Each test starts with a clean, ENABLED arena and leaves no trace:
    env knobs, ledgers, pools, registry and metrics all restored."""
    saved = {k: os.environ.get(k)
             for k in ("SRJT_HBM_ARENA", "SRJT_HBM_BUDGET",
                       "SRJT_INDEX_CACHE_CAP", "SRJT_ARENA_ZEROS_CAP")}
    os.environ["SRJT_HBM_ARENA"] = "1"
    os.environ.pop("SRJT_HBM_BUDGET", None)
    budget.set_enabled(None)
    arena.reset()
    spill.reset()
    budget.reset()
    metrics.reset()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    arena.reset()
    spill.reset()
    budget.reset()
    metrics.reset()
    metrics.set_enabled(None)
    budget.set_enabled(None)
    from spark_rapids_jni_tpu.ops import join_plan
    join_plan._INDEX_CACHE.clear()


# --- size classes / slab pool -----------------------------------------------


def test_size_class_rounding():
    assert arena.size_class(1) == 256          # floor
    assert arena.size_class(256) == 256
    assert arena.size_class(257) == 512
    assert arena.size_class(1000) == 1024
    assert arena.size_class(1 << 20) == 1 << 20
    for n in (3, 900, 5000, 123456):
        cls = arena.size_class(n)
        assert cls >= n and cls % 256 == 0     # alignment invariant


def test_slab_identity_reuse():
    s1 = arena.alloc(1000, tag="t")
    assert s1.nbytes == 1024 and s1.data.nbytes == 1024
    buf = s1.data
    arena.free(s1)
    s2 = arena.alloc(900, tag="t")             # same size class → same slab
    assert s2.data is buf
    arena.free(s2)
    assert arena.stats()["pooled_bytes"] == 1024
    assert arena.trim() == 1024
    assert arena.stats()["pooled_bytes"] == 0


def test_double_free_is_noop():
    s = arena.alloc(256)
    arena.free(s)
    arena.free(s)
    assert arena.stats()["pooled_bytes"] == 256


def test_zeros_pooling_identity():
    a = arena.zeros(128, jnp.int32)
    b = arena.zeros(128, jnp.int32)
    assert a is b
    assert not np.asarray(a).any()
    c = arena.zeros((128,), jnp.int64)
    assert c is not a


# --- budgets ----------------------------------------------------------------


def test_parse_bytes():
    assert budget.parse_bytes("512") == 512
    assert budget.parse_bytes("4k") == 4096
    assert budget.parse_bytes("2m") == 2 << 20
    assert budget.parse_bytes("1g") == 1 << 30
    assert budget.parse_bytes("1.5k") == 1536
    assert budget.parse_bytes("") is None
    assert budget.parse_bytes("none") is None
    assert budget.parse_bytes(4096) == 4096


def test_budget_exhaustion_raises_typed():
    os.environ["SRJT_HBM_BUDGET"] = "4k"
    with pytest.raises(HbmBudgetExceeded) as ei:
        arena.alloc(1 << 20, tag="big")        # strict admission
    err = ei.value
    assert err.requested == 1 << 20
    assert err.limit == 4096
    assert err.tag == "arena.big"
    assert budget.in_use() == 0                # denied charge rolled back


def test_soft_reserve_completes_over_budget():
    os.environ["SRJT_HBM_BUDGET"] = "1k"
    metrics.set_enabled(True)
    with arena.reserve(1 << 20, tag="join.expand"):
        assert budget.in_use() == 1 << 20      # stands over-limit
    assert budget.in_use() == 0
    snap = metrics.snapshot()["counters"]
    assert snap.get("arena.budget.soft_over", 0) >= 1


def test_query_budget_scopes_limit():
    with budget.query_budget("q", limit_bytes="2k") as q:
        assert budget.limit_now() == 2048
        with pytest.raises(HbmBudgetExceeded) as ei:
            arena.alloc(8192, tag="x")
        assert ei.value.query == "q"
        assert q.peak == 0                     # denied charge left no peak
    assert budget.limit_now() is None


def test_reserve_noop_when_disabled():
    budget.set_enabled(False)
    assert arena.reserve(1 << 30) is arena.reserve(1 << 30)  # shared no-op
    with arena.reserve(1 << 30):
        assert budget.in_use() == 0


# --- spill / fault-back -----------------------------------------------------


def test_spill_faultback_bit_exact():
    rng = np.random.default_rng(0)
    payloads = {
        "i64": jnp.asarray(rng.integers(-2**62, 2**62, 1000, dtype=np.int64)),
        "u32": jnp.asarray(rng.integers(0, 2**32, 777, dtype=np.uint32)
                           .reshape(-1, 7)),
        "none": None,
    }
    want = {k: (None if v is None else np.asarray(v))
            for k, v in payloads.items()}
    sp = spill.SpillableArrays("t", payloads)
    assert not sp.spilled
    freed = sp.spill()
    assert sp.spilled and freed == sp.nbytes > 0
    assert sp.spill() == 0                     # idempotent
    back = sp.get()
    assert not sp.spilled
    for k, w in want.items():
        if w is None:
            assert back[k] is None
        else:
            np.testing.assert_array_equal(np.asarray(back[k]), w)


def test_reclaim_spills_lru_first():
    os.environ["SRJT_HBM_BUDGET"] = "1m"
    order = []
    a1 = spill.SpillableArrays("a", {"x": jnp.arange(100)})
    a2 = spill.SpillableArrays("b", {"x": jnp.arange(200)})
    spill.register("k1", a1.nbytes, "a",
                   lambda: (order.append("k1"), a1.spill())[1])
    spill.register("k2", a2.nbytes, "b",
                   lambda: (order.append("k2"), a2.spill())[1])
    spill.touch("k1")                          # k2 becomes LRU
    freed = spill.reclaim(1)
    assert order == ["k2"] and freed > 0
    assert spill.resident_count() == 1


def test_join_index_spill_faultback_identical():
    """Force the cached build index to spill; the next join must fault it
    back and produce identical indices (and identity on the hit after)."""
    from spark_rapids_jni_tpu.ops import join_plan
    keys = jnp.asarray(np.arange(4096, dtype=np.int64) % 97)
    ix1 = join_plan.build_index(keys, None, True)
    assert join_plan.build_index(keys, None, True) is ix1   # plain hit
    assert spill.resident_count() == 1
    assert spill.reclaim(1) > 0                # spill the resident
    ix2 = join_plan.build_index(keys, None, True)
    assert ix2 is not ix1
    assert (ix2.kind, ix2.n_valid, ix2.kmin, ix2.span, ix2.unique) == \
           (ix1.kind, ix1.n_valid, ix1.kmin, ix1.span, ix1.unique)
    for lane in ("row_ids", "sorted_keys", "lut_lo", "lut_cnt"):
        a, b = getattr(ix1, lane), getattr(ix2, lane)
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert join_plan.build_index(keys, None, True) is ix2   # identity again


def test_index_cache_capacity_eviction():
    from spark_rapids_jni_tpu.ops import join_plan
    os.environ["SRJT_INDEX_CACHE_CAP"] = "1k"
    metrics.set_enabled(True)
    k1 = jnp.asarray(np.arange(4096, dtype=np.int64) % 31)
    k2 = jnp.asarray(np.arange(4096, dtype=np.int64) % 13)
    join_plan.build_index(k1, None, True)
    join_plan.build_index(k2, None, True)      # over cap → k1 evicted
    assert metrics.snapshot()["counters"].get(
        "join.build_index.evictions", 0) >= 1
    assert join_plan._INDEX_CACHE.device_bytes() <= \
        join_plan._index_nbytes(join_plan.build_index(k2, None, True))


# --- differential: TPC-DS under a tiny budget -------------------------------


@pytest.fixture(scope="module")
def _tpcds_tables():
    files = tpcds_data.generate(n_sales=20_000, n_items=300, seed=11)
    return tpcds.load_tables(files)


@pytest.mark.parametrize("qname", ["q3", "q42", "q52"])
def test_tpcds_differential_under_tiny_budget(_tpcds_tables, qname):
    from spark_rapids_jni_tpu.ops import join_plan
    tables = _tpcds_tables
    # budgeted run FIRST (cold caches: the sandbox fixture cleared the
    # index cache and spill registry) — each query joins twice, so the
    # second join's resident registration pushes past the (deliberately
    # absurd) 256-byte budget and spills the first join's cached index
    join_plan._INDEX_CACHE.clear()
    os.environ["SRJT_HBM_BUDGET"] = "256"
    budget.set_enabled(None)
    assert budget.active()
    metrics.set_enabled(True)
    with budget.query_budget(qname):
        got = tpcds.QUERIES[qname](tables)
    snap = metrics.snapshot()["counters"]
    assert snap.get("arena.spill.events", 0) >= 1, snap
    # the spill and the query's root are spans, so a trace carries both
    assert {"arena.spill", f"query:{qname}"} <= set(metrics.stage_breakdown())

    budget.set_enabled(False)
    metrics.set_enabled(False)
    expect = tpcds.QUERIES[qname](tables)
    assert got.num_rows == expect.num_rows
    for i in range(len(expect.columns)):
        a, b = expect[i], got[i]
        if a.dtype.id.name == "STRING":
            assert a.to_pylist() == b.to_pylist()
        else:
            np.testing.assert_array_equal(a.to_numpy(), b.to_numpy())
