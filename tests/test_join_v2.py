"""Join engine v2 differential tests (ops/join_plan.py).

Three contracts:
* the dense direct-lookup engine produces BIT-IDENTICAL join indices to
  the sort-probe engine on every overlapping input (null keys, duplicate
  build keys, empty build side, inner/left/semi/anti) — pinned with
  ``join_plan.force_engine``;
* the build-side index cache returns the same physical index (and thus
  identical join indices) when the same key buffers join again;
* ``join_aggregate`` fusion (unique-build, weighted, and fallback paths)
  matches the unfused ``groupby_aggregate(inner_join(...))`` exactly.
"""

import numpy as np
import pandas as pd
import pytest
import jax.numpy as jnp

import spark_rapids_jni_tpu as sr
from spark_rapids_jni_tpu import Column, Table
from spark_rapids_jni_tpu import ops
from spark_rapids_jni_tpu.ops import join_plan
from spark_rapids_jni_tpu.ops.join import join_indices

RNG = np.random.default_rng(42)


def int_col(vals, validity=None, dt=None):
    return Column.from_numpy(np.asarray(vals), dt, validity)


def _both_engines(left, right, how):
    with join_plan.force_engine("dense"):
        d = join_indices(left, right, how)
    with join_plan.force_engine("sorted"):
        s = join_indices(left, right, how)
    return d, s


def _assert_same(d, s):
    if isinstance(d, tuple):
        for a, b in zip(d, s):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        np.testing.assert_array_equal(np.asarray(d), np.asarray(s))


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_dense_matches_sorted_random(how):
    lk = RNG.integers(0, 400, 3000, dtype=np.int64)
    rk = RNG.integers(0, 400, 500, dtype=np.int64)   # duplicate build keys
    d, s = _both_engines(int_col(lk), int_col(rk), how)
    _assert_same(d, s)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_dense_matches_sorted_null_keys(how):
    lk = RNG.integers(0, 50, 600, dtype=np.int64)
    rk = RNG.integers(0, 50, 200, dtype=np.int64)
    lv = RNG.random(600) < 0.85
    rv = RNG.random(200) < 0.85
    d, s = _both_engines(int_col(lk, validity=lv), int_col(rk, validity=rv),
                         how)
    _assert_same(d, s)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_dense_matches_sorted_unique_build(how):
    # unique build keys take the scatter-built no-sort index and the
    # no-expansion probe tail — the TPC-DS star shape
    rk = RNG.permutation(np.arange(1000, 2000, dtype=np.int64))[:700]
    lk = np.where(RNG.random(4000) < 0.8,
                  rk[RNG.integers(0, 700, 4000)],
                  RNG.integers(5000, 6000, 4000)).astype(np.int64)
    d, s = _both_engines(int_col(lk), int_col(rk), how)
    _assert_same(d, s)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_dense_matches_sorted_empty_build(how):
    lk = np.asarray([1, 2, 3], dtype=np.int64)
    rk = np.zeros(0, dtype=np.int64)
    d, s = _both_engines(int_col(lk), int_col(rk), how)
    _assert_same(d, s)


def test_dense_inner_join_vs_pandas():
    nl, nr = 2000, 300
    lk = RNG.integers(0, 120, nl, dtype=np.int64)
    rk = RNG.integers(0, 120, nr, dtype=np.int64)
    lv = np.arange(nl, dtype=np.int32)
    rv = np.arange(nr, dtype=np.int32) + 7000
    with join_plan.force_engine("dense"):
        out = ops.inner_join(Table([int_col(lk), int_col(lv)]),
                             Table([int_col(rk), int_col(rv)]), 0, 0)
    got = sorted(zip(out[0].to_pylist(), out[1].to_pylist(),
                     out[3].to_pylist()))
    df = pd.merge(pd.DataFrame({"k": lk, "lv": lv}),
                  pd.DataFrame({"k": rk, "rv": rv}), on="k")
    assert got == sorted(zip(df["k"], df["lv"], df["rv"]))


def test_planner_picks_dense_for_dense_keys_only():
    dense = jnp.asarray(np.arange(100, 1100, dtype=np.int64))
    sparse = jnp.asarray(
        RNG.integers(0, 2**60, 1000, dtype=np.int64))
    assert join_plan.build_index(dense, None, True).kind == "dense"
    assert join_plan.build_index(sparse, None, True).kind == "sorted"
    # ineligible dtypes never go dense, regardless of span
    f = Column.from_numpy(np.asarray([1.0, 2.0]))
    assert not join_plan.dense_eligible(f)
    u = Column.from_numpy(np.asarray([1, 2], dtype=np.uint64))
    assert not join_plan.dense_eligible(u)
    i = Column.from_numpy(np.asarray([1, 2], dtype=np.int32))
    assert join_plan.dense_eligible(i)


def test_build_index_cache_hit_returns_identical_index():
    data = jnp.asarray(np.arange(10, 500, dtype=np.int64))
    ix1 = join_plan.build_index(data, None, True)
    ix2 = join_plan.build_index(data, None, True)
    assert ix1 is ix2                      # memoized on buffer identity
    # a distinct buffer with equal contents is a different build side
    data2 = jnp.asarray(np.arange(10, 500, dtype=np.int64))
    assert join_plan.build_index(data2, None, True) is not ix1


def test_cache_hit_join_indices_identical():
    rt = int_col(RNG.permutation(np.arange(300, dtype=np.int64)))
    lt = int_col(RNG.integers(0, 300, 2000, dtype=np.int64))
    li1, ri1 = join_indices(lt, rt, "inner")
    li2, ri2 = join_indices(lt, rt, "inner")   # build index from cache
    np.testing.assert_array_equal(np.asarray(li1), np.asarray(li2))
    np.testing.assert_array_equal(np.asarray(ri1), np.asarray(ri2))


def test_forced_engine_env_var(monkeypatch):
    monkeypatch.setenv("SRJT_JOIN_ENGINE", "sorted")
    dense = jnp.asarray(np.arange(0, 256, dtype=np.int64))
    assert join_plan.build_index(dense, None, True).kind == "sorted"
    monkeypatch.setenv("SRJT_JOIN_ENGINE", "bogus")   # ignored
    assert join_plan.forced_engine() is None


# ---- join→aggregate fusion -------------------------------------------------


def _fused_vs_unfused(lt, rt, left_on, right_on, keys, aggs):
    fused = ops.join_aggregate(lt, rt, left_on, right_on, keys, aggs)
    j = ops.inner_join(lt, rt, left_on, right_on)
    ref = ops.groupby_aggregate(j, keys, aggs)
    ks = list(range(len(keys)))
    fused = ops.sort_table(fused, ks)
    ref = ops.sort_table(ref, ks)
    assert fused.num_rows == ref.num_rows
    assert fused.num_columns == ref.num_columns
    for i in range(ref.num_columns):
        assert fused[i].to_pylist() == ref[i].to_pylist()


def test_fused_unique_build_all_aggs():
    # star shape: unique dimension PK, group by a dimension attribute
    n, nd = 5000, 400
    dim_sk = np.arange(10, 10 + nd, dtype=np.int64)
    dim_cat = RNG.integers(0, 9, nd, dtype=np.int64)
    fk = np.where(RNG.random(n) < 0.9, dim_sk[RNG.integers(0, nd, n)],
                  RNG.integers(9000, 9500, n)).astype(np.int64)
    val = RNG.integers(-50, 50, n, dtype=np.int64)
    vv = RNG.random(n) < 0.9
    lt = Table([int_col(fk), int_col(val, validity=vv)])
    rt = Table([int_col(dim_sk), int_col(dim_cat)])
    _fused_vs_unfused(lt, rt, 0, 0, [3],
                      [(1, "sum"), (1, "count"), (1, "mean"),
                       (1, "min"), (1, "max")])


def test_fused_unique_build_left_side_keys():
    n, nd = 3000, 256
    dim_sk = np.arange(0, nd, dtype=np.int64)
    fk = dim_sk[RNG.integers(0, nd, n)].astype(np.int64)
    grp = RNG.integers(0, 6, n, dtype=np.int64)
    val = RNG.integers(0, 100, n, dtype=np.int64)
    lt = Table([int_col(fk), int_col(grp), int_col(val)])
    rt = Table([int_col(dim_sk)])
    _fused_vs_unfused(lt, rt, 0, 0, [1], [(2, "sum"), (2, "mean")])


def test_fused_weighted_duplicate_build():
    # duplicate build keys + probe-side-only keys/values → weighted path
    n, nb = 2500, 300
    base = np.arange(50, 150, dtype=np.int64)
    bk = base[RNG.integers(0, 100, nb)].astype(np.int64)
    fk = np.where(RNG.random(n) < 0.8, base[RNG.integers(0, 100, n)],
                  RNG.integers(700, 900, n)).astype(np.int64)
    grp = RNG.integers(0, 5, n, dtype=np.int64)
    val = RNG.integers(-9, 9, n, dtype=np.int64)
    vv = RNG.random(n) < 0.85
    lt = Table([int_col(fk), int_col(grp), int_col(val, validity=vv)])
    rt = Table([int_col(bk)])
    _fused_vs_unfused(lt, rt, 0, 0, [1],
                      [(2, "sum"), (2, "count"), (2, "mean"),
                       (2, "min"), (2, "max")])


def test_fused_fallback_right_side_keys_duplicate_build():
    # duplicate build + RIGHT-side group key → materialized fallback
    n, nb = 800, 120
    base = np.arange(0, 40, dtype=np.int64)
    bk = base[RNG.integers(0, 40, nb)].astype(np.int64)
    bg = RNG.integers(0, 4, nb, dtype=np.int64)
    fk = base[RNG.integers(0, 40, n)].astype(np.int64)
    val = RNG.integers(0, 20, n, dtype=np.int64)
    lt = Table([int_col(fk), int_col(val)])
    rt = Table([int_col(bk), int_col(bg)])
    _fused_vs_unfused(lt, rt, 0, 0, [3], [(1, "sum")])


def test_fused_string_group_key_unique_build():
    nd = 64
    dim_sk = np.arange(0, nd, dtype=np.int64)
    cats = Column.strings_from_list([f"cat{i % 7}" for i in range(nd)])
    fk = dim_sk[RNG.integers(0, nd, 1500)].astype(np.int64)
    val = RNG.integers(0, 30, 1500, dtype=np.int64)
    lt = Table([int_col(fk), int_col(val)])
    rt = Table([int_col(dim_sk), cats])
    _fused_vs_unfused(lt, rt, 0, 0, [3], [(1, "sum"), (1, "count")])


def test_fused_empty_probe():
    lt = Table([int_col(np.zeros(0, np.int64)),
                int_col(np.zeros(0, np.int64))])
    rt = Table([int_col(np.arange(5, dtype=np.int64))])
    out = ops.join_aggregate(lt, rt, 0, 0, [0], [(1, "sum")])
    assert out.num_rows == 0


def test_fused_under_capture_replay():
    # the fused dense path must compile: planner scalars ride the tape and
    # the build-index memo is disabled so capture and replay stay aligned
    from spark_rapids_jni_tpu.models.compiled import compile_query

    nd, n = 128, 2000
    dim_sk = np.arange(0, nd, dtype=np.int64)
    dim_cat = RNG.integers(0, 5, nd, dtype=np.int64)
    fk = dim_sk[RNG.integers(0, nd, n)].astype(np.int64)
    val = RNG.integers(0, 40, n, dtype=np.int64)
    tables = {
        "fact": Table([int_col(fk), int_col(val)]),
        "dim": Table([int_col(dim_sk), int_col(dim_cat)]),
    }

    def q(t):
        out = ops.join_aggregate(t["fact"], t["dim"], 0, 0, [3],
                                 [(1, "sum")])
        return ops.sort_table(out, [0])

    eager = q(tables)
    cq = compile_query(q, tables)
    out = cq.run(tables)
    assert out[0].to_pylist() == eager[0].to_pylist()
    assert out[1].to_pylist() == eager[1].to_pylist()


# ---- distributed dense shard probe ----------------------------------------


def test_repartition_dense_spec_matches_sorted():
    from spark_rapids_jni_tpu.parallel import make_mesh
    from spark_rapids_jni_tpu.parallel.repartition_join import (
        JoinAggSpec, repartition_join_agg)

    mesh = make_mesh(8, "data")
    rng = np.random.default_rng(7)
    n_fact, n_item, n_cat = 2048, 256, 6
    base = np.arange(100, 200, dtype=np.int64)
    item_sk = base[rng.integers(0, 100, n_item)].astype(np.int64)
    item_cat = rng.integers(0, n_cat, n_item).astype(np.int32)
    fact_sk = np.where(rng.random(n_fact) < 0.8,
                       base[rng.integers(0, 100, n_fact)],
                       rng.integers(700, 900, n_fact)).astype(np.int64)
    fact_qty = rng.integers(1, 30, n_fact).astype(np.int64)
    fv = np.ones((n_fact, 2), bool)
    iv = np.ones((n_item, 2), bool)
    fv[:, 0] = rng.random(n_fact) < 0.9
    iv[:, 0] = rng.random(n_item) < 0.9

    common = dict(fact_schema=(sr.int64, sr.int64),
                  build_schema=(sr.int64, sr.int32),
                  fact_key_idx=0, build_key_idx=0, build_group_idx=1,
                  fact_value_idx=1, num_groups=n_cat,
                  fact_capacity=n_fact, build_capacity=n_item)
    args = ((jnp.asarray(fact_sk), jnp.asarray(fact_qty)), jnp.asarray(fv),
            (jnp.asarray(item_sk), jnp.asarray(item_cat)), jnp.asarray(iv))
    # dense window deliberately wider than the key range (offset base)
    dense = JoinAggSpec(**common, key_min=64, key_span=1024)
    sorted_ = JoinAggSpec(**common)
    ds, dc, dd = repartition_join_agg(mesh, dense, *args)
    ss_, sc, sd = repartition_join_agg(mesh, sorted_, *args)
    assert int(np.asarray(dd)) == 0 and int(np.asarray(sd)) == 0
    np.testing.assert_array_equal(np.asarray(ds), np.asarray(ss_))
    np.testing.assert_array_equal(np.asarray(dc), np.asarray(sc))


# ---- multi-column keys: composite / fingerprint / fallback -----------------


def _py_pairs(lcols, rcols, how):
    """Reference multi-key equi-join on host tuples: a null in ANY key
    column never matches; matches enumerate in build-row order (the
    engines' stable key-sorted tie order)."""
    nl, nr = len(lcols[0][0]), len(rcols[0][0])
    rmap = {}
    for j in range(nr):
        if any(v is not None and not v[j] for _, v in rcols):
            continue
        rmap.setdefault(tuple(a[j] for a, _ in rcols), []).append(j)
    out = []
    for i in range(nl):
        null = any(v is not None and not v[i] for _, v in lcols)
        matches = [] if null else rmap.get(tuple(a[i] for a, _ in lcols), [])
        if how == "inner":
            out += [(i, j) for j in matches]
        elif how == "left":
            out += [(i, j) for j in matches] or [(i, -1)]
        elif how == "semi":
            out += [i] if matches else []
        else:
            out += [] if matches else [i]
    return out


def _got_pairs(res, how):
    if how in ("semi", "anti"):
        return np.asarray(res).tolist()
    li, ri = res
    return list(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_composite_2key_engines_and_oracle(how):
    n, m = 1500, 400
    la = RNG.integers(0, 40, n, dtype=np.int64)
    lb = RNG.integers(0, 30, n).astype(np.int32)    # mixed key widths
    ra = RNG.integers(0, 40, m, dtype=np.int64)
    rb = RNG.integers(0, 30, m).astype(np.int32)
    lv = RNG.random(n) < 0.9
    rv = RNG.random(m) < 0.9
    lt = [int_col(la, validity=lv), int_col(lb)]
    rt = [int_col(ra), int_col(rb, validity=rv)]
    plan = join_plan.plan_keys(lt, rt)
    assert plan.mode == "composite" and plan.dense_ok and not plan.verify
    d, s = _both_engines(lt, rt, how)
    _assert_same(d, s)
    ref = _py_pairs([(la, lv), (lb, None)], [(ra, None), (rb, rv)], how)
    assert _got_pairs(d, how) == ref


def test_composite_3key_vs_pandas():
    n, m = 2000, 500
    lk = [RNG.integers(0, 12, n, dtype=np.int64) for _ in range(3)]
    rk = [RNG.integers(0, 12, m, dtype=np.int64) for _ in range(3)]
    lv = RNG.random(n) < 0.92
    lt = [int_col(lk[0], validity=lv), int_col(lk[1]), int_col(lk[2])]
    rt = [int_col(rk[0]), int_col(rk[1]), int_col(rk[2])]
    assert join_plan.plan_keys(lt, rt).mode == "composite"
    # null keys → per-row sentinels outside the key range, so a plain
    # pandas merge reproduces SQL null-never-matches semantics
    a = lk[0].copy()
    a[~lv] = -1000 - np.arange(np.count_nonzero(~lv))
    ldf = pd.DataFrame({"a": a, "b": lk[1], "c": lk[2], "li": np.arange(n)})
    rdf = pd.DataFrame({"a": rk[0], "b": rk[1], "c": rk[2],
                        "rj": np.arange(m)})
    for how in ("inner", "left"):
        li, ri = join_indices(lt, rt, how)
        mg = ldf.merge(rdf, on=["a", "b", "c"], how=how)
        exp = sorted(zip(mg["li"].tolist(),
                         mg["rj"].fillna(-1).astype(int).tolist()))
        assert sorted(_got_pairs((li, ri), how)) == exp


def test_composite_string_int_key():
    cats = [f"s{i}" for i in range(9)]
    n, m = 1200, 300
    ls = [cats[i] for i in RNG.integers(0, 9, n)]
    rs = [cats[i] for i in RNG.integers(0, 9, m)]
    lb = RNG.integers(0, 25, n, dtype=np.int64)
    rb = RNG.integers(0, 25, m, dtype=np.int64)
    lt = [Column.strings_from_list(ls), int_col(lb)]
    rt = [Column.strings_from_list(rs), int_col(rb)]
    # dictionary codes from the shared encode are dense-eligible → packed
    assert join_plan.plan_keys(lt, rt).mode == "composite"
    li, ri = join_indices(lt, rt, "inner")
    got = sorted((ls[i], int(lb[i]), int(rb[j]))
                 for i, j in _got_pairs((li, ri), "inner"))
    df = pd.merge(pd.DataFrame({"s": ls, "b": lb}),
                  pd.DataFrame({"s": rs, "b": rb}), on=["s", "b"])
    assert got == sorted(zip(df["s"], df["b"], df["b"]))


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_fingerprint_overflow_matches_oracle(how):
    # two wide-window int64 keys: span product overflows 63 bits → the
    # planner probes on a murmur3 fingerprint and verifies tuple equality
    n, m = 900, 250
    base = RNG.integers(-2**61, 2**61, 60, dtype=np.int64)
    la, ra = base[RNG.integers(0, 60, n)], base[RNG.integers(0, 60, m)]
    lb, rb = base[RNG.integers(0, 60, n)], base[RNG.integers(0, 60, m)]
    lv = RNG.random(n) < 0.9
    lt = [int_col(la, validity=lv), int_col(lb)]
    rt = [int_col(ra), int_col(rb)]
    plan = join_plan.plan_keys(lt, rt)
    assert plan.mode == "fingerprint" and plan.verify and not plan.dense_ok
    got = _got_pairs(join_indices(lt, rt, how), how)
    ref = _py_pairs([(la, lv), (lb, None)], [(ra, None), (rb, None)], how)
    assert sorted(got) == sorted(ref)
    if how == "left":   # engine emits probe-row-major order, like expansion
        assert got == ref


def test_fallback_f64_key_matches_oracle():
    # an f64 lane can never pack exactly → hashed probe, counted "fallback"
    n, m = 800, 200
    lf = (RNG.integers(0, 20, n) / 4.0).astype(np.float64)
    rf = (RNG.integers(0, 20, m) / 4.0).astype(np.float64)
    lb = RNG.integers(0, 10, n, dtype=np.int64)
    rb = RNG.integers(0, 10, m, dtype=np.int64)
    lt = [Column.from_numpy(lf), int_col(lb)]
    rt = [Column.from_numpy(rf), int_col(rb)]
    plan = join_plan.plan_keys(lt, rt)
    assert plan.mode == "fallback" and plan.verify
    got = _got_pairs(join_indices(lt, rt, "inner"), "inner")
    ref = _py_pairs([(lf, None), (lb, None)], [(rf, None), (rb, None)],
                    "inner")
    assert sorted(got) == sorted(ref)


def test_fingerprint_collisions_are_rejected(monkeypatch):
    # cripple the fingerprint to 5 buckets: every probe drowns in
    # collisions, the verification pass must still reject them all
    from spark_rapids_jni_tpu.ops import hashing

    monkeypatch.setattr(
        hashing, "fingerprint64",
        lambda lanes: (lanes[0].astype(jnp.int64) % 5 + 5) % 5)
    n, m = 400, 120
    la = RNG.integers(-2**61, 2**61, n, dtype=np.int64)
    ra = np.concatenate([la[RNG.integers(0, n, 60)],
                         RNG.integers(-2**61, 2**61, m - 60, dtype=np.int64)])
    lb = RNG.integers(0, 4, n, dtype=np.int64)
    rb = RNG.integers(0, 4, m, dtype=np.int64)
    lt = [int_col(la), int_col(lb)]
    rt = [int_col(ra), int_col(rb)]
    for how in ("inner", "left", "semi", "anti"):
        got = _got_pairs(join_indices(lt, rt, how), how)
        ref = _py_pairs([(la, None), (lb, None)], [(ra, None), (rb, None)],
                        how)
        assert sorted(got) == sorted(ref)


def test_single_key_list_equals_scalar_key():
    lk = int_col(RNG.integers(0, 90, 700, dtype=np.int64))
    rk = int_col(RNG.integers(0, 90, 200, dtype=np.int64))
    _assert_same(join_indices([lk], [rk], "inner"),
                 join_indices(lk, rk, "inner"))
    assert join_plan.plan_keys([lk], [rk]).mode == "single"


def test_multikey_pack_counters_and_cache_hits():
    from spark_rapids_jni_tpu.utils import metrics

    metrics.set_enabled(True)
    metrics.reset()
    try:
        lt = [int_col(RNG.integers(0, 50, 1000, dtype=np.int64)),
              int_col(RNG.integers(0, 20, 1000, dtype=np.int64))]
        rt = [int_col(RNG.integers(0, 50, 300, dtype=np.int64)),
              int_col(RNG.integers(0, 20, 300, dtype=np.int64))]
        a = join_indices(lt, rt, "inner")
        b = join_indices(lt, rt, "inner")   # same buffers → both caches hit
        _assert_same(a, b)
        c = metrics.snapshot()["counters"]
        assert c["join.pack.composite"] == 1
        assert "join.pack" in metrics.stage_breakdown()    # its span
        assert c["join.pack.cache_hit"] >= 1
        assert c["join.build_index.cache_hit"] >= 1
    finally:
        metrics.reset()
        metrics.set_enabled(None)


# ---- left-outer join→aggregate fusion --------------------------------------


def _fused_vs_unfused_how(lt, rt, left_on, right_on, keys, aggs, how):
    fused = ops.join_aggregate(lt, rt, left_on, right_on, keys, aggs,
                               how=how)
    j = (ops.inner_join if how == "inner" else ops.left_join)(
        lt, rt, left_on, right_on)
    ref = ops.groupby_aggregate(j, keys, aggs)
    ks = list(range(len(keys)))
    fused = ops.sort_table(fused, ks)
    ref = ops.sort_table(ref, ks)
    assert fused.num_rows == ref.num_rows
    for i in range(ref.num_columns):
        assert fused[i].to_pylist() == ref[i].to_pylist()


def test_fused_left_unique_build():
    # unmatched probe rows keep null build columns — incl. the null group
    n, nd = 3000, 300
    dim_sk = np.arange(10, 10 + nd, dtype=np.int64)
    dim_cat = RNG.integers(0, 7, nd, dtype=np.int64)
    fk = np.where(RNG.random(n) < 0.8, dim_sk[RNG.integers(0, nd, n)],
                  RNG.integers(9000, 9500, n)).astype(np.int64)
    val = RNG.integers(-40, 40, n, dtype=np.int64)
    vv = RNG.random(n) < 0.9
    lt = Table([int_col(fk), int_col(val, validity=vv)])
    rt = Table([int_col(dim_sk), int_col(dim_cat)])
    _fused_vs_unfused_how(lt, rt, 0, 0, [3],
                          [(1, "sum"), (1, "count"), (1, "mean"),
                           (1, "min"), (1, "max")], "left")


def test_fused_left_weighted_duplicate_build():
    # unmatched rows weight 1 (their single null-extended joined row)
    n, nb = 2000, 250
    base = np.arange(0, 80, dtype=np.int64)
    bk = base[RNG.integers(0, 80, nb)].astype(np.int64)
    fk = np.where(RNG.random(n) < 0.7, base[RNG.integers(0, 80, n)],
                  RNG.integers(500, 700, n)).astype(np.int64)
    grp = RNG.integers(0, 5, n, dtype=np.int64)
    val = RNG.integers(-9, 9, n, dtype=np.int64)
    vv = RNG.random(n) < 0.85
    lt = Table([int_col(fk), int_col(grp), int_col(val, validity=vv)])
    rt = Table([int_col(bk)])
    _fused_vs_unfused_how(lt, rt, 0, 0, [1],
                          [(2, "sum"), (2, "count"), (2, "mean"),
                           (2, "min"), (2, "max")], "left")


def test_fused_multikey_composite_inner_and_left():
    n, nd = 2500, 160
    da = np.repeat(np.arange(40, dtype=np.int64), 4)
    db = np.tile(np.arange(4, dtype=np.int64), 40)      # unique (a, b) pairs
    dcat = RNG.integers(0, 6, nd, dtype=np.int64)
    fa = np.where(RNG.random(n) < 0.85, RNG.integers(0, 40, n),
                  RNG.integers(90, 120, n)).astype(np.int64)
    fb = RNG.integers(0, 4, n, dtype=np.int64)
    val = RNG.integers(0, 50, n, dtype=np.int64)
    lt = Table([int_col(fa), int_col(fb), int_col(val)])
    rt = Table([int_col(da), int_col(db), int_col(dcat)])
    for how in ("inner", "left"):
        _fused_vs_unfused_how(lt, rt, [0, 1], [0, 1], [5],
                              [(2, "sum"), (2, "count")], how)


def test_fused_fingerprint_falls_back_to_join():
    # hashed probe counts are candidate counts — fusion must not trust them
    n, m = 600, 100
    base = RNG.integers(-2**61, 2**61, 50, dtype=np.int64)
    fa, fb = base[RNG.integers(0, 50, n)], base[RNG.integers(0, 50, n)]
    ba, bb = base[RNG.integers(0, 50, m)], base[RNG.integers(0, 50, m)]
    grp = RNG.integers(0, 4, n, dtype=np.int64)
    val = RNG.integers(0, 9, n, dtype=np.int64)
    lt = Table([int_col(fa), int_col(fb), int_col(grp), int_col(val)])
    rt = Table([int_col(ba), int_col(bb)])
    for how in ("inner", "left"):
        _fused_vs_unfused_how(lt, rt, [0, 1], [0, 1], [2],
                              [(3, "sum"), (3, "count")], how)


def test_decimal128_single_key_fingerprint_verify_path():
    """A lone decimal128 key must route through the hashed
    fingerprint-and-verify pack (its (n, 2) limb storage has no single
    probe lane for the sort-probe engine) and produce the same indices
    as an int64 key with identical equality structure."""
    from spark_rapids_jni_tpu.ops import decimal128 as d128

    # mirror: same positions match in both keyings; the >64-bit values
    # force real two-limb equality through the verify lanes
    lmap = {0: 3, 1: 1, 2: 2, 3: 3, 4: 5, 5: 2**70, 6: -2**70, 7: 7}
    rmap = {0: 2, 1: 3, 2: 5, 3: 2**70, 4: 9, 5: -2**70}
    lc = d128.from_pyints([lmap[i] for i in range(8)], scale=0)
    rc = d128.from_pyints([rmap[i] for i in range(6)], scale=0)
    small = {2**70: 100, -2**70: -100}
    li = int_col(np.asarray([small.get(lmap[i], lmap[i])
                             for i in range(8)], np.int64))
    ri = int_col(np.asarray([small.get(rmap[i], rmap[i])
                             for i in range(6)], np.int64))

    plan = join_plan.plan_keys([lc], [rc])
    assert plan.mode == "fallback"
    assert plan.ldata.ndim == 1 and len(plan.verify) == 2

    for how in ("inner", "left"):
        dl, dr = join_indices(lc, rc, how)
        il, ir_ = join_indices(li, ri, how)
        assert sorted(zip(np.asarray(dl).tolist(),
                          np.asarray(dr).tolist())) \
            == sorted(zip(np.asarray(il).tolist(),
                          np.asarray(ir_).tolist()))
    for how in ("semi", "anti"):
        assert np.asarray(join_indices(lc, rc, how)).tolist() \
            == np.asarray(join_indices(li, ri, how)).tolist()


def test_decimal128_key_with_nulls_and_collision_scale():
    """Nulls never match, and same-low-limb values differing only in the
    high limb (fingerprint collision bait) are kept apart by the verify
    lanes."""
    from spark_rapids_jni_tpu.ops import decimal128 as d128

    # low limbs equal, high limbs differ: v and v + 2**64
    lv = [5, 5 + 2**64, None, 9]
    rv = [5, 9, None, 5 + 2**64]
    lc = d128.from_pyints(lv, scale=0)
    rc = d128.from_pyints(rv, scale=0)
    dl, dr = join_indices(lc, rc, "inner")
    pairs = sorted(zip(np.asarray(dl).tolist(), np.asarray(dr).tolist()))
    expect = sorted((i, j) for i, a in enumerate(lv)
                    for j, b in enumerate(rv)
                    if a is not None and b is not None and a == b)
    assert pairs == expect


# ---- the sorted index's two probes ------------------------------------------


_C = join_plan.COMPARE_PROBE_MAX_KEYS
_KEY_DTYPES = {"int32": (np.int32, None), "int64": (np.int64, None),
               "date": (np.int32, sr.types.timestamp_days)}


@pytest.mark.parametrize("key", sorted(_KEY_DTYPES))
@pytest.mark.parametrize("nulls", [False, True], ids=["nonull", "nulls"])
@pytest.mark.parametrize("dups", [False, True], ids=["distinct", "dups"])
@pytest.mark.parametrize("n_valid",
                         [0, 1, 18, 127, 128, 129, 212, _C, _C + 1])
def test_sorted_probe_compare_equals_bsearch(n_valid, dups, nulls, key,
                                             monkeypatch):
    # a sorted index of at most COMPARE_PROBE_MAX_KEYS keys is probed by
    # counting compares, a larger one by the two binary searches: same
    # (lo, counts) everywhere, same join indices as the dense engine
    import re

    import jax
    from spark_rapids_jni_tpu.utils import metrics

    npdt, dt = _KEY_DTYPES[key]
    rng = np.random.default_rng(1000 * n_valid + 2 * dups + nulls)
    pool = rng.choice(1 << 18, max(n_valid // 3 if dups else n_valid, 1),
                      replace=False) + 1000
    rk = (rng.choice(pool, n_valid) if dups else pool[:n_valid]).astype(npdt)
    lk = np.where(rng.random(1500) < 0.5, rng.choice(pool, 1500),
                  rng.integers(0, (1 << 18) + 2000, 1500)).astype(npdt)
    rv = lv = None
    if nulls:
        # null build rows carry keys that WOULD match: they must not count
        rk = np.concatenate([rk, rng.choice(pool, 40).astype(npdt)])
        rv = np.arange(rk.shape[0]) < n_valid
        perm = rng.permutation(rk.shape[0])
        rk, rv = rk[perm], rv[perm]
        lv = rng.random(1500) < 0.85
    left, right = int_col(lk, lv, dt), int_col(rk, rv, dt)

    with join_plan.force_engine("sorted"):
        ix = join_plan.build_index(right.data, right.validity, True)
    assert (ix.kind, ix.n_valid) == ("sorted", n_valid)
    want = "compare" if n_valid <= _C else "bsearch"
    assert join_plan.probe_kind(ix) == want

    metrics.set_enabled(True)
    metrics.reset()
    try:
        lo, counts = join_plan.probe_counts(ix, left.data, left.validity)
        ticks = {k: metrics.counter_value(f"join.probe.{k}")
                 for k in ("compare", "bsearch", "dense")}
    finally:
        metrics.reset()
        metrics.set_enabled(None)
    assert ticks == {k: int(k == want) for k in ticks}
    lo_ref = jnp.searchsorted(ix.sorted_keys, left.data, side="left")
    hi_ref = jnp.searchsorted(ix.sorted_keys, left.data, side="right")
    cnt_ref = hi_ref - lo_ref
    if lv is not None:
        cnt_ref = jnp.where(left.validity, cnt_ref, 0)
    assert (lo.dtype, counts.dtype) == (lo_ref.dtype, cnt_ref.dtype)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_ref))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(cnt_ref))

    # the program the chip gets (off it the probe axis goes by in blocks);
    # a fresh function each time: a trace is cached on the function
    probe = (lambda keys, q: join_plan._probe_compare.__wrapped__(keys, q)) \
        if want == "compare" else lambda keys, q: join_plan.probe_counts(
            ix._replace(sorted_keys=keys), q, None)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        text = str(jax.make_jaxpr(probe)(ix.sorted_keys, left.data))
    loops = re.findall(r"\b(?:gather|scan|while)\[", text)
    assert not loops if want == "compare" else loops

    events = []
    monkeypatch.setattr(metrics, "_profile_op_hook",
                        lambda name, **f: events.append((name, f["probe"])))
    for how in ("inner", "left", "semi", "anti"):
        _assert_same(*_both_engines(left, right, how))
    # an empty build has no span to be dense over
    assert set(events) == {("join", "dense" if n_valid else want),
                           ("join", want)}
