"""``ops.select.owners``: which row owns output j, and j's place in that
row's run — every form against ``np.repeat``, eagerly and under ``jax.jit``
with ``total`` static."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_jni_tpu  # noqa: F401  (x64 on, as the program runs)
from spark_rapids_jni_tpu.ops import select
from spark_rapids_jni_tpu.ops.filter import sized_nonzero

K = 16          # a block of the forced forms (ROW_WORDS is 128)


def _counts(case: str) -> np.ndarray:
    rng = np.random.default_rng(35)
    if case == "n_not_a_multiple_of_k":
        return rng.integers(0, 3, 5 * K + 7)
    if case == "n_under_k":
        return rng.integers(0, 4, K - 5)
    if case == "n_is_k":
        return rng.integers(0, 2, K)
    if case == "all_zero":
        return np.zeros(3 * K + 1, int)
    if case == "no_rows":
        return np.zeros(0, int)
    if case == "one_row_owns_more_than_k":
        c = rng.integers(0, 2, 4 * K)
        c[K + 3] = 5 * K + 1
        return c
    if case == "runs_straddle_block_ends":
        c = np.zeros(6 * K, int)
        c[K - 1::K] = 3             # the last row of every block
        c[K::K] = 2                 # and the first of the next
        return c
    if case == "sparse":            # most blocks own nothing
        c = np.zeros(40 * K, int)
        c[rng.choice(40 * K, 9, replace=False)] = 1
        return c
    if case == "left_join":         # total >= n: unmatched rows keep one
        return np.maximum(rng.integers(0, 3, 7 * K + 2), 1)
    if case == "long_probe_side":   # two levels at ROW_WORDS, off the TPU
        return rng.integers(0, 3, select.ROW_WORDS ** 2 * 2 + 5)
    raise AssertionError(case)


CASES = ["n_not_a_multiple_of_k", "n_under_k", "n_is_k", "all_zero",
         "no_rows", "one_row_owns_more_than_k", "runs_straddle_block_ends",
         "sparse", "left_join", "long_probe_side"]

# each form through the private functions' arguments: one chunk, several
# chunks (the last one ragged), the select recursing over the block firsts
# (twice over at 40 * K rows), the chip's parameters, and this backend's
FORMS = {
    "block": lambda c, t: select._owners_block(c, t, K, 1 << 15, 1 << 20),
    "chunked": lambda c, t: select._owners_block(c, t, K, 1 << 15, 7),
    "block_two_levels": lambda c, t: select._owners_block(c, t, K, 2,
                                                          1 << 20),
    "chunked_two_levels": lambda c, t: select._owners_block(c, t, K, 2, 5),
    "as_picked_on_the_chip": lambda c, t: select._owners_block(
        c, t, select.ROW_WORDS, select.COMPARE_TOP, select.CHUNK_PAIRS),
    "as_picked": select.owners,
}


def _expected(counts):
    left = np.repeat(np.arange(counts.size), counts)
    starts = np.cumsum(counts) - counts
    return left, np.arange(left.size) - starts[left]


# with nothing to own, owners returns before any form runs
_EMPTY = {"all_zero", "no_rows"}


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case,form", [
    (c, f) for c in CASES for f in FORMS
    if c not in _EMPTY or f == "as_picked"])
def test_owners_against_repeat(case, form, jitted):
    counts = _counts(case).astype(np.int32)
    total = int(counts.sum())
    want_left, want_within = _expected(counts)
    fn = FORMS[form]
    if jitted:
        fn = jax.jit(fn, static_argnums=1)
    left, within = fn(jnp.asarray(counts), total)
    assert left.dtype == jnp.int64 and within.dtype == jnp.int64
    assert left.shape == within.shape == (total,)
    np.testing.assert_array_equal(np.asarray(left), want_left)
    np.testing.assert_array_equal(np.asarray(within), want_within)


@pytest.mark.parametrize("total,want", [
    (1, "block"), (select.CHUNK_PAIRS, "block"),
    (select.CHUNK_PAIRS + 1, "chunked"), (10**9, "chunked")])
def test_form_is_picked_from_total(total, want):
    assert select.form(total) == want
    # what a chunk gathers stays under its bound whatever total is
    assert select.temp_bytes(10_000_000, total) <= \
        12 * 10_000_000 + 8 * total + 2 * select.ROWS_BYTES


@pytest.mark.parametrize("backend,want", [
    ("tpu", select.COMPARE_TOP), ("cpu", select.ROW_WORDS)])
def test_unfused_compare_holds_a_chunk_of_rows(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert select._compare_top() == want
    if backend != "tpu":
        assert want * select.CHUNK_PAIRS * 4 <= select.ROWS_BYTES


def test_words_widen_past_32_bits():
    assert select._word(2**31 - 1, 2**31 - 1) == jnp.int32
    assert select._word(2**31, 5) == jnp.int64
    assert select._word(5, 2**31) == jnp.int64


@pytest.mark.parametrize("n_keep", ["population", "more", "fewer", "none"])
@pytest.mark.parametrize("mask_case", ["dense", "sparse", "empty"])
def test_sized_nonzero_traced_branch_equals_eager(mask_case, n_keep):
    rng = np.random.default_rng(7)
    n = 9 * 512 + 77
    p = {"dense": 0.6, "sparse": 0.002, "empty": 0.0}[mask_case]
    mask = rng.random(n) < p
    pop = int(mask.sum())
    keep = {"population": pop, "more": pop + 13, "fewer": pop // 2,
            "none": 0}[n_keep]
    eager = sized_nonzero(jnp.asarray(mask), keep)
    traced = jax.jit(lambda m: sized_nonzero(m, keep))(jnp.asarray(mask))
    assert traced.dtype == eager.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(eager))
