"""Which row owns output ``j``: the inverse of a running sum of counts.

A join's pair expansion (``ops/join.py``) and a traced mask compaction
(``ops/filter.py: sized_nonzero``) ask the same question.  Row ``i`` of n
owns ``counts[i]`` consecutive outputs, ``total`` in all (a host int the
caller has already synced); for output ``j`` find the owning row and ``j``'s
place within that row's run::

    left_idx = np.repeat(np.arange(n), counts)
    within   = np.arange(total) - (np.cumsum(counts) - counts)[left_idx]

A binary search per output over ``starts = cumsum(counts) - counts`` does
it in ``ceil(log2(n+1))`` levels of element gathers, and an n-update
scatter-add does the compaction: the two things the chip is worst at (on a
v5e an element gathered from a 10M-row table costs 7-14 ns, a scatter
update 90; PERF.md section 6, PR 35).  Here it is a **block select** made
of dense work:

1. ``starts`` over n rows (one running sum), viewed as ``[nb, ROW_WORDS]``
   blocks, padded past n with a value no output reaches.
2. The block of output ``j`` is the last one whose first start is ``<= j``:
   a compare-count against the ``nb`` block firsts, which XLA:TPU fuses into
   the reduction (``join_plan._probe_compare``'s form).  Over more than
   ``COMPARE_TOP`` firsts the same select runs on the firsts first.
3. One **row** gather of that block's starts, ``[pairs, ROW_WORDS]``
   contiguous rows and no elements, then dense: the count of starts
   ``<= j`` places the row within its block, their largest is the row's own
   start.

Every word is 32-bit while n and total allow and widens once at the end.
Outputs are taken ``CHUNK_PAIRS`` at a time so the gathered rows stay
under ``ROWS_BYTES`` whatever ``total`` is: one chunk is the ``block`` form,
several the ``chunked`` form (``lax.map``); :func:`form` says which from
``total`` alone.  It is one algorithm on every backend.  One that does not
fuse the compare into the reduction (XLA:CPU, which runs the tests) would
hold ``[pairs, firsts]`` cells, so there the firsts compare-counted in one
go are as many as a gathered row has, as ``join_plan._probe_compare``
bounds its blocks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# Rates on a v5e over n = 10M probe rows (tools/owner_rates.py; PERF.md
# section 6, PR 35; ms a call at 106597 / 1M / 10M outputs, of which the
# running sum and its block view are 3.5): the search over int64 starts
# 48.7 / 633 / 6530, over int32 21.3 / 170 / 1889, this select 4.10 / 10.3 /
# 68.8.  A compare against a block first costs 0.0007 ns an output, a gathered
# row of 128 starts with its count and max ~3 ns.

# Starts a block holds: the width of the gathered rows.  One lane row; 256
# reads 4.79 / 12.3 / 86.6, 64 reads 4.47 / 18.2 / 145.9, 512 with one level of
# 19532 firsts 6.47 / 75.5 / 694.
ROW_WORDS = 128
# Block firsts that are compare-counted in one go on the chip; above it the
# select runs on the firsts first.  A level costs what ~3000 compares cost.
# The ends are measured: 78125 firsts in one go read 10.0 / 264 / 2625, and
# 128 (a third level for 611 firsts) 4.19 / 11.65 / 80.6.  Between them Step 0
# does not separate 4096 from its neighbours: at n = 10M every top from 611
# to 78124 gives the same two levels, and where it decides (400k rows, 3125
# firsts, 100k outputs) one go reads 1.40 and a second level 1.29.
COMPARE_TOP = 1 << 12
# The gathered rows one chunk of outputs may hold, and the outputs that is:
# 131072.  Four times the chunk reads 13.6 / 104.4 at 1M / 10M outputs and
# holds 381 MB of temporaries there, this 122.
ROWS_BYTES = 64 << 20
CHUNK_PAIRS = ROWS_BYTES // (4 * ROW_WORDS)


def form(total: int) -> str:
    """How :func:`owners` serves ``total`` outputs: ``block`` (one chunk)
    or ``chunked``."""
    return "block" if total <= CHUNK_PAIRS else "chunked"


def _compare_top() -> int:
    """Block firsts compare-counted in one go: ``COMPARE_TOP`` where the
    compare fuses into the count, else no more cells a pair than a gathered
    row holds (64 MiB of int32 a chunk, ``_probe_compare``'s bound)."""
    return COMPARE_TOP if jax.default_backend() == "tpu" else ROW_WORDS


def temp_bytes(n: int, total: int) -> int:
    """Device bytes :func:`owners` may hold beside its two results: the
    running sum and its padded block view; one chunk's gathered rows with
    their compare mask and pair ids; the chunks' stacked 32-bit answers."""
    chunks = 8 * total if total > CHUNK_PAIRS else 0
    return 12 * n + chunks + min(total, CHUNK_PAIRS) * (5 * ROW_WORDS + 16)


def _block_views(s, row_words: int, top: int) -> list:
    """``s`` as ``[nb, row_words]`` blocks, then the blocks' firsts likewise
    while there are more than ``top`` of them; last the firsts that are
    compare-counted.  Padded with a value above every ``j``."""
    views = []
    while not views or s.shape[0] > top:
        nb = -(-s.shape[0] // row_words)
        s2d = jnp.pad(s, (0, nb * row_words - s.shape[0]),
                      constant_values=jnp.iinfo(s.dtype).max
                      ).reshape(nb, row_words)
        views.append(s2d)
        s = s2d[:, 0]
    return views + [s]


def _select(views: list, j):
    """Per ``j``: index of the last element ``<= j`` of the ascending array
    that :func:`_block_views` cut up, and the element.  Its first element is
    ``<= j`` for every ``j``."""
    *blocks, firsts = views
    at = jnp.searchsorted(firsts, j, side="right", method="compare_all")
    at = at.astype(j.dtype) - 1
    val = None
    for s2d in reversed(blocks):
        rows = s2d if s2d.shape[0] == 1 else s2d.at[at].get(
            mode="promise_in_bounds", indices_are_sorted=True)
        le = rows <= j[:, None]
        val = jnp.max(jnp.where(le, rows, 0), axis=1)
        at = at * s2d.shape[1] + jnp.sum(le, axis=1, dtype=j.dtype) - 1
    return at, val


def _word(n: int, ids: int):
    """32-bit words while n rows and ``ids`` output ids allow."""
    return jnp.int32 if max(n, ids) < 2**31 else jnp.int64


@partial(jax.jit, static_argnames=("total", "row_words", "top", "chunk"))
def _owners_block(counts, total: int, row_words: int, top: int, chunk: int):
    nc = -(-total // chunk)
    word = _word(counts.shape[0], nc * chunk)
    c = counts.astype(word)
    views = _block_views(jnp.cumsum(c) - c, row_words, top)

    def pairs(j):
        left, start = _select(views, j)
        return left, j - start

    if nc == 1:
        left, within = pairs(jnp.arange(total, dtype=word))
    else:
        ids = jnp.arange(chunk, dtype=word)
        left, within = jax.lax.map(
            lambda k: pairs(k * chunk + ids), jnp.arange(nc, dtype=word))
        left, within = left.reshape(-1)[:total], within.reshape(-1)[:total]
    return left.astype(jnp.int64), within.astype(jnp.int64)


def owners(counts, total: int):
    """``(left_idx, within)``, int64 ``[total]``: the row of ``counts`` that
    owns each of the ``total = sum(counts)`` outputs, row-major, and the
    output's place within its row's run.  An output past ``sum(counts)``
    reads the last row."""
    n = int(counts.shape[0])
    if total == 0 or n == 0:
        z = jnp.zeros(total, jnp.int64)
        return z, z
    return _owners_block(counts, total, ROW_WORDS, _compare_top(),
                         CHUNK_PAIRS)
