"""Distributed query execution over the device mesh (SURVEY §7 step 7).

The reference stops at emitting shuffle-ready blobs (Spark executes the
query plan); here multi-chip execution is first-class: the canonical
Spark-on-TPU aggregation — a star-schema join + groupby over a sharded fact
table — runs as ONE jitted SPMD program:

  * fact columns sharded over the mesh axis (rows split across chips)
  * the dimension table replicated and pre-sorted by join key
  * per chip: ``searchsorted`` probe (static-shaped sort-merge lookup — the
    TPU formulation of a hash-probe), sentinel-dropped misses, and a
    fixed-width ``segment_sum`` partial aggregate
  * one ``psum`` over ICI combines the per-chip partials

No host sync anywhere: group count is static (dictionary codes), the probe
is static-shaped, and the collective is a single XLA ``all-reduce`` riding
ICI.  This is the BASELINE.json north-star shape (TPC-DS aggregation over a
sharded executor pool) in miniature.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_shard_map = jax.shard_map

from ..column import Column
from ..ops import strings as S
from ..utils import metrics


class Dimension(NamedTuple):
    """A replicated, probe-ready dimension: keys sorted ascending, one
    int32 group code per key (codes from ``strings.dictionary_encode`` or
    any bounded categorical), and the static group count."""
    keys: jnp.ndarray          # int [m], sorted ascending, unique
    group_codes: jnp.ndarray   # int32 [m] in [0, num_groups)
    num_groups: int


def prepare_dimension(key_col: Column, group_col: Column) -> Dimension:
    """Host-side prep: sort by key; dictionary-encode the group column
    (string or integer) into dense codes."""
    keys = np.asarray(key_col.data)
    if np.unique(keys).shape[0] != keys.shape[0]:
        # searchsorted probes resolve each fact key to ONE dimension row;
        # duplicate keys would silently drop the shadowed rows' groups
        raise ValueError("dimension join keys must be unique")
    order = np.argsort(keys)
    if group_col.dtype.is_variable_width:
        codes_col, uniq = S.dictionary_encode(group_col)
        codes = np.asarray(codes_col.data)
        num_groups = uniq.num_rows
    else:
        vals = np.asarray(group_col.data)
        uniq_vals, codes = np.unique(vals, return_inverse=True)
        num_groups = int(uniq_vals.shape[0])
    return Dimension(jnp.asarray(keys[order]),
                     jnp.asarray(codes[order].astype(np.int32)),
                     num_groups)


def _probe(dim_keys: jnp.ndarray, fact_keys: jnp.ndarray):
    """Static-shaped sort-merge probe: position + hit mask per fact row."""
    pos = jnp.searchsorted(dim_keys, fact_keys)
    pos = jnp.clip(pos, 0, dim_keys.shape[0] - 1)
    return pos, dim_keys[pos] == fact_keys


def _local_star_agg(num_groups: int, axis_name: str, dim_keys, dim_codes,
                    fact_key, fact_value):
    pos, hit = _probe(dim_keys, fact_key)
    # sentinel group `num_groups` absorbs probe misses via mode="drop"
    g = jnp.where(hit, dim_codes[pos], num_groups)
    sums = jnp.zeros(num_groups, fact_value.dtype).at[g].add(
        jnp.where(hit, fact_value, 0), mode="drop")
    cnts = jnp.zeros(num_groups, jnp.int32).at[g].add(
        hit.astype(jnp.int32), mode="drop")
    return (jax.lax.psum(sums, axis_name), jax.lax.psum(cnts, axis_name))


def distributed_star_agg(mesh: jax.sharding.Mesh, dim: Dimension,
                         fact_key: jnp.ndarray, fact_value: jnp.ndarray,
                         axis_name="data"):
    """SELECT group, SUM(value), COUNT(*) FROM fact ⋈ dim GROUP BY group,
    executed SPMD over the mesh.

    ``fact_key``/``fact_value`` are global [n] arrays (n divisible by the
    mesh size); they are sharded over ``axis_name``, the dimension is
    replicated (explicit P() specs — no closure capture under shard_map).
    Returns replicated ([num_groups] sums, [num_groups] counts) — group
    codes index them.

    ``axis_name`` may be a tuple of mesh axes (e.g. ``("dcn", "ici")`` on a
    2-D multi-host mesh): the fact table shards over all of them and the
    final psum reduces over all of them — XLA lowers that to an ICI
    all-reduce per host followed by one DCN all-reduce.
    """
    axis = tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
        else axis_name
    fn = _compiled_star_agg(mesh, dim.num_groups, axis)
    if metrics.recording():
        # record around the SPMD dispatch: sharded fact bytes cross ICI,
        # the partial-aggregate psum is one [num_groups] all-reduce
        metrics.count("dist.star_agg.calls")
        metrics.count("dist.star_agg.fact_bytes",
                      int(fact_key.nbytes) + int(fact_value.nbytes))
        with metrics.span("dist.star_agg", groups=dim.num_groups,
                          devices=len(mesh.devices.flat)):
            return fn(dim.keys, dim.group_codes, fact_key, fact_value)
    return fn(dim.keys, dim.group_codes, fact_key, fact_value)


@lru_cache(maxsize=64)
def _compiled_star_agg(mesh, num_groups: int, axis_name: str):
    """jitted program cached on (mesh, num_groups, axis) — rebuilding the
    shard_map wrapper per call would retrace every invocation."""
    P = jax.sharding.PartitionSpec
    fn = _shard_map(
        partial(_local_star_agg, num_groups, axis_name),
        mesh=mesh,
        in_specs=(P(), P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P()))
    return jax.jit(fn)
