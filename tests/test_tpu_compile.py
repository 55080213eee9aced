"""Ask the TPU's compiler, without a TPU: the main path's kernels and
programs at real widths, compiled for a described (not attached) v5e.

Interpret mode and the CPU backend cannot see what the chip's compiler
refuses — a Mosaic slice off its tiling, a program that does not fit 16 GB,
an op whose TPU lowering blows up.  These compiles guard every later PR at
no chip time.  Nothing runs, so they say nothing about results or speed.

The topology is described only inside the module-scoped fixture below:
never at import, never in conftest, never autouse — one process at a time
may load the TPU library, and only the xdist worker that is handed this
file may try.  Everything is in this one file for the same reason.

Code that asks ``jax.default_backend()`` sees the CPU here; the tests steer
it with monkeypatch where the TPU branch is the one that must compile.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import spark_rapids_jni_tpu as sr
from spark_rapids_jni_tpu.rowconv import convert, ragged, xpack
from spark_rapids_jni_tpu.rowconv.layout import (
    MAX_BATCH_BYTES, build_batches, compute_row_layout,
    row_sizes_with_strings)

HBM_BYTES = 16 << 30          # one v5e chip
CYCLE = [sr.int8, sr.int16, sr.int32, sr.int64, sr.float32, sr.float64,
         sr.bool8]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Take the repo's TPU branches (f64 bits arithmetic, donation)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(one_chip, jitted, *args, statics=()):
    """Compile ``jitted`` for the described chip from shapes alone; fails
    when the compiler refuses or the program cannot fit the chip."""
    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    shaped = jax.tree_util.tree_map(sds, args)
    compiled = jitted.lower(*statics, *shaped).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert need < HBM_BYTES, f"program needs {need >> 20} MiB of HBM"
    return compiled


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# --- ragged.py: the three Mosaic DMA kernels (never run in interpret mode) ---
# geometries: the strings_mixed12 x 1M axis of chip_smoke.py (M=176-byte
# rows, 122.7 MB of row bytes); the segmented copy at tools/tpu_check.py's

@pytest.mark.parametrize("statics,n_pad,offs_rows", [
    ((16384, 16, 1, 128, 4, 8192), 1048576, 10240),      # strings 1M
])
def test_ragged_pack_kernel(one_chip, statics, n_pad, offs_rows):
    nblocks, _sb, mws = statics[:3]
    with jax.enable_x64(False):
        c = _compile(one_chip, ragged._pack_call(*statics),
                     _s((nblocks,), jnp.int32), _s((nblocks,), jnp.int32),
                     _s((nblocks,), jnp.int32),
                     _s((offs_rows, 128), jnp.int32),
                     _s((n_pad, mws, 128), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("statics,flat_rows,offs_rows", [
    ((131072, 8, 1, 8, 2), 262144, 10240),               # strings 1M, fpv=74
])
def test_ragged_unpack_kernel(one_chip, statics, flat_rows, offs_rows):
    nblocks = statics[0]
    with jax.enable_x64(False):
        c = _compile(one_chip, ragged._unpack_call(*statics),
                     _s((nblocks,), jnp.int32),
                     _s((offs_rows, 128), jnp.int32),
                     _s((flat_rows, 128), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


def test_ragged_segmented_copy_kernel(one_chip):
    statics = (6, 16, 8192, 128, 2)      # tools/tpu_check.py's gappy copy
    with jax.enable_x64(False):
        c = _compile(one_chip, ragged._segcopy_call(*statics),
                     _s((6,), jnp.int32), _s((6,), jnp.int32),
                     _s((6,), jnp.int32), _s((8, 128), jnp.int32),
                     _s((8, 128), jnp.int32), _s((8, 128), jnp.int32),
                     _s((1280, 128), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


# --- fixed-width transcode at the nvbench axes --------------------------------

def _fixed_axis(n_cols, n=1_000_000):
    schema = [CYCLE[i % len(CYCLE)] for i in range(n_cols)]
    datas = tuple(
        _s((n, 2), jnp.uint32) if dt == sr.float64 else
        _s((n,), jnp.uint8 if dt == sr.bool8 else dt.storage)
        for dt in schema)
    has_valid = tuple(i % 3 == 0 for i in range(n_cols))
    valids = tuple(_s((n,), jnp.bool_) for hv in has_valid if hv)
    return compute_row_layout(schema), has_valid, datas, valids, n


@pytest.mark.parametrize("n_cols", [12, 212])
def test_fixed_to_rows_program(one_chip, n_cols):
    layout, has_valid, datas, valids, n = _fixed_axis(n_cols)
    _compile(one_chip, convert._to_rows_fixed_full, datas, valids,
             statics=(layout, has_valid, 0, n))


def _cell_config(name):
    """One of chipbench's fixed-width configurations (the reference
    benchmark's nine-type cycle): ``(config, schema)``."""
    import json
    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench",
                           "configs", name + ".json")) as f:
        cfg = json.load(f)
    return cfg, [getattr(sr, cfg["type_cycle"][i % len(cfg["type_cycle"])])
                 for i in range(cfg["columns"])]


def _cell_layout():
    """chipbench's fixed155_roundtrip: 155 columns, 1<<20 rows."""
    cfg, schema = _cell_config("nvbench_fixed155_1m")
    return compute_row_layout(schema), cfg["rows"]


@pytest.mark.parametrize("shape", [12, "cell155", 212])
def test_fixed_from_rows_program(one_chip, shape):
    if shape == "cell155":
        layout, n = _cell_layout()
    else:
        layout, _, _, _, n = _fixed_axis(shape)
    words = _s((n * layout.fixed_row_size // 4,), jnp.uint32)
    c = _compile(one_chip, convert._from_rows_fixed_full, words,
                 statics=(layout,))
    # word-major decode: no word column sliced out of the row-major [n, W]
    # matrix (padded 128x under the (8,128) tiling; 10.5 GiB of temporaries
    # at the cell's shape before PR 29)
    assert f"[{n},1]{{1,0:T(8,128)" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 3 << 30


# argument + output + temporary bytes of the two programs a C-ABI call
# runs at fixed155_cabi_t4's shape (compile rehearsal, PR 36).  Nothing
# stays on the chip between calls, so this is all a call holds there; four
# task threads that launch the same direction at the same instant hold four
# of them, 14.37 GB for ``from``: what PERF.md §7 says of a gate.
CABI_PROGRAM_BYTES = {"to": 3_524_081_664, "from": 3_593_147_904}


@pytest.mark.parametrize("direction", list(CABI_PROGRAM_BYTES))
def test_cabi_cell_programs_as_the_bridge_launches_them(one_chip, direction):
    cfg, schema = _cell_config("cabi_fixed155_1m")
    layout, n = compute_row_layout(schema), cfg["rows"]
    if direction == "to":
        # the bridge uploads payloads in their storage types and validity as
        # bools, for the nullable columns only
        has_valid = tuple(i % cfg["null_every"] == 0
                          for i in range(cfg["columns"]))
        datas = tuple(_s((n,), dt.storage) for dt in schema)
        valids = tuple(_s((n,), jnp.bool_) for hv in has_valid if hv)
        c = _compile(one_chip, convert._to_rows_fixed_full, datas, valids,
                     statics=(layout, has_valid, 0, n))
    else:
        # ... and the batch as uint32 words: the resident cells' program,
        # no bytes -> words pass (2.05 GB of temporaries of its own)
        assert cfg["batch_bytes"] == n * layout.fixed_row_size
        words = _s((cfg["batch_bytes"] // 4,), jnp.uint32)
        c = _compile(one_chip, convert._from_rows_fixed_full, words,
                     statics=(layout,))
    ma = c.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert abs(need - CABI_PROGRAM_BYTES[direction]) < 0.05 * need
    # two calls in flight always fit; four at the same instant may not
    assert 2 * need < 14.4e9


def _batches_cell():
    """chipbench's fixed212_roundtrip: the 212-column table at 2<<20 rows,
    in the two batches the reference's rule cuts it into."""
    cfg, schema = _cell_config("nvbench_fixed212_2m")
    n = cfg["rows"]
    datas = tuple(_s((n,), dt.storage) for dt in schema)
    has_valid = tuple(i % cfg["null_every"] == 0
                      for i in range(cfg["columns"]))
    valids = tuple(_s((n,), jnp.bool_) for hv in has_valid if hv)
    return (compute_row_layout(schema), has_valid, datas, valids,
            [tuple(b["rows"]) for b in cfg["derived"]["batches"]])


# argument + output + temporary bytes of each program (compile rehearsal,
# PR 34); beside ``from_rows`` of the first batch the cell also keeps the
# table (1.684 GB) and the second batch (0.285 GB): 10.9 GB of the ~14.4 a
# v5e leaves a program
BATCH_PROGRAM_BYTES = {("to", 0): 8_932_038_656, ("to", 1): 2_640_654_336,
                       ("from", 0): 8_932_339_200, ("from", 1): 1_189_716_480}


@pytest.mark.parametrize("direction,batch", list(BATCH_PROGRAM_BYTES))
def test_fixed_batch_programs_at_the_cells_shapes(one_chip, direction,
                                                  batch):
    layout, has_valid, datas, valids, bounds = _batches_cell()
    lo, hi = bounds[batch]
    assert (hi - lo) * layout.fixed_row_size < 2**31
    if direction == "to":
        c = _compile(one_chip, convert._to_rows_fixed_full, datas, valids,
                     statics=(layout, has_valid, lo, hi))
    else:
        words = _s(((hi - lo) * layout.fixed_row_size // 4,), jnp.uint32)
        c = _compile(one_chip, convert._from_rows_fixed_full, words,
                     statics=(layout,))
    ma = c.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    # the first batch's two are the largest; with the table and the other
    # batch resident they have to leave room under 14.4 GB
    assert need + 1_684_013_056 + 285_230_080 < 14.4e9
    assert abs(need - BATCH_PROGRAM_BYTES[direction, batch]) < 0.05 * need


def test_byte_views_of_the_largest_batch(one_chip):
    """``RowBatch.device_u8`` and ``convert_from_rows`` of a u8 batch at
    2,147,466,240 B: a bitcast between u32 [N] and u8 [N, 4] pads its
    minor axis 32x (68.7 GB; refused before PR 34)."""
    n_words = 1851264 * 290
    for jitted, arg in ((convert._words_to_bytes, _s((n_words,), jnp.uint32)),
                        (convert._bytes_to_words,
                         _s((4 * n_words,), jnp.uint8))):
        c = _compile(one_chip, jitted, arg)
        assert c.memory_analysis().temp_size_in_bytes < 6 << 30


# --- xpack: the strings engine, strings_mixed12 schema --------------------------

def test_xpack_to_rows_program(one_chip):
    # 64K rows, not the smoke's 1M: the program is the same shape-generic
    # slab/roll tree (same layout, same per-column windows) and compiles
    # in seconds instead of a minute
    import chip_smoke
    from spark_rapids_jni_tpu.utils import hostcache
    n = 1 << 16
    table = chip_smoke.build_table(n, 12, 3, 7)
    layout = compute_row_layout(table.schema)
    var_idx = layout.variable_column_indices
    col_offs = [hostcache.host_i64(table[ci].offsets) for ci in var_idx]
    lens = np.zeros(n, np.int64)
    for o in col_offs:
        lens += o[1:] - o[:-1]
    batches = build_batches(row_sizes_with_strings(layout, lens),
                            MAX_BATCH_BYTES)
    geom = xpack._plan_geometry(layout, n,
                                batches.row_offsets_within_batch[0],
                                col_offs)
    assert geom is not None, xpack.fallback_counts
    _compile(one_chip, xpack._to_rows_x_jit,
             tuple(c.data for c in table.columns),
             tuple(table[ci].offsets for ci in var_idx),
             tuple(c.validity for c in table.columns),
             statics=(layout, geom))


def _minor_to_major(text, shape):
    """The layouts the compiled program gives arrays of ``shape``, as the
    HLO text writes them (minor to major)."""
    dims = ",".join(str(d) for d in shape)
    return set(re.findall(rf"u32\[{dims}\]\{{([0-9,]+)", text))


def test_xtile_strings_levels_stay_words_major(one_chip):
    """The tiled strings programs at the cell's tile (8192 rows, 15 string
    columns of 0-32 B among 155): they compile for the chip, and every level
    of the per-string trees keeps the words on the untiled major axis —
    left alone, the compiler relabels the transposed slab gather and puts
    them back on the lanes, padded to 128 (PERF.md §6, PR 33)."""
    import functools
    from chipbench import datagen_strings
    from chipbench.drivers import transcode_strings
    from spark_rapids_jni_tpu.rowconv import xtile
    from spark_rapids_jni_tpu.utils import hostcache
    n = 2 * 8192
    table = transcode_strings.build_table(datagen_strings.strings_columns(
        n, 155, 33, 3, 0.9, {"dist": "normal", "lo": 0, "hi": 32}))
    layout = compute_row_layout(table.schema)
    var_idx = layout.variable_column_indices
    col_offs = [hostcache.host_i64(table[ci].offsets) for ci in var_idx]
    lens = np.zeros(n, np.int64)
    for o in col_offs:
        lens += o[1:] - o[:-1]
    batches = build_batches(row_sizes_with_strings(layout, lens),
                            MAX_BATCH_BYTES)
    geom = xtile.plan_to_rows(layout, n, batches.row_offsets_within_batch[0],
                              col_offs)
    assert geom is not None and geom[1:3] == (320, 8192), geom
    text = _compile(one_chip, xtile.to_rows_jit,
                    tuple(c.data for c in table.columns),
                    tuple(table[ci].offsets for ci in var_idx),
                    tuple(c.validity for c in table.columns),
                    statics=(layout, geom)).as_text()
    strings = (xtile.GROUP, 15 * 8192 // xtile.GROUP)
    for words in (geom[4] + 1, 98):        # the narrowest level, the widest
        assert _minor_to_major(text, (words,) + strings) == {"2,1,0"}, words

    # from_rows' per-string stage alone (its whole program compiles for a
    # minute and a half): groups of 8 rows, windows of 10 words, Bd 80
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    text = _compile(
        one_chip,
        jax.jit(functools.partial(xtile._group_chars, g=8, Lw=10, Bd=80)),
        jnp.zeros((8192, 98), jnp.uint32), i32((15, 8192)), i32((15, 8192)),
        i32((15, 8192)), i32((15 * 1024 + 1,))).as_text()
    for words in (10, 74):
        assert _minor_to_major(text, (words,) + strings) == {"2,1,0"}, words


# --- scan: the q6 columns at 6M rows, and the f64 bits boundary -----------------

def test_scan_decode_q6_columns(one_chip):
    # three 8-byte PLAIN columns in ONE program: with reshape(-1, 2) in
    # _device_plain_w this compile took 12 minutes and 3 GB of temporaries
    from spark_rapids_jni_tpu.parquet import decode as D
    from spark_rapids_jni_tpu.parquet import device_scan as DS
    n = 6_000_000
    plan = (("plain", (D.PT_INT64, sr.int64, False), 1),
            ("plain", (D.PT_DOUBLE, sr.float64, False), 1),
            ("plain", (D.PT_DOUBLE, sr.float64, False), 1),
            ("plain", (D.PT_INT32, sr.int32, False), 1))
    flat = (_s((2 * n,), jnp.uint32), _s((2 * n,), jnp.uint32),
            _s((2 * n,), jnp.uint32), _s((n,), jnp.uint32))
    c = _compile(one_chip, DS._decode_file_jit, flat, statics=(plan,))
    assert c.memory_analysis().temp_size_in_bytes < (512 << 20)


def test_f64_bits_to_values_and_q6(one_chip, as_tpu):
    from spark_rapids_jni_tpu.models import q6
    from spark_rapids_jni_tpu.utils import f64bits
    n = 6_000_000
    assert not f64bits.backend_has_f64_bitcast()
    # the arithmetic path: an f64 bitcast is what this compiler refuses
    _compile(one_chip, jax.jit(f64bits.from_bits), _s((n, 2), jnp.uint32))
    _compile(one_chip, jax.jit(f64bits.to_bits), _s((n,), jnp.float64))
    _compile(one_chip, q6.q6_kernel, _s((n,), jnp.int64),
             _s((n,), jnp.float64), _s((n,), jnp.float64),
             _s((n,), jnp.int32), _s((), jnp.int32), _s((), jnp.int32))


# --- join: q42's probe at the star cell's size ---------------------------------

def test_compare_probe_fuses_at_q42_size(one_chip, as_tpu):
    # 212 sorted build keys against the 10M-row fact: the compare fuses
    # into the sum over the key axis, so the program holds no [212, 10M]
    # array, no loop over levels or blocks, and no gather
    from spark_rapids_jni_tpu.ops import join_plan
    # (a fresh function: a trace is cached on the function, backend and all)
    probe = jax.jit(lambda k, q: join_plan._probe_compare.__wrapped__(k, q))
    c = _compile(one_chip, probe, _s((212,), jnp.int32),
                 _s((10_000_000,), jnp.int32))
    assert c.memory_analysis().temp_size_in_bytes == 0
    text = c.as_text()
    assert " while(" not in text and " gather(" not in text


@pytest.mark.parametrize("total,form", [(106_597, "block"),
                                        (10_000_000, "chunked")])
def test_pair_expansion_is_a_block_select_at_q42_size(one_chip, as_tpu,
                                                      total, form):
    # q42's expansion at the star cell's size, 106597 pairs over the
    # 10M-row fact: dense passes, a fused compare-count and gathers of
    # whole rows - no loop over search levels, no element gathered from a
    # 10M-row table, temporaries inside what the join site reserves.  An FK
    # join's 10M pairs go by in chunks: one loop over chunks, the same
    # gathers, temporaries that do not grow with the rows gathered.
    import time
    from spark_rapids_jni_tpu.ops import select
    n = 10_000_000
    assert select.form(total) == form
    # (a fresh function: a trace is cached on the function, backend and all)
    expand = jax.jit(lambda counts: select._owners_block.__wrapped__(
        counts, total, select.ROW_WORDS, select.COMPARE_TOP,
        select.CHUNK_PAIRS))
    t0 = time.perf_counter()
    c = _compile(one_chip, expand, _s((n,), jnp.int32))
    assert time.perf_counter() - t0 < 60
    assert c.memory_analysis().temp_size_in_bytes \
        <= select.temp_bytes(n, total)
    text = c.as_text()
    assert text.count(" while(") == (form == "chunked")
    pairs = min(total, select.CHUNK_PAIRS)
    gathers = re.findall(r"= (\S+) gather\(.*slice_sizes=\{([\d,]+)\}", text)
    assert gathers and all(
        shape.startswith(f"s32[{pairs},{select.ROW_WORDS}]")
        and sizes == f"1,{select.ROW_WORDS}" for shape, sizes in gathers), \
        gathers


# --- four chips: the shuffle step as ONE program across the 2x2 mesh ------------

def test_mesh_shuffle_program_four_chips(topo):
    # what chip_smoke.py --chips 4 runs: shard_map + all_to_all + psum at
    # 64K rows/device, compiled for the four described chips
    import __graft_entry__ as G
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rows = 1 << 16
    n = rows * 4
    sh = NamedSharding(mesh, P("data"))
    datas = tuple(jax.ShapeDtypeStruct((n,), dt.storage, sharding=sh)
                  for dt in G.FLAGSHIP_SCHEMA)
    valid = jax.ShapeDtypeStruct((n, len(G.FLAGSHIP_SCHEMA)), jnp.bool_,
                                 sharding=sh)
    compiled = G.make_shuffle_step(mesh, rows).lower(datas, valid).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "all-reduce" in text
