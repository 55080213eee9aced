"""Drive the JNI bridge entry points through a ctypes-built mock JNIEnv.

The reference tests its JNI surface from JUnit through a real JVM
(RowConversionTest.java:29-59); without a JDK in this image, we construct
the JNI function table ourselves (slot numbers per the JNI 6 spec, matching
native/jni_min.h) and call the JNIEXPORT functions directly — exercising
handle unwrapping, schema marshalling, the column-release protocol, and
exception translation.
"""

import ctypes as C

import numpy as np
import pytest

import spark_rapids_jni_tpu as sr
from spark_rapids_jni_tpu.native import load

lib = load()
pytestmark = pytest.mark.skipif(lib is None, reason="libsrjt.so unavailable")

# JNI 6 slot numbers (jni_min.h)
SLOTS = 233
S_FINDCLASS, S_THROWNEW = 6, 14
S_GETSTRINGUTF, S_RELEASESTRINGUTF = 169, 170
S_GETARRAYLEN, S_GETOBJARRAYELT = 171, 173
S_NEWLONGARRAY = 180
S_GETINTREGION, S_GETLONGREGION = 203, 204
S_SETLONGREGION = 212

VOIDP = C.c_void_p


class MockEnv:
    """A JNINativeInterface_ table + object registry standing in for a JVM."""

    def __init__(self):
        self.objects = {}       # id -> python object ("jobject" handles)
        self.next_id = 1
        self.thrown = None      # (class_name, message)
        self._cbs = []          # keep callbacks alive
        table = (VOIDP * SLOTS)()

        def reg(obj):
            oid = self.next_id
            self.next_id += 1
            self.objects[oid] = obj
            return oid

        self.register = reg

        def put(slot, restype, argtypes, fn):
            cb = C.CFUNCTYPE(restype, *argtypes)(fn)
            self._cbs.append(cb)
            table[slot] = C.cast(cb, VOIDP)

        put(S_FINDCLASS, C.c_void_p, [VOIDP, C.c_char_p],
            lambda env, name: reg(("class", name.decode())))
        put(S_THROWNEW, C.c_int32, [VOIDP, C.c_void_p, C.c_char_p],
            self._throw_new)
        put(S_GETSTRINGUTF, C.c_void_p, [VOIDP, C.c_void_p, VOIDP],
            self._get_string_utf)
        put(S_RELEASESTRINGUTF, None, [VOIDP, C.c_void_p, C.c_char_p],
            lambda env, s, chars: None)
        put(S_GETARRAYLEN, C.c_int32, [VOIDP, C.c_void_p],
            lambda env, arr: len(self.objects[arr]))
        put(S_GETOBJARRAYELT, C.c_void_p, [VOIDP, C.c_void_p, C.c_int32],
            lambda env, arr, i: self.objects[arr][i])
        put(S_NEWLONGARRAY, C.c_void_p, [VOIDP, C.c_int32],
            lambda env, n: reg([0] * n))
        put(S_GETINTREGION, None,
            [VOIDP, C.c_void_p, C.c_int32, C.c_int32, C.POINTER(C.c_int32)],
            self._get_region)
        put(S_GETLONGREGION, None,
            [VOIDP, C.c_void_p, C.c_int32, C.c_int32, C.POINTER(C.c_int64)],
            self._get_region)
        put(S_SETLONGREGION, None,
            [VOIDP, C.c_void_p, C.c_int32, C.c_int32, C.POINTER(C.c_int64)],
            self._set_long_region)

        self._table = table
        # JNIEnv* = pointer to (pointer to table)
        self._table_p = C.cast(table, VOIDP)
        self.env = C.pointer(self._table_p)
        self._utf_bufs = []

    def _throw_new(self, env, cls, msg):
        self.thrown = (self.objects[cls][1], msg.decode())
        return 0

    def _get_string_utf(self, env, s, is_copy):
        buf = C.create_string_buffer(self.objects[s].encode())
        self._utf_bufs.append(buf)
        return C.cast(buf, VOIDP).value

    def _get_region(self, env, arr, start, n, out):
        vals = self.objects[arr]
        for i in range(n):
            out[i] = vals[start + i]

    def _set_long_region(self, env, arr, start, n, vals):
        target = self.objects[arr]
        for i in range(n):
            target[start + i] = vals[i]

    # helpers to build "jarray"/"jstring" handles
    def long_array(self, vals):
        return self.register([int(v) for v in vals])

    def int_array(self, vals):
        return self.register([int(v) for v in vals])

    def string_array(self, strs):
        return self.register([self.register(s) for s in strs])


def _fn(name, restype, argtypes):
    f = getattr(lib, name)
    f.restype = restype
    f.argtypes = argtypes
    return f


ENVP = C.POINTER(VOIDP)


def test_row_conversion_round_trip_through_jni():
    env = MockEnv()
    make_fixed = _fn("Java_com_tpu_rapids_jni_HostColumn_makeFixed",
                     C.c_int64, [ENVP, VOIDP, C.c_int32, C.c_int32,
                                 C.c_int64, C.c_int64, C.c_int64])
    make_table = _fn("Java_com_tpu_rapids_jni_HostTable_makeTable",
                     C.c_int64, [ENVP, VOIDP, C.c_void_p])
    to_rows = _fn("Java_com_tpu_rapids_jni_RowConversion_convertToRows",
                  C.c_int64, [ENVP, VOIDP, C.c_int64])
    from_rows = _fn("Java_com_tpu_rapids_jni_RowConversion_convertFromRows",
                    C.c_int64, [ENVP, VOIDP, C.c_int64, C.c_int32,
                                C.c_void_p, C.c_void_p])
    tbl_columns = _fn("Java_com_tpu_rapids_jni_HostTable_columns",
                      C.c_void_p, [ENVP, VOIDP, C.c_int64])
    col_close = _fn("Java_com_tpu_rapids_jni_HostColumn_close",
                    None, [ENVP, VOIDP, C.c_int64])
    tbl_close = _fn("Java_com_tpu_rapids_jni_HostTable_close",
                    None, [ENVP, VOIDP, C.c_int64])
    rows_free = _fn("Java_com_tpu_rapids_jni_RowConversion_freeRows",
                    None, [ENVP, VOIDP, C.c_int64])
    col_data = _fn("srjt_column_data", C.POINTER(C.c_uint8), [C.c_void_p])
    col_valid = _fn("srjt_column_valid", C.POINTER(C.c_uint8), [C.c_void_p])
    col_rows = _fn("srjt_column_rows", C.c_int64, [C.c_void_p])

    rng = np.random.default_rng(0)
    n = 1000
    i64 = rng.integers(-(2**60), 2**60, n, dtype=np.int64)
    i32 = rng.integers(-(2**30), 2**30, n, dtype=np.int32)
    valid32 = (rng.random(n) < 0.9).astype(np.uint8)

    h64 = make_fixed(env.env, None, int(sr.int64.id), 0, n,
                     i64.ctypes.data, 0)
    h32 = make_fixed(env.env, None, int(sr.int32.id), 0, n,
                     i32.ctypes.data, valid32.ctypes.data)
    assert h64 and h32 and env.thrown is None

    th = make_table(env.env, None, env.long_array([h64, h32]))
    assert th and env.thrown is None

    rows = to_rows(env.env, None, th)
    assert rows and env.thrown is None

    out_th = from_rows(env.env, None, rows, 0,
                       env.int_array([int(sr.int64.id), int(sr.int32.id)]),
                       env.int_array([0, 0]))
    assert out_th and env.thrown is None

    cols_arr = tbl_columns(env.env, None, out_th)
    handles = env.objects[cols_arr]
    assert len(handles) == 2

    got64 = np.ctypeslib.as_array(col_data(C.c_void_p(handles[0])),
                                  shape=(n * 8,)).view(np.int64)
    np.testing.assert_array_equal(got64, i64)
    got32 = np.ctypeslib.as_array(col_data(C.c_void_p(handles[1])),
                                  shape=(n * 4,)).view(np.int32)
    gotv = np.ctypeslib.as_array(col_valid(C.c_void_p(handles[1])),
                                 shape=(n,))
    np.testing.assert_array_equal(gotv, valid32)
    np.testing.assert_array_equal(got32[valid32 == 1], i32[valid32 == 1])
    assert col_rows(C.c_void_p(handles[0])) == n

    for h in handles:
        col_close(env.env, None, h)
    rows_free(env.env, None, rows)
    tbl_close(env.env, None, th)
    tbl_close(env.env, None, out_th)
    col_close(env.env, None, h64)
    col_close(env.env, None, h32)


def test_row_size_limit_throws_java_exception():
    # no 1KB limit any more: the 200 x int64 table (1632 B rows) converts
    # through the bridge, nothing is thrown, and its bytes equal the
    # reference packer's
    from spark_rapids_jni_tpu import Column, Table
    from spark_rapids_jni_tpu.rowconv import reference as ref
    env = MockEnv()
    make_fixed = _fn("Java_com_tpu_rapids_jni_HostColumn_makeFixed",
                     C.c_int64, [ENVP, VOIDP, C.c_int32, C.c_int32,
                                 C.c_int64, C.c_int64, C.c_int64])
    make_table = _fn("Java_com_tpu_rapids_jni_HostTable_makeTable",
                     C.c_int64, [ENVP, VOIDP, C.c_void_p])
    to_rows = _fn("Java_com_tpu_rapids_jni_RowConversion_convertToRows",
                  C.c_int64, [ENVP, VOIDP, C.c_int64])
    rows_free = _fn("Java_com_tpu_rapids_jni_RowConversion_freeRows",
                    None, [ENVP, VOIDP, C.c_int64])
    batches = _fn("srjt_rows_num_batches", C.c_int32, [C.c_void_p])
    batch_data = _fn("srjt_rows_batch_data", C.POINTER(C.c_uint8),
                     [C.c_void_p, C.c_int32])
    batch_size = _fn("srjt_rows_batch_size", C.c_int64,
                     [C.c_void_p, C.c_int32])

    n = 8
    rng = np.random.default_rng(200)
    datas = [rng.integers(-(2**60), 2**60, n, dtype=np.int64)
             for _ in range(200)]
    handles = [make_fixed(env.env, None, int(sr.int64.id), 0, n,
                          d.ctypes.data, 0) for d in datas]
    th = make_table(env.env, None, env.long_array(handles))
    out = to_rows(env.env, None, th)  # 200*8B + validity > 1KB
    assert out and env.thrown is None
    assert batches(C.c_void_p(out)) == 1
    size = batch_size(C.c_void_p(out), 0)
    got = np.ctypeslib.as_array(batch_data(C.c_void_p(out), 0),
                                shape=(size,)).copy()
    want, _ = ref.to_rows_np(Table([Column.from_numpy(d) for d in datas]))
    assert size == n * 1632
    np.testing.assert_array_equal(got, want)
    rows_free(env.env, None, out)


def test_string_round_trip_through_jni():
    env = MockEnv()
    make_string = _fn("Java_com_tpu_rapids_jni_HostColumn_makeString",
                      C.c_int64, [ENVP, VOIDP, C.c_int64, C.c_int64,
                                  C.c_int64, C.c_int64])
    make_fixed = _fn("Java_com_tpu_rapids_jni_HostColumn_makeFixed",
                     C.c_int64, [ENVP, VOIDP, C.c_int32, C.c_int32,
                                 C.c_int64, C.c_int64, C.c_int64])
    make_table = _fn("Java_com_tpu_rapids_jni_HostTable_makeTable",
                     C.c_int64, [ENVP, VOIDP, C.c_void_p])
    to_rows = _fn("Java_com_tpu_rapids_jni_RowConversion_convertToRows",
                  C.c_int64, [ENVP, VOIDP, C.c_int64])
    from_rows = _fn("Java_com_tpu_rapids_jni_RowConversion_convertFromRows",
                    C.c_int64, [ENVP, VOIDP, C.c_int64, C.c_int32,
                                C.c_void_p, C.c_void_p])
    tbl_columns = _fn("Java_com_tpu_rapids_jni_HostTable_columns",
                      C.c_void_p, [ENVP, VOIDP, C.c_int64])
    col_data = _fn("srjt_column_data", C.POINTER(C.c_uint8), [C.c_void_p])
    col_offsets = _fn("srjt_column_offsets", C.POINTER(C.c_int32),
                      [C.c_void_p])
    col_data_size = _fn("srjt_column_data_size", C.c_int64, [C.c_void_p])

    strs = ["hello", "", "tpu", "jcudf rows", "x" * 40]
    n = len(strs)
    chars = "".join(strs).encode()
    offsets = np.zeros(n + 1, dtype=np.int32)
    offsets[1:] = np.cumsum([len(s.encode()) for s in strs])
    chars_np = np.frombuffer(chars, dtype=np.uint8).copy()
    ints = np.arange(n, dtype=np.int32)

    hs = make_string(env.env, None, n, offsets.ctypes.data,
                     chars_np.ctypes.data, 0)
    hi = make_fixed(env.env, None, int(sr.int32.id), 0, n,
                    ints.ctypes.data, 0)
    th = make_table(env.env, None, env.long_array([hs, hi]))
    rows = to_rows(env.env, None, th)
    assert rows and env.thrown is None

    out_th = from_rows(env.env, None, rows, 0,
                       env.int_array([int(sr.string.id), int(sr.int32.id)]),
                       None)
    assert out_th and env.thrown is None
    handles = env.objects[tbl_columns(env.env, None, out_th)]
    offs = np.ctypeslib.as_array(col_offsets(C.c_void_p(handles[0])),
                                 shape=(n + 1,))
    np.testing.assert_array_equal(offs, offsets)
    size = col_data_size(C.c_void_p(handles[0]))
    got_chars = np.ctypeslib.as_array(col_data(C.c_void_p(handles[0])),
                                      shape=(size,))
    assert bytes(got_chars) == chars


def test_parquet_footer_through_jni():
    from spark_rapids_jni_tpu.parquet import (StructElement, ValueElement,
                                              read_and_filter)
    from spark_rapids_jni_tpu.parquet.footer import extract_footer_bytes
    from test_parquet_footer import simple_file

    data = extract_footer_bytes(simple_file(n=10))
    schema = StructElement("root", ValueElement("a"))
    expected = read_and_filter(data, 0, 1 << 30, schema)

    env = MockEnv()
    read_filter = _fn("Java_com_tpu_rapids_jni_ParquetFooter_readAndFilter",
                      C.c_int64, [ENVP, VOIDP, C.c_int64, C.c_int64,
                                  C.c_int64, C.c_int64, C.c_void_p,
                                  C.c_void_p, C.c_void_p, C.c_int32,
                                  C.c_uint8])
    num_rows = _fn("Java_com_tpu_rapids_jni_ParquetFooter_getNumRows",
                   C.c_int64, [ENVP, VOIDP, C.c_int64])
    num_cols = _fn("Java_com_tpu_rapids_jni_ParquetFooter_getNumColumns",
                   C.c_int64, [ENVP, VOIDP, C.c_int64])
    serialize = _fn(
        "Java_com_tpu_rapids_jni_ParquetFooter_serializeThriftFile",
        C.c_int64, [ENVP, VOIDP, C.c_int64, C.c_int64, C.c_int64])
    close = _fn("Java_com_tpu_rapids_jni_ParquetFooter_close",
                None, [ENVP, VOIDP, C.c_int64])

    buf = np.frombuffer(data, dtype=np.uint8).copy()
    flat_names, flat_nc, flat_tags = schema.flatten_depth_first()
    names = env.string_array(flat_names)
    nc = env.int_array(flat_nc)
    tags = env.int_array(flat_tags)

    h = read_filter(env.env, None, buf.ctypes.data, len(data), 0, 1 << 30,
                    names, nc, tags, len(schema.children), 0)
    assert env.thrown is None and h
    assert num_rows(env.env, None, h) == expected.num_rows == 10
    assert num_cols(env.env, None, h) == expected.num_columns == 1

    want = expected.serialize_thrift_file()
    out = np.zeros(len(want) + 64, dtype=np.uint8)
    written = serialize(env.env, None, h, out.ctypes.data, len(out))
    assert bytes(out[:written]) == want   # byte-identical to the python engine
    close(env.env, None, h)
