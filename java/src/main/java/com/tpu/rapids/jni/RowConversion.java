/*
 * Columnar <-> JCUDF row transcode, the framework's flagship API.
 *
 * Capability parity with the reference's RowConversion (RowConversion.java
 * :101-125): convertToRows produces row batches in the JCUDF format,
 * convertFromRows rebuilds columns from one batch plus a (typeId, scale)
 * schema.  The engine underneath is TPU-native (XLA/Pallas on device,
 * host_table.cpp on host) instead of CUDA.
 *
 * JCUDF row format (bit-identical to the reference's spec,
 * RowConversion.java:40-99):
 *   - rows are C-struct-like; each fixed-width column slot is aligned to
 *     its own byte size, string columns hold an 8-byte (offset,length)
 *     pair aligned to 4;
 *   - one validity bit per column, bit i of validity byte b = column
 *     b*8+i, bytes appended after the last data slot;
 *   - string chars follow the validity bytes; every row is padded to an
 *     8-byte boundary;
 *   - each output batch stays under 2GB (int32 offsets), split at 32-row
 *     multiples; a row only has to fit a batch (no 1KB limit, as in the
 *     reference's convert_to_rows).
 */
package com.tpu.rapids.jni;

public final class RowConversion {
  static {
    NativeDepsLoader.loadNativeDeps();
  }

  private RowConversion() {}

  /** One or more ≤2GB JCUDF row batches (LIST&lt;INT8&gt; analog). */
  public static final class RowBatches implements AutoCloseable {
    private long handle;

    RowBatches(long handle) {
      this.handle = handle;
    }

    public long getNativeHandle() {
      if (handle == 0) {
        throw new IllegalStateException("row batches closed");
      }
      return handle;
    }

    @Override
    public void close() {
      if (handle != 0) {
        freeRows(handle);
        handle = 0;
      }
    }
  }

  /**
   * Columnar table -> JCUDF row batches.  Served by the device engine
   * ({@code srjt_to_rows_device}, every batch of the table); the host C++
   * engine answers only where the device path returns null (no embedded
   * runtime, {@code SRJT_DEVICE=0}, or a failed call, which
   * {@code bridge.null.to} counts and the bridge logs).
   */
  public static RowBatches convertToRows(HostTable table) {
    return new RowBatches(convertToRows(table.getNativeHandle()));
  }

  /**
   * One JCUDF row batch -> columnar table.  {@code typeIds}/{@code scales}
   * mirror the reference's schema marshalling (RowConversion.java:110-120).
   *
   * <p>Which engine serves which batch: the device engine
   * ({@code srjt_from_rows_device}) decodes batch 0 only, the reference's
   * one-batch contract; for {@code batch > 0} the JNI wrapper goes straight
   * to the host C++ engine ({@code srjt_from_rows}), and no counter of the
   * bridge sees that call: the wrapper cannot reach the Python metrics
   * store ({@code bridge.null.from} counts only device calls that failed).
   * A caller that wants every batch of a table past 2 GB on the device
   * imports each batch as a handle of its own ({@link #importRows}).
   */
  public static HostTable convertFromRows(RowBatches rows, int batch,
      int[] typeIds, int[] scales) {
    return HostTable.wrap(
        convertFromRows(rows.getNativeHandle(), batch, typeIds, scales));
  }

  /** Wraps caller-owned row bytes (e.g. shuffle-received) as a batch. */
  public static RowBatches importRows(long dataAddress, long dataSize,
      long offsetsAddress, long rowCount) {
    return new RowBatches(
        importRows(dataAddress, dataSize, offsetsAddress, rowCount));
  }

  private static native long convertToRows(long tableHandle);

  private static native long convertFromRows(long rowsHandle, int batch,
      int[] typeIds, int[] scales);

  private static native long importRows(long dataAddress, long dataSize,
      long offsetsAddress, long rowCount);

  private static native void freeRows(long rowsHandle);
}
