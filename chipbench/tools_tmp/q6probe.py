"""Does a scan's time follow the blob?  One process, several blobs."""
import os, sys, time, statistics
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import numpy as np
from chipbench import harness, datagen
cell = harness.Cell("q6_scan")
assert harness.find_chip(cell)
from spark_rapids_jni_tpu.models import q6
import pyarrow as pa, pyarrow.parquet as pq, io

def blob(seed, order=None):
    raw, arrays = datagen.tpch_q6_parquet(6_000_000, seed, 1048576)
    if order is None:
        return raw
    t = pq.read_table(io.BytesIO(raw))
    t = t.take(pa.array(np.random.default_rng(order).permutation(t.num_rows)))
    buf = io.BytesIO()
    pq.write_table(t, buf, compression="SNAPPY", use_dictionary=False, row_group_size=1048576)
    return buf.getvalue()

blobs = [("s17", blob(17)), ("s65537", blob(65537)), ("s17p1", blob(17, 1)), ("s17p2", blob(17, 2)),
         ("s3000000011", blob(3000000011)), ("s123456789", blob(123456789))]
for rep in range(3):
    for name, raw in blobs:
        q6.run(raw, 8766, 9131)
        ts = []
        for _ in range(8):
            t = time.perf_counter(); q6.run(raw, 8766, 9131); ts.append(time.perf_counter() - t)
        print(rep, name, len(raw), "median %.4f min %.4f max %.4f" % (statistics.median(ts), min(ts), max(ts)), flush=True)
