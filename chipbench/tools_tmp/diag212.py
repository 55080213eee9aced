"""fixed212 with two earlier answers held: what fails?"""
import os, sys, time, json
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from chipbench import harness
cell = harness.Cell("fixed212_roundtrip")
dev = harness.find_chip(cell)
cfg = {**cell.config, "check_sample": {"calls": 2, "of_first": 12}}
r = harness.run_cell(cell, 7, 8.0, False, time.time(), dev, config=cfg)
print(json.dumps({k: r[k] for k in ("correct", "attempted", "failed", "device", "compared")}))
