#!/usr/bin/env bash
# Nightly pipeline — the analog of the reference's ci/nightly-build.sh:25-30
# (mvn deploy of the cuda-classified jar after a full build): build, full
# test suite, driver-contract checks, the chip drive, the on-TPU validation
# sweep and the benchmark's cells, with every artifact dropped under
# target/nightly/ for archival.
#
# Usage: ci/nightly.sh [--no-tpu]   (--no-tpu skips chip-bound stages)
set -euo pipefail
cd "$(dirname "$0")/.."
OUT=target/nightly
mkdir -p "$OUT"

echo "== build + wheel + provenance =="
bash ci/premerge.sh --skip-tests

echo "== full CPU suite (8-device virtual mesh) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q | tee "$OUT/pytest.log"

echo "== multichip dryrun (driver contract) =="
env XLA_FLAGS= JAX_PLATFORMS= python __graft_entry__.py dryrun 8 \
    | tee "$OUT/dryrun.log"

if [[ "${1:-}" != "--no-tpu" ]]; then
    echo "== the main path end to end on the chip, every answer checked =="
    python chip_smoke.py | tee "$OUT/chip_smoke.log"

    echo "== on-TPU validation sweep =="
    python tools/tpu_check.py "$OUT/tpu_check.json" || true

    echo "== the benchmark's cells, one process each (BENCHMARK.json) =="
    for cell in $(python3 -c 'import json
print(*[w["name"] for w in json.load(open("BENCHMARK.json"))["workloads"]])'); do
        python3 -m chipbench --workload "$cell" --seed 7 --seconds 51 --trace 0 \
            | tail -1 > "$OUT/$cell.json" || true
    done
fi

cp -f target/dist/*.whl "$OUT"/ 2>/dev/null || true
cp -f target/version-info.properties "$OUT"/ 2>/dev/null || true
echo "nightly artifacts in $OUT/:"
ls -la "$OUT"
