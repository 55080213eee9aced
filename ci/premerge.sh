#!/usr/bin/env bash
# Premerge gate — the analog of the reference's ci/premerge-build.sh:26-29
# (`mvn verify -DBUILD_TESTS=ON` on a device runner): build the native
# artifact, stamp build provenance, run the full test suite, and — when a
# JDK is present — compile the Java tier.
#
# Usage: ci/premerge.sh [--skip-tests]
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== static analysis (srjt_lint) =="
ci/lint_smoke.sh

echo "== native build =="
make -C spark_rapids_jni_tpu/native -s clean
make -C spark_rapids_jni_tpu/native -s -j"$(nproc)"

echo "== build provenance =="
python ci/build_info.py

if command -v javac >/dev/null 2>&1; then
    echo "== java tier (compiled BEFORE the wheel so classes embed) =="
    CLASSDIR=spark_rapids_jni_tpu/java_classes
    rm -rf "$CLASSDIR"                   # no orphaned .class files
    mkdir -p "$CLASSDIR"
    [[ -f "$CLASSDIR/__init__.py" ]] || cat > "$CLASSDIR/__init__.py" <<'PYEOF'
"""Compiled Java tier (present only when the wheel was built with a JDK —
the reference jar's .class payload analog, pom.xml:450-471)."""
PYEOF
    javac -d "$CLASSDIR" $(find java -name '*.java')
    if command -v java >/dev/null 2>&1; then
        echo "== java tier: JVM smoke (RowConversionSmoke) =="
        java -Dsrjt.native.path="$(pwd)/spark_rapids_jni_tpu/native/libsrjt.so" \
            -cp "$CLASSDIR" com.tpu.rapids.jni.RowConversionSmoke \
            | tee target/java_smoke.log
    fi
else
    echo "== java tier: no javac in environment, skipped =="
fi

echo "== wheel packaging (jar-with-embedded-.so analog) =="
python -m pip wheel . --no-deps --no-build-isolation -q -w target/dist
python - <<'PYEOF'
import glob, zipfile
w = sorted(glob.glob("target/dist/*.whl"))[-1]
names = zipfile.ZipFile(w).namelist()
for so in ("native/libsrjt.so", "native/libsrjt_parquet.so"):
    assert any(n.endswith(so) for n in names), f"{so} missing from wheel"
print(f"wheel OK: {w}")
PYEOF

if [[ "${1:-}" != "--skip-tests" ]]; then
    echo "== tests =="
    python -m pytest tests/ -q
fi

echo "premerge OK"
