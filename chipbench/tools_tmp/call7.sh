make -C spark_rapids_jni_tpu/native clean >/dev/null 2>&1
S="bash chipbench/tools_tmp/sets.sh"
A="3000000011 17 982451653 2147483659 65537 123456789"
python3 chipbench/tools_tmp/diag212.py 2>&1 | grep -v "^  warnings\|UserWarning" | tail -25 | cut -c1-600
$S V2 fixed212_roundtrip 30 0 61 62 63
$S V2 fixed12_roundtrip 30 0 61 62 63
$S V2 q6_scan 30 0 61 62 63
$S T star_streams4 30 1 31
$S A star_streams4 30 0 $A
