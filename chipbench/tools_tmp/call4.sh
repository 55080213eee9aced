make -C spark_rapids_jni_tpu/native clean >/dev/null 2>&1
S="bash chipbench/tools_tmp/sets.sh"
A="3000000011 17 982451653 2147483659 65537 123456789"
$S warm fixed212_roundtrip 5 0 5
$S A fixed212_roundtrip 30 0 $A
$S B fixed212_roundtrip 30 0 $A
$S T fixed212_roundtrip 30 1 31 32 33
$S X fixed212_roundtrip 30 0 41 42 43
$S warm fixed12_roundtrip 5 0 5
$S A fixed12_roundtrip 30 0 $A
$S B fixed12_roundtrip 30 0 $A
$S T fixed12_roundtrip 30 1 31 32 33
$S X fixed12_roundtrip 30 0 41 42 43
