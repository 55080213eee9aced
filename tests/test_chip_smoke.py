"""CPU rehearsal of ``chip_smoke.py``: every one-chip phase runs as a
function at a tiny size and its comparison passes; ``main()`` refuses to
report success off a TPU; the compile-cache rule resolves to one fixed
place.  What the smoke proves — that the path runs on the chip — only the
chip run proves; this keeps the script itself from rotting between runs.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from spark_rapids_jni_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phase_transcode_fixed_and_strings():
    out = chip_smoke.phase_transcode(n_rows=2000, oracle_rows=300)
    assert set(out) == {"fixed12", "fixed212", "strings_mixed12"}
    # 12-column cycle: 47 payload bytes + 2 validity bytes -> 56-byte rows
    assert out["fixed12"]["row_bytes"] == 2000 * 56


def test_phase_c_abi_round_trip():
    out = chip_smoke.phase_c_abi(n_rows=2000)
    assert out["rows"] == 2000


def test_phase_c_abi_fails_on_null_handle(monkeypatch):
    # the bridge turns any exception into a null handle (the JVM caller
    # then takes the host engine); the smoke must see that as a failure
    from spark_rapids_jni_tpu import bridge

    def boom(_table):
        raise RuntimeError("engine down")
    monkeypatch.setattr(bridge, "convert_to_rows", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="returned null"):
        chip_smoke.phase_c_abi(n_rows=64)


def test_phase_scan_q6():
    out = chip_smoke.phase_scan(n_rows=40_000)
    assert out["matched"] > 0 and out["host_fallback_cols"] == 0


def test_phase_scan_detects_a_wrong_answer(monkeypatch):
    from benchmarks import tpch_data
    real = tpch_data.q6_reference
    monkeypatch.setattr(tpch_data, "q6_reference",
                        lambda *a: (real(*a)[0] * 1.001, real(*a)[1]))
    with pytest.raises(chip_smoke.SmokeFailure, match="revenue"):
        chip_smoke.phase_scan(n_rows=20_000)


def test_phase_sql_served_queries():
    # q55 is the tier-1 rehearsal (one join); the other three texts go
    # through the same function on the chip and in tests/test_sql.py
    out = chip_smoke.phase_sql(n_sales=20_000, n_items=2000, n_stores=12,
                               queries=("q55",))
    q = out["q55"]
    assert q["rows"] > 0
    assert q["compiled.capture"] == 1 and q["exec.plan_cache.hit"] == 2


def test_main_refuses_without_a_tpu(capsys):
    rc = chip_smoke.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    res = json.loads(last)
    assert rc != 0
    assert res["ok"] is False and res["device"]["platform"] == "cpu"
    assert "no TPU" in res["error"]


def test_four_chip_option_needs_four_tpus(capsys):
    rc = chip_smoke.main(["--chips", "4"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0 and res["ok"] is False


def test_mesh_shuffle_on_virtual_devices():
    # the --chips 4 shuffle phase, on four of conftest's virtual devices
    out = chip_smoke.phase_mesh_shuffle(4)
    assert out["dropped"] == 0 and out["checksum"] == out["host_checksum"]


# --- the one compile-cache rule ------------------------------------------------

_PRINT_DIR = ("import jax; from spark_rapids_jni_tpu.utils import "
              "compile_cache as c; c.configure(); "
              "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_in_child(cwd, env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PRINT_DIR], cwd=cwd,
                         env=env, check=True, capture_output=True, text=True)
    return out.stdout.strip().splitlines()[-1]


def test_cache_rule_fixed_path_from_any_cwd(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    a = _cache_dir_in_child(str(tmp_path))
    b = _cache_dir_in_child(REPO)
    assert a == b == want and os.path.isabs(want)


def test_cache_rule_honours_the_environment(tmp_path, monkeypatch):
    mine = str(tmp_path / "operator_cache")
    assert _cache_dir_in_child(REPO, env_dir=mine) == mine
    # in-process: with the variable set, configure() sets no directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", mine)
    import jax
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == mine
    assert jax.config.jax_compilation_cache_dir == before


# --- no device benchmark reports success off a TPU ------------------------------

@pytest.mark.parametrize("script", ["tools/tpu_check.py", "chip_smoke.py"])
def test_device_scripts_exit_nonzero_without_a_tpu(script, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    args = [sys.executable, os.path.join(REPO, script)]
    if script == "tools/tpu_check.py":
        args.append(str(tmp_path / "check.json"))
    out = subprocess.run(args, cwd=REPO, env=env, capture_output=True,
                         text=True)
    assert out.returncode not in (0, None), out.stdout[-500:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False


def test_smoke_alone_in_a_directory_fails(tmp_path):
    # the driver also runs the script with nothing else of the repo
    # around it: that must fail, not pass vacuously
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True)
    assert out.returncode != 0
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"] is False
