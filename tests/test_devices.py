"""support/devices.py: compile-cache placement, the explicit-lanes
device requirement, counted device errors; native/ build keying."""

import importlib.util
import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FLAG_ARRAY = REPO / "tests/fixtures/testdata/inputs/flag_array.sol.o"


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR places the cache and the code sets no
    other directory; unset, the cache is <checkout>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c",
         "from mythril_tpu.support.devices import enable_compile_cache\n"
         "enable_compile_cache()\n"
         "import jax\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=str(REPO), env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr
    expect = tmp_path if placed else REPO / ".jax_cache"
    assert out.stdout.strip() == str(expect)


def test_require_device(monkeypatch):
    from mythril_tpu.support import devices

    monkeypatch.setattr(devices, "_DEVICE_ERROR", "RuntimeError: gone")
    devices.require_device(0)  # host-only never asks the device
    with pytest.raises(devices.DeviceUnavailable, match="--tpu-lanes 64"):
        devices.require_device(64)
    monkeypatch.setattr(devices, "_DEVICE_ERROR", "")
    devices.require_device(64)


def test_explicit_lanes_without_device_fail_the_run(monkeypatch):
    """`myth analyze --tpu-lanes 64` with a device that cannot execute
    exits with a clear error instead of finishing on the host."""
    from mythril_tpu.interfaces import cli
    from mythril_tpu.support import devices
    from mythril_tpu.support.support_args import args

    monkeypatch.setattr(devices, "_DEVICE_ERROR", "RuntimeError: gone")
    monkeypatch.setattr(args, "tpu_lanes", args.tpu_lanes)
    monkeypatch.setattr(sys, "argv", [
        "myth", "analyze", "-f", str(FLAG_ARRAY), "-t", "1",
        "-m", "EtherThief", "--tpu-lanes", "64", "--no-onchain-data",
        "-o", "json"])
    out = io.StringIO()
    with redirect_stdout(out), pytest.raises(SystemExit) as exc:
        cli.main()
    assert exc.value.code == 1
    report = json.loads(out.getvalue())
    assert report["success"] is False
    assert "needs a JAX device that executes" in report["error"]


def test_note_device_error_counts_and_warns(monkeypatch):
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
    from mythril_tpu.support import devices
    from mythril_tpu.support.devices import note_device_error

    warned = []
    monkeypatch.setattr(devices.log, "warning",
                        lambda msg, *a: warned.append(msg % a))
    ss = SolverStatistics()
    n0 = ss.device_explore_errors
    note_device_error("device_explore_errors", "lane sweep",
                      RuntimeError("fault"))
    assert ss.device_explore_errors == n0 + 1
    assert warned and "lane sweep failed on the device" in warned[0]
    with pytest.raises(MemoryError):
        note_device_error("device_explore_errors", "lane sweep",
                          MemoryError())
    assert ss.device_explore_errors == n0 + 1


def test_native_build_keyed_on_source_hash(tmp_path):
    """A stale library whose hash file does not match the committed
    sources is rebuilt, whatever the file times say; a matching one is
    loaded as is."""
    src = REPO / "mythril_tpu" / "native"
    pkg = tmp_path / "native_copy"
    shutil.copytree(src, pkg, ignore=shutil.ignore_patterns(
        "_native.so*", "__pycache__"))
    (pkg / "_native.so").write_bytes(b"not a library")
    (pkg / "_native.so.sha256").write_text("stale\n")
    spec = importlib.util.spec_from_file_location(
        "native_copy", pkg / "__init__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.get_lib()
    assert (pkg / "_native.so.sha256").read_text().strip() \
        == mod._sources_hash()
    assert mod.keccak256(b"").hex().startswith("c5d24601")
    built = (pkg / "_native.so").stat().st_mtime_ns
    mod._lib = None
    mod.get_lib()  # hash matches: no rebuild
    assert (pkg / "_native.so").stat().st_mtime_ns == built
