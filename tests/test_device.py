"""The device seam (utils/device.py): which platform a process computes
on is decided once and said out loud; nothing falls back. Plus
chip_smoke.py's CPU rehearsal — the command that proves the served path
on the chip, debugged where there is no chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import dfs_tpu.utils.device as device

REPO = Path(__file__).resolve().parent.parent


def _env(**over) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(REPO)
    env.update(over)
    return env


# -- compile cache ----------------------------------------------------------

def test_compile_cache_dir_honours_the_variable(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.compile_cache_dir() == tmp_path
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == tmp_path
    # the environment named it: code sets no other
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default_is_fixed_and_git_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert device.compile_cache_dir() == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_conftest_forces_cpu_without_private_jax_imports():
    import jax

    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert jax.default_backend() == "cpu"
    private = "jax." + "_src"       # spelled apart: this file is grepped too
    assert private not in (REPO / "tests" / "conftest.py").read_text()


# -- which platform ---------------------------------------------------------

def test_wants_tpu_rule(monkeypatch):
    """JAX_PLATFORMS decides when set (cpu = CPU on purpose); otherwise
    the PCI bus does — no threshold, no timeout, no re-probe."""
    monkeypatch.setattr(device, "tpu_chips", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.cpu_on_purpose() and not device.wants_tpu()
    for v in ("tpu", "tpu,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", v)
        assert device.wants_tpu() and not device.cpu_on_purpose()
    monkeypatch.delenv("JAX_PLATFORMS")
    assert device.wants_tpu()
    monkeypatch.setattr(device, "tpu_chips", lambda: 0)
    assert not device.wants_tpu()


def test_require_tpu_names_the_platform_it_found(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")     # backend is up: cpu
    with pytest.raises(device.DeviceError) as e:
        device.require_tpu("this test")
    assert "'cpu'" in str(e.value) and "--sidecar-port" in str(e.value)


def test_sharded_steps_raise_where_the_device_was_asked_for(monkeypatch):
    """Only the CPU-on-purpose rehearsal degrades to the host engine;
    anywhere else too few devices (or a refused kernel) is an error."""
    from dfs_tpu.fragmenter.sharded_common import ShardedSteps

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(device.DeviceError):
        ShardedSteps(2, lambda mesh: object()).get()


@pytest.mark.parametrize("argv", [
    ["sidecar", "--fragmenter", "cdc-anchored-tpu", "--sidecar-port", "0"],
    ["serve", "--node-id", "1", "--nodes", "1", "--replication-factor",
     "1", "--fragmenter", "cdc-anchored-tpu"],
])
def test_cli_refuses_a_tpu_engine_without_a_tpu(argv, tmp_path):
    """No CPU engine, no interpret mode, no XLA twin after a failed TPU
    init: the process exits non-zero within seconds, naming the backend
    and the one-owner rule."""
    if argv[0] == "serve":
        argv = argv + ["--data-root", str(tmp_path)]
    r = subprocess.run(
        [sys.executable, "-m", "dfs_tpu.cli.main", *argv], cwd=tmp_path,
        env=_env(JAX_PLATFORMS="tpu"), capture_output=True, text=True,
        timeout=120)
    assert r.returncode == 1, r.stderr[-2000:]
    last = r.stderr.strip().splitlines()[-1]
    assert last.startswith("error: ") and "'tpu'" in last \
        and "--sidecar-port" in last
    assert "listening" not in r.stdout


# -- native build cache -----------------------------------------------------

def test_native_artifact_is_keyed_on_source_flags_and_cpu(tmp_path):
    from dfs_tpu import native

    src = tmp_path / "x.cpp"
    src.write_text("int f() { return 1; }\n")
    a = native._artifact(src, ("-O2",), ".so")
    assert a.parent == native._BUILD_DIR and a.name.startswith("x-")
    assert native._artifact(src, ("-O3",), ".so") != a
    src.write_text("int f() { return 2; }\n")
    assert native._artifact(src, ("-O2",), ".so") != a
    assert native.engine() in ("native", "numpy")


# -- chip_smoke.py ----------------------------------------------------------

def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    (tmp_path / "chip_smoke.py").write_bytes(
        (REPO / "chip_smoke.py").read_bytes())
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode not in (0, 10)
    assert r.stdout.strip() == ""


def test_chip_smoke_verdict_line_has_exactly_the_contract_keys():
    """The last stdout line of a passing chip run is the driver's
    contract object and nothing more; the rich summary is the line
    before it. A device the owner did not report is no verdict."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    summary = {"ok": True, "phases": {"a": True}, "claim": None,
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1, "regions": 17}}
    assert json.loads(smoke.verdict_line(summary)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    summary["device"] = {"platform": None, "kind": None, "count": None}
    with pytest.raises(SystemExit):
        smoke.verdict_line(summary)


def test_chip_smoke_rehearsal_runs_end_to_end():
    """Owner + three nodes + upload/dedup/download/range/census on the
    CPU: every phase passes, and the run can still never be read as a
    pass — exit code 10, ``ok`` false, ``REHEARSAL`` as the last line."""
    r = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse-cpu"],
                       cwd=REPO, env=_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 10, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines[-1] == "REHEARSAL"
    out = json.loads(lines[-2])
    assert out["rehearsal"] and not out["ok"] and out["error"] is None
    assert out["phases"] and all(out["phases"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["regions"]["v1"] >= 1 and out["regions"]["v2"] >= 1
    assert lines[-2].endswith('"claim": null}')
