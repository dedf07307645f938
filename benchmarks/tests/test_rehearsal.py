"""Each cell end to end at a few MiB with the owner on the CPU: the
whole harness but its look for a chip. A rehearsal ends with exit code
10, ``REHEARSAL`` as the last line of stdout and no result line there,
so it can never be read as a chip run; what would have been the result
is on stderr behind ``REHEARSAL-RESULT``.

Also here: the controls (one stated guarantee broken each: rf=1, no
fsync) and the timed path broken underneath (chunk files of stored
objects cut short) — all have to come out ``correct: false``.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

import run

BENCH = Path(__file__).resolve().parent.parent
_BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in _BENCHMARK["workloads"]]
_CONFIG_FILE = {c["name"]: BENCH.parent / c["file"]
                for c in _BENCHMARK["configs"]}
CONTROLS = [(w["name"], control) for w in _BENCHMARK["workloads"]
            for control in json.loads(
                _CONFIG_FILE[w["config"]].read_text())["controls"]]
MARK = "REHEARSAL-RESULT "


def rehearsed(out: str, err: str) -> dict:
    """The rehearsal's result from stderr, once stdout has been seen to
    end with the mark and to hold nothing a reader could take for a
    result line."""
    lines = out.strip().splitlines()
    assert lines[-1] == "REHEARSAL"
    assert not any(line.lstrip().startswith("{") for line in lines)
    found = [line for line in err.splitlines() if line.startswith(MARK)]
    return json.loads(found[-1][len(MARK):])


def rehearse(cell: str, *extra: str) -> dict:
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "4", "--rehearse-cpu",
         *extra], capture_output=True, text=True, timeout=900)
    assert done.returncode == run.EXIT_REHEARSAL, done.stderr[-3000:]
    return rehearsed(done.stdout, done.stderr)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_correct(cell, trace):
    result = rehearse(cell, "--trace", str(trace))
    assert tuple(result) == run.RESULT_KEYS      # no device ops on a CPU
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert "setup_s" in result["metrics"] or trace
    assert len(result["metrics"]) >= 2
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell,control", CONTROLS)
def test_control_is_not_correct(cell, control):
    result = rehearse(cell, "--control", control)
    assert result["correct"] is False


def _cut_chunks_short(data_root: Path, session: list) -> None:
    """The timed path broken underneath: every stored chunk file loses
    its last byte (an answer altered where it is produced)."""
    for path in data_root.glob("node-*/chunks/*/*"):
        raw = path.read_bytes()
        path.write_bytes(raw[:-1])


@pytest.mark.parametrize("cell", CELLS)
def test_broken_store_is_not_correct(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                   "3", "--rehearse-cpu"],
                  hooks={"before_check": _cut_chunks_short})
    captured = capsys.readouterr()
    assert rc == run.EXIT_REHEARSAL
    assert rehearsed(captured.out, captured.err)["correct"] is False


def test_no_owner_means_no_result_line(tmp_path):
    """Outside a checkout that holds the program the owner cannot start:
    exit 1 and nothing on stdout that parses as a result."""
    import shutil

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert done.returncode == 1
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
