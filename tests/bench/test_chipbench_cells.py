"""Each cell's set-up and a few window iterations at a tiny size on the
CPU, and the command's refusals."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench_tiny import CELLS, ROOT, run_tiny  # noqa: E402

from bench import manifest, peaks  # noqa: E402


@pytest.mark.parametrize("cell", CELLS)
def test_cell_sets_up_and_runs(cell):
    r, lines = run_tiny(cell)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in manifest.Manifest().metrics_of(cell, False)}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"          # compared numbers come last
    assert set(r["checks"]) == {"rel_err", "bad_outputs"}
    # the set-up breakdown is printed step by step before the window
    steps = [ln for ln in lines if ln.startswith("[setup] ")]
    assert any("warm-up" in ln for ln in steps)
    assert any(ln.startswith("[setup] total") for ln in steps)


@pytest.mark.parametrize("cell", ["gcn2-molhiv"])
def test_traced_run_reads_the_counters(cell):
    r, _ = run_tiny(cell, trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert m["compiles_in_window.serve"]["value"] == 0
    assert m["groups_per_request.serve"]["value"] >= 1
    # the CPU has no TPU plane, so no device metric can be read here
    assert "device_idle_share.serve" not in m and "busy_s" not in r["device"]


def _run_command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gcn2-rgg45k-full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_command_refuses_without_a_tpu():
    p = _run_command(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's
    paths has no system to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(manifest.BenchError):
        peaks.peaks("TPU v99")


def test_full_loop_reads_its_programs_memory():
    """The full loop's program plans temporaries that the allocator's peak
    may leave out; ``program_memory`` reads them from the compiled program
    the window runs."""
    seen = {}
    r, _ = run_tiny("gcn2-rgg45k-full",
                    patch=lambda loop, state: seen.update(
                        loop.program_memory(state)))
    assert r["correct"] is True
    assert seen["temp"] > 0 and seen["arguments"] > 0 and seen["outputs"] > 0
