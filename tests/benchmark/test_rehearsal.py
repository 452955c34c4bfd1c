"""``benchmark/run.py`` end to end at toy sizes on the CPU.

``--rehearse N`` drives the runner's own code (pool, build, lower once,
warm-up, the closed loop, the checks, the readers) on N virtual devices.
What it computes is no measurement: the last line must say so by carrying
no metric and ``"correct": false``. Without the flag a CPU gets nothing."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
RUN = str(ROOT / "benchmark" / "run.py")
CELLS = [c["name"] for c in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
#: every cell on one device and on four, half of the runs traced
CASES = [(cell, n, (i + n // 4) % 2)
         for i, cell in enumerate(CELLS) for n in (1, 4)]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def start(cell, *extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, RUN, "--workload", cell, "--seed", "2147483659",
         "--seconds", "1", *extra],
        cwd=ROOT, env=env, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)


@pytest.fixture(scope="module")
def rehearsals():
    """All cases at once: each is a process of its own, as on the chip."""
    procs = {case: start(case[0], "--trace", str(case[2]),
                         "--rehearse", str(case[1])) for case in CASES}
    done = {}
    try:
        for case, proc in procs.items():
            out, err = proc.communicate(timeout=600)
            done[case] = (proc.returncode, out, err)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
    return done


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"{c[0]}-x{c[1]}-t{c[2]}")
def test_rehearsal_runs_the_runner_and_reports_nothing(rehearsals, case):
    cell, n_devices, _trace = case
    rc, out, err = rehearsals[case]
    assert rc == 0, err[-3000:]
    lines = [json.loads(l) for l in out.splitlines()]
    last = lines[-1]
    assert set(last) == RESULT_KEYS
    assert last["correct"] is False and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": n_devices, "memory_peak_bytes": 0}
    by_phase = {l["phase"]: l for l in lines[:-1]}
    assert by_phase["start"]["rehearsal"] is True
    # the metric code ran, and what it computed is marked as no measurement
    computed = by_phase["rehearsal_not_a_measurement"]["computed"]
    assert {"samples_per_s_per_chip", "step_ms_p90", "setup_s"} <= set(
        computed)
    window = by_phase["window"]
    assert window["steps"] == last["attempted"]
    assert window["compiles_in_window"]["backend"] == 0
    # every check but the one only a TPU can pass (kernels are interpreted)
    failed = [k for k, ok in window["checks"].items() if not ok]
    assert failed == ["mosaic_calls_in_step"]
    # where the configuration has a plain reference, the system's loss
    # (bf16, kernels interpreted here) agreed with it at toy width
    config = json.loads((ROOT / "benchmark" / "workloads" / (cell + ".json"))
                        .read_text())["config"]
    if (ROOT / "benchmark" / "reference" / (config + ".py")).exists():
        assert window["checks"]["reference"] is True
        assert by_phase["reference"]["rel_diff"] <= \
            by_phase["reference"]["rel_tol"]


def test_without_a_tpu_it_refuses():
    proc = start(CELLS[0], "--trace", "0")
    out, err = proc.communicate(timeout=300)
    assert proc.returncode != 0
    assert out.strip() == "", "no result may be printed off a TPU"
    assert "needs a TPU" in err
