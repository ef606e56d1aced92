"""Whole rehearsal runs through the one command — and the same runs
with the timed path broken underneath, which must come out as not
correct. The harness's look for a chip is the only thing skipped
(`--rehearsal 1`: toy widths, CPU ranks)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))


def _command(cell: str, trace: int, seed: int) -> list:
    return [sys.executable, os.path.join(mf.HERE, "run.py"),
            "--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), "--rehearsal", "1"]


@pytest.mark.parametrize("cell, trace", [
    ("opt30b-train-t1024", 0), ("opt30b-train-t2048", 1),
    ("osu-allreduce-4rank", 0), ("osu-allreduce-4rank", 1)])
def test_rehearsal_run_is_correct_and_claims_no_device_number(cell, trace):
    p = subprocess.run(_command(cell, trace, 2**31 + 17),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"] == {}  # never a rate under a device name
    assert "REHEARSAL counts" in p.stdout
    # the same line, with metrics, is what the checker holds a chip
    # run to; here it must say that they are all missing
    errs = cm.check_line(mf.load(), cell, trace,
                         p.stdout.strip().splitlines()[-1])
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("cell, fault, failing", [
    ("opt30b-train-t1024", "unchanged_state", "param_change_norm_gap"),
    ("osu-allreduce-4rank", "skipped_rank", "sum_gap"),
    ("osu-allreduce-4rank", "altered_answer", "sum_gap")])
def test_broken_timed_path_comes_out_not_correct(cell, fault, failing,
                                                 tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = cell, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(cell),
                                   str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_rank.py"), fault]
    p = subprocess.run(argv, env=bench_run.child_env(),
                       capture_output=True, text=True, timeout=600,
                       cwd=mf.ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines()
                if ln.startswith(RESULT_TAG))
    assert json.loads(line[len(RESULT_TAG):])["correct"] is False
    assert f"check {failing}:" in p.stdout
    assert "NOT CORRECT" in next(
        ln for ln in p.stdout.splitlines()
        if ln.startswith(f"check {failing}:"))


def test_no_program_no_result(tmp_path):
    """BENCHMARK.json and benchmark/ alone are not a run: another exit
    code than 0 and no result line."""
    import shutil

    shutil.copy(mf.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(mf.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "opt30b-train-t1024", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True,
        timeout=120, cwd=tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert "correct" not in p.stdout
