"""The harness end to end at a smoke size on the CPU, and the CLI's
refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests.helpers import SECONDS, SEED, smoke_cell


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cell = smoke_cell(tmp_path_factory.mktemp("root"))
    run, sample, dev, short = harness.serve(cell, SEED, SECONDS)
    return cell, run, sample, dev, short


def test_new_cell_runs_and_is_correct(served):
    cell, run, sample, dev, short = served
    chk = harness.checks(cell, SEED, sample, short)
    res = harness.result(cell, run, dev, chk, trace=False)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tpot_p95_ms", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def test_lower_precision_control_is_not_correct(served):
    cell, run, sample, dev, short = served
    got = harness.compare(cell, SEED, sample, controls=("float8",))
    limit = cell.mix["check"]["gap_limit"]
    assert got["widest_gap"] <= limit
    assert got["control"]["float8"]["widest_gap"] > 3 * limit


def test_window_requests_are_measured(served):
    cell, run, sample, dev, short = served
    w0, w1 = run.window
    assert w1 - w0 >= SECONDS
    assert all(w0 <= r.t_sched < w1 for r in run.requests)
    assert all(r.finished and r.n_out == r.output_len
               for r in run.requests)
    assert sum(len(s[3]) for s in sample) >= \
        cell.mix["check"]["served_tokens"]


def _cli(cwd, extra_env):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "internlm2-1.8b.skew-drift-pinned", "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_cli_refuses_the_cpu():
    p = _cli(harness.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert p.stdout == ""


def test_cli_refuses_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
