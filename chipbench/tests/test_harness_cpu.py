"""The harness driven end to end on the CPU at the tiny variants, Pallas in
interpret mode: the served answers pass the comparison; a served answer
altered where it is produced fails it; and the command refuses to run
without a TPU or without the program."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness

ROOT = harness.ROOT


def _bconv(ci, co, k=3, s=1, p=1, first=False):
    d = dict(type="bconv", c_in=ci, c_out=co, kernel=k, stride=s, pad=p)
    return dict(d, first=True) if first else d


def _pool(w=2, s=2, pad=(0, 0)):
    return dict(type="pool", window=w, stride=s, pad=list(pad))


# the program's variant="tiny" nets, layer for layer
TINY_YOLO = [_bconv(3, 16, first=True), _pool(), _bconv(16, 32), _pool(),
             _bconv(32, 64), _pool(2, 1, (0, 1)), _bconv(64, 64),
             dict(type="fconv", c_in=64, c_out=125, kernel=1, stride=1,
                  pad=0)]
TINY_ALEX = [_bconv(3, 32, 5, 2, 2, True), _pool(), _bconv(32, 48), _pool(),
             dict(type="bdense", d_in=192, d_out=64),
             dict(type="bdense", d_in=64, d_out=64),
             dict(type="fdense", d_in=64, d_out=10)]


def tiny_cell(name: str) -> harness.Cell:
    """The BENCHMARK.json cell at the program's tiny variant: its own mode,
    traffic kind and metrics, with shapes a CPU can serve."""
    cell = harness.load_cell(name)
    if cell.config["task"] == "detect":
        cell.config = dict(cell.config, variant="tiny", layers=TINY_YOLO,
                           input_hw=[32, 32])
        cell.traffic = dict(cell.traffic, streams=3, frames_per_resolution=2,
                            resolutions=[[24, 32], [40, 30], [32, 32]],
                            buckets=[1, 2, 4])
    else:
        cell.config = dict(cell.config, variant="tiny", layers=TINY_ALEX,
                           input_hw=[16, 16])
        cell.traffic = dict(cell.traffic, clients=4, images=8, buckets=[2])
    return cell


SEED = 2**31 + 12345


def run(cell, monkeypatch=None, alter=None, seconds=0.6):
    if alter is not None:
        from repro.workloads import WorkloadEngine

        compile_ = WorkloadEngine.compile

        def broken(self, *a, **kw):
            exe = compile_(self, *a, **kw)
            return lambda x: alter(exe(x))

        monkeypatch.setattr(WorkloadEngine, "compile", broken)
    return harness.run_cell(cell, SEED, seconds, trace=False,
                            t_start=time.perf_counter(), log=lambda s: None)


@pytest.mark.parametrize("name", ["yolo416_camera", "alexnet227_offline"])
def test_tiny_run_is_correct(name):
    line = run(tiny_cell(name))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    cell = harness.load_cell(name)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["device"]["platform"] == "cpu"


def _shift_box(out):
    return out.at[:, 0, 0:4].add(50.0)       # first detection 50 px off


def _change_class(out):
    return out.at[:, 0, 5].set((out[:, 0, 5] + 1) % 20)


def _drop_detection(out):
    return out.at[:, 0].set(0.0)             # first detection left out


def _swap_classes(out):
    return out.at[:, 0, 1].multiply(0.5)     # top-1 probability halved


@pytest.mark.parametrize("name,alter", [
    ("yolo416_camera", _shift_box),
    ("yolo416_camera", _change_class),
    ("yolo416_camera", _drop_detection),
    ("alexnet227_offline", _swap_classes),
])
def test_altered_answer_is_not_correct(monkeypatch, name, alter):
    line = run(tiny_cell(name), monkeypatch, alter)
    assert not line["correct"], line["checks"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "yolo416_camera",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj), line


def test_command_refuses_without_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert "needs 1 TPU" in proc.stderr
    _no_result(proc)


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)
