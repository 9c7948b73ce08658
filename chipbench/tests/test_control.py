"""The control of ``correct``: the reference one precision below the
configuration's (bfloat16 for its float32), put in the program's place,
must fail the comparison.

Run here on the CPU at the cells' own layer widths but small inputs (YOLO
at 64x64; AlexNet at 67x67, its first dense layer fed 1x1x256), so that a
test run holds it.  The readings at the cells' own sizes come from
``chipbench/control.py`` on the chip.  (At the program's tiny variants the
control flips no activation on some seeds and reads like a sound run.)"""

import copy

import pytest

from chipbench import compare, control, harness

from .test_harness_cpu import SEED


def small_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    if cfg["task"] == "detect":
        cfg["input_hw"] = [64, 64]
        cell.traffic = dict(cell.traffic, frames_per_resolution=2,
                            resolutions=[[48, 64], [72, 128], [108, 192]])
    else:
        cfg["input_hw"] = [67, 67]
        dense = next(i for i, l in enumerate(cfg["layers"])
                     if l["type"] == "bdense")
        cfg["layers"][dense] = dict(cfg["layers"][dense], d_in=256)
        cell.traffic = dict(cell.traffic, images=16)
    cell.config = cfg
    return cell


@pytest.mark.parametrize("name", ["yolo416_camera", "alexnet227_offline"])
@pytest.mark.parametrize("seed", [SEED, 7, 2**31 + 99])
def test_bf16_control_is_not_correct(name, seed):
    cell = small_cell(name)
    numbers = control.control_numbers(cell, seed)
    ok, checks = compare.verdict(numbers, cell.config["limits"])
    assert not ok, checks
