"""The control comes out not correct: the reference computed with int8
(and with fp8) matrix products, put in the program's place on the same
prompts and served tokens, fails a limit that the program passes.  A tiny
cell on the CPU; on the chip the same comparison set the cells' limits
(``python3 -m bench.control``)."""

import pytest

import bench_cpu
from bench import check, peaks, run, spec


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    root = bench_cpu.make_root(tmp_path_factory.mktemp("control"))
    cell = spec.resolve("tiny.decode-offload", root)
    res = run.run(cell, 2 ** 31 + 29, 1.5, False, require_tpu=False,
                  peaks=peaks.PEAKS["TPU v5 lite"], kinds=("int8", "fp8"))
    return cell.cell["check"]["limits"], res["readings"]


def test_program_passes(readings):
    limits, r = readings
    assert check.verdict(r, limits)["ok"], r


@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_control_fails(readings, kind):
    limits, r = readings
    assert not check.verdict(r["control"][kind], limits)["ok"], r
