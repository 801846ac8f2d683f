"""The control that `correct` has to fail, at a size a test can hold.

On the chip, at each cell's own size, `benchmark/control.py` read the sound
runs and the control over a dozen seeds (PERF.md has the readings). Here the
same comparison runs on the CPU on an MNIST-sized model: the program's
numbers pass the committed limits, and the plain reference put in the
program's place in float8 fails them. (The program runs in float32 here: at
d=128 and a batch of 8 bfloat16's own error is several times what it is at
the cells' sizes, where the limits were read.)"""
import pytest

from benchmark import control
from benchmark import correct as cmp
from tiny import on_cpu, tiny_cell


@pytest.mark.parametrize("name", ["flagship.train", "local1024.train"])
def test_float8_reference_fails_a_training_cells_limits(name, capsys):
    cell = tiny_cell(name)
    with on_cpu():
        rows = control.train_readings(cell, [11, 2**31 + 7, 12345], "float8")
    capsys.readouterr()
    for r in rows:
        assert cmp.judge(r["sound"], cell["limits"])["ok"], r
        assert not cmp.judge(r["control"], cell["limits"])["ok"], r


def test_float8_reference_fails_the_serving_cells_limit(capsys):
    cell = tiny_cell("flagship.serve-steady")
    with on_cpu():
        rows = control.serve_readings(cell, [11, 2**31 + 7, 12345], "float8", 1.0)
    capsys.readouterr()
    for r in rows:
        assert cmp.judge(r["sound"], cell["limits"])["ok"], r
        assert not cmp.judge(r["control"], cell["limits"])["ok"], r
