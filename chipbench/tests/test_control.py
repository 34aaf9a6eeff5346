"""The control, at smoke width on the CPU: the reference put in the
program's place at float8 must come out not correct by at least one of a
cell's numbers, on three seeds, while the program, in the same runs, is
correct.  At the cells' own sizes ``chipbench/control.py`` reads the same
on the chip."""

import pytest

from chipbench.tests import smoke


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = smoke.make_root(str(tmp_path_factory.mktemp("bench")))
    smoke.add_cell(r, "lm.serve", "lm", smoke.SMOKE_LM, "serve", smoke.SERVE,
                   like="stablelm-1.6b.serve-decode")
    smoke.add_cell(r, "ed.train", "ed", smoke.SMOKE_ENCDEC, "train",
                   smoke.TRAIN, like="whisper-small.train-b32")
    return r


def fails(readings, checks):
    return any(readings[k] > lim for k, (_, lim) in checks.items()
               if k in readings)


@pytest.mark.parametrize("seed", [41, 42, 43])
def test_serve_control_is_not_correct(root, seed):
    out = smoke.run_control(root, "lm.serve", seed)
    assert all(v <= lim for v, lim in out["checks"].values())
    assert fails(out["control"]["fp8"], out["checks"])


@pytest.mark.parametrize("seed", [41, 42, 43])
def test_train_control_and_half_batch_are_not_correct(root, seed):
    out = smoke.run_control(root, "ed.train", seed)
    assert all(v <= lim for v, lim in out["checks"].values())
    assert fails(out["control"]["fp8"], out["checks"])
    assert fails(out["control"]["half_batch"], out["checks"])
