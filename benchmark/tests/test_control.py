"""``correct`` has to be able to fail.  At a toy size on the CPU:

* the control comes out not correct, by ``feature_gap``, under the
  limit the configuration states.  It is one precision below what the
  configuration states: for ``inceptionv3`` (bfloat16) the reference put
  in the program's place with every convolution's operands held in
  int8; for a configuration stated at float32 (the toy's
  ``inceptionv3.f32``, which a later PR can enter as data alone) the
  program's own ``SPARKDL_ZOO_COMPUTE_DTYPE=bfloat16`` path;
* the rest of a run, driven behind the look for a chip with the timed
  path broken underneath, comes out not correct: an answer altered where
  it is produced (one row's features off by 2%), rows handed back in
  another order, and the last rows of a padded dispatch lost.

(A step that leaves its state unchanged, half a batch left out of a
mean, and a skipped exchange between chips are faults of training and
of multi-chip cells; these cells can have neither.)  The readings on
the chip at the cell's own size are in PERF.md section 2.
"""

import os

import numpy as np
import pytest

from benchmark.tests import toy

@pytest.fixture(autouse=True)
def _restore_environment():
    saved = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(saved)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return toy.make_root(tmp_path_factory.mktemp("control"))


@pytest.mark.parametrize("cell", ["inceptionv3.jpeg", "inceptionv3.f32.structs"])
def test_the_program_is_correct_and_its_control_is_not(toy_root, cell):
    sound = toy.run(*toy_root, cell)
    assert sound["correct"] is True, sound["checks"]
    # set-up's small warm job warmed every shape the window used
    assert sound["compiles_in_window"] == 0
    gap = sound["checks"]["feature_gap"]
    control = toy.run(*toy_root, cell, control=True)
    assert control["correct"] is False
    failed = control["checks"]["feature_gap"]
    assert failed["value"] > failed["limit"] > gap["value"]
    assert control["checks"]["rows_off"]["value"] == 0


def _alter_one_answer(out, n):
    out = np.array(out[:n])
    out[n // 2] *= 1.02
    return out


def _swap_two_rows(out, n):
    out = np.array(out[:n])
    out[[0, n - 1]] = out[[n - 1, 0]]
    return out


def _lose_the_last_row(out, n):
    return np.array(out[:n])[:max(n - 1, 0)]


@pytest.mark.parametrize("fault,number", [
    (_alter_one_answer, "feature_gap"),
    (_swap_two_rows, "feature_gap"),
    (_lose_the_last_row, "rows_off"),
])
def test_a_broken_timed_path_comes_out_not_correct(
        toy_root, monkeypatch, fault, number):
    from sparkdl_tpu.parallel.engine import InferenceEngine

    # where every dispatch's rows come back to the host
    monkeypatch.setattr(InferenceEngine, "_trim",
                        lambda self, out, n: fault(np.asarray(out), n))
    try:
        result = toy.run(*toy_root, "inceptionv3.f32.structs")
    except Exception:
        # a program that notices its own broken rows and raises has not
        # reported a correct run either
        return
    assert result["correct"] is False
    check = result["checks"][number]
    assert check["value"] > check["limit"]
