"""The correctness check fails the lower-precision control and broken paths.

At a size a test run holds (the tiny twins of ``conftest.tiny_spec``, on
the CPU), against each configuration's own limit: the program's
``native_bf16`` path in place of ``kom_int14`` must read above the limit,
and so must a served path with half of each batch left out or one answer
altered where the engine produces it.
"""
import numpy as np
import pytest

import run
from conftest import tiny_spec

SEED = 2**33 + 17


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny_spec(tmp_path_factory.mktemp("cfg"))


def check(spec, workload, **kw):
    line, _ = run.run_cell(spec, workload, SEED, 0.5, False,
                           require_chip=False, **kw)
    return line


@pytest.mark.parametrize("workload", ["vgg16.offline", "alexnet.sync1"])
def test_program_passes(spec, workload):
    line = check(spec, workload)
    assert line["correct"] is True, line["checks"]


@pytest.mark.parametrize("workload", ["vgg16.offline", "alexnet.sync1"])
def test_lower_precision_control_fails(spec, workload):
    config = run.cell(spec, workload)["config"]
    line = check(spec, workload, policy=config["control_policy"])
    assert line["correct"] is False, line["checks"]
    chk = line["checks"]["max_rel_err"]
    assert chk["value"] > chk["limit"]


def half_batch(run_batch):
    def broken(self, batch):
        batch = np.array(batch)
        batch[len(batch) // 2:] = 0      # the second half never reaches it
        return run_batch(self, batch)
    return broken


def altered_answer(run_batch):
    def broken(self, batch):
        out = np.array(run_batch(self, batch))
        out[0, 0] += 0.05 * np.abs(out[0]).max()
        return out
    return broken


@pytest.mark.parametrize("workload,fault", [
    ("vgg16.offline", half_batch),
    ("vgg16.offline", altered_answer),
    ("alexnet.offline", half_batch),
    ("alexnet.sync1", altered_answer),
    ("alexnet.offline", altered_answer),
])
def test_broken_timed_path_is_not_correct(spec, workload, fault, monkeypatch):
    from repro.serving.cnn_engine import CNNServeEngine

    monkeypatch.setattr(CNNServeEngine, "_run_batch",
                        fault(CNNServeEngine._run_batch))
    line = check(spec, workload)
    assert line["correct"] is False, line["checks"]
