"""A whole run on the CPU at a tiny size: the result line's keys and order."""
import json

import pytest

import run
from conftest import tiny_spec

CONTRACT = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny_spec(tmp_path_factory.mktemp("cfg"))


@pytest.mark.parametrize("trace", [0, 1])
def test_line_has_exactly_the_contract_keys(spec, trace, capsys):
    line, info = run.run_cell(spec, "alexnet.sync1", 2**33 + 3, 0.5,
                              bool(trace), require_chip=False)
    run.print_result(line, info)
    out, err = capsys.readouterr()
    printed = json.loads(out.strip().splitlines()[-1])
    want = CONTRACT + (["breakdown"] if trace else []) + ["checks"]
    assert list(printed) == want                 # `checks` comes last
    assert printed["correct"] is True
    assert set(printed["device"]) >= {"platform", "kind", "count",
                                      "memory_peak_bytes"}
    if trace:
        assert set(printed["device"]) >= {"busy_s", "window_s"}
        assert set(printed["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(printed["metrics"]) == {"p50_ms", "p95_ms", "setup_s"}
    for m in printed["metrics"].values():
        assert set(m) == {"value", "unit"}
    # the checks are the last lines on standard error, each with its limit
    tail = err.strip().splitlines()[-len(printed["checks"]):]
    assert [t.split()[1] for t in tail] == list(printed["checks"])
    assert all(" limit " in t for t in tail)


def test_no_chip_means_no_result(spec):
    with pytest.raises(run.NoChip):
        run.run_cell(spec, "vgg16.offline", 1, 0.5, False)


def test_main_exits_nonzero_without_a_chip(capsys):
    assert run.main(["--workload", "alexnet.sync1", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 1
    assert capsys.readouterr().out == ""


def test_every_metric_has_a_reader():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_each_cell_reports_setup_another_e2e_and_a_layer_metric():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        c = run.cell(spec, w["name"])
        names = {m["name"] for m in c["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert c["per_layer"]
        assert all(m["moves"] in names for m in c["per_layer"])


def test_a_pair_with_no_cell_assembles():
    """The knee sweep builds a configuration and traffic that no cell pairs."""
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    assert "vgg16.poisson" not in {w["name"] for w in spec["workloads"]}
    c = run.assemble(spec, {"name": "vgg16.poisson", "config": "vgg16",
                            "traffic": "poisson", "chips": 1})
    assert c["traffic"]["kind"] == "poisson" and c["config"]["model"] == "vgg16"


def test_mfu_reads_the_device_busy_time():
    """mfu.offline divides by the trace's busy seconds, not the host window."""
    peaks = {"int8_ops_per_s": 1e12}
    rec = {"trace": {"busy_s": 2.0, "window_s": 20.0}, "peaks": peaks,
           "images": 100, "ops_per_image": 1e9, "window_s": 20.0}
    mfu = run.reader("mfu.offline")
    assert mfu(rec) == pytest.approx(5.0)        # 1e11 ops over 2 s at 1e12/s
    assert mfu(dict(rec, trace=None)) is None     # nothing traced, nothing read
