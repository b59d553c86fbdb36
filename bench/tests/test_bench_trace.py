"""trace_reduce: busy union, Pallas/XLA split and labelled idle gaps."""
from types import SimpleNamespace as NS

import pytest

import trace_reduce


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile():
    """One forward on the device clock; the host clock runs 5000 ns ahead."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_fwd", 100, 600)]),
        NS(name="XLA Ops", events=[
            ev("%custom-call.1 = f32[8] custom-call(f32[8] %p)", 100, 200),
            ev("%fusion.2 = f32[8] fusion(f32[8] %a)", 250, 150),  # overlaps
            ev("%fusion.3 = f32[8] fusion(f32[8] %b)", 600, 100),
            ev("%fusion.4 = f32[8] fusion(f32[8] %c)", 1100, 50),  # after it
        ]),
    ])
    host = NS(name="/host:CPU", lines=[])
    return NS(planes=[host, device])


SPANS = [("engine.step", 5050, 5750), ("arrival_wait", 5750, 6000),
         ("window", 5000, 6000)]
FORWARDS = [(5050, 5750)]


def test_clock_offset_puts_each_module_inside_its_step():
    # host 5050..5750 holds device 100..700 for offsets 4950..5050
    assert trace_reduce.clock_offset([(100, 700)], FORWARDS) == 5000
    # counts that differ: the median pair's start offset
    assert trace_reduce.clock_offset([(100, 700), (900, 950)],
                                     [(5060, 5750)]) == 4960


def test_synthetic_trace():
    r = trace_reduce.reduce(profile(), SPANS, FORWARDS)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)       # [100,400] + [600,700]
    assert r["pallas_s"] == pytest.approx(200e-9)
    assert r["xla_s"] == pytest.approx(250e-9)
    assert r["device_ops"][0] == ["custom-call.1", pytest.approx(200e-9)]
    # on the device clock the step spans 50..750: gaps 0..100 (mid 50) and
    # 400..600 fall in it, 700..1000 (mid 850) in arrival_wait
    assert r["idle_gaps"] == [["arrival_wait", pytest.approx(300e-9)],
                              ["engine.step", pytest.approx(200e-9)],
                              ["engine.step", pytest.approx(100e-9)]]


def test_op_name():
    assert trace_reduce.op_name("%fusion.3 = f32[2]{0} fusion(%a)") == "fusion.3"
    assert trace_reduce.op_name("copy.1") == "copy.1"


def test_pallas_is_a_custom_call():
    assert trace_reduce.is_pallas("%_conv2d_winograd_core.5 = f32[8] "
                                  "custom-call(f32[8] %p)")
    assert not trace_reduce.is_pallas("%fusion.9 = f32[8] fusion(f32[8] %a)")


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce(profile(), SPANS[:2], FORWARDS)


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """12 steps of alexnet.sync1 traced on one v5e, with the harness's spans."""
    import gzip
    import json
    import shutil

    from conftest import BENCH

    data = BENCH / "tests" / "data"
    path = tmp_path_factory.mktemp("trace") / "alexnet_sync1.xplane.pb"
    with gzip.open(data / "alexnet_sync1.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    rec = json.loads((data / "alexnet_sync1.spans.json").read_text())
    return trace_reduce.load(str(path)), rec["spans"], rec["forwards"]


def test_chip_trace(chip_trace):
    profile, spans, forwards = chip_trace
    r = trace_reduce.reduce(profile, spans, forwards)
    # the numbers this reduction gave on the chip when the trace was taken
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.050075707, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.019107917, abs=1e-9)
    assert r["pallas_s"] == pytest.approx(0.001540191, abs=1e-9)
    assert r["xla_s"] == pytest.approx(0.017567726, abs=1e-9)
    assert r["device_ops"][0] == ["abs_reduce_fusion",
                                  pytest.approx(0.011494436, abs=1e-9)]
    assert r["idle_gaps"][0] == ["engine.step",
                                 pytest.approx(0.0063436785, abs=1e-9)]
    # and what must hold whatever the numbers
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["pallas_s"] + r["xla_s"] >= r["busy_s"] * 0.999
    pallas = [n for n, _ in r["device_ops"] if "conv2d" in n]
    assert pallas and all(n.startswith("_conv2d_winograd_core") for n in pallas)
    assert sum(t for _, t in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
    assert {n for n, _ in r["idle_gaps"]} <= {"engine.step", "submit", "none"}


def test_chip_trace_modules_pair_with_forwards(chip_trace):
    profile, spans, forwards = chip_trace
    dev = [p for p in profile.planes if p.name.startswith("/device:TPU:")][0]
    modules = sorted((e.start_ns, e.start_ns + e.duration_ns) for line in dev.lines
                     if line.name == "XLA Modules" for e in line.events)
    assert len(modules) == len(forwards) == 12
    off = trace_reduce.clock_offset(modules, sorted(forwards))
    for (h0, h1), (d0, d1) in zip(sorted(forwards), modules):
        assert h0 <= d0 + off and d1 + off <= h1   # each forward inside its step
