"""trace_phases and phases.py: the program's spans against the device clock."""
import importlib.util
from types import SimpleNamespace as NS

import pytest

import trace_phases
from conftest import BENCH


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


CONV = "%_conv2d_winograd_core.5 = f32[8] custom-call(f32[8] %p)"
FC = "%fusion.2 = f32[8] fusion(f32[8] %a)"
COPY = "%copy.1 = f32[8] copy(f32[8] %x)"


def profile():
    """One forward, device 100..700; the host clock runs 5000 ns ahead."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_fwd", 100, 600)]),
        NS(name="XLA Ops", events=[ev(COPY, 100, 50), ev(CONV, 150, 250),
                                   ev(FC, 600, 100)]),
    ])
    return NS(planes=[NS(name="/host:CPU", lines=[]), device])


#: (name, t0, t1, parent, error) rows as the program records them: a step
#: 4900..5800 on the host clock; its forward 5080..5705 holds device 100..700.
SPANS = [
    ("window", 4900, 6000, None, False),              # 0
    ("engine.step", 4900, 5800, 0, False),            # 1
    ("batch.admit", 4900, 4950, 1, False),            # 2
    ("batch.stack", 4950, 5050, 1, False),            # 3
    ("engine.to_device", 5050, 5080, 1, False),       # 4
    ("engine.forward", 5080, 5705, 1, False),         # 5
    ("engine.from_device", 5705, 5720, 1, False),     # 6
    ("batch.finish", 5720, 5800, 1, False),           # 7
    ("arrival_wait", 5800, 6000, 0, False),           # 8
]
SCOPES = {CONV: "jit(fwd)/l02.conv1_2/jit(_conv2d_winograd_core)/pallas_call:",
          FC: "jit(fwd)/l15.fc6/dot_general:",
          COPY: "jit(fwd)/copy:"}


def test_forward_offset_is_the_tight_end_bound():
    # ends: 5705 - 700 = 5005; starts: 5080 - 100 = 4980 -> slack 25
    assert trace_phases.forward_offset([(100, 700)], [(5080, 5705)]) == (5005, 25)
    # counts that differ: the median pair's end offset, no slack
    assert trace_phases.forward_offset([(100, 700), (800, 900)],
                                       [(5080, 5705)]) == (5005, None)


def test_reduce_splits_gaps_by_phase_and_names_ops_by_layer():
    r = trace_phases.reduce(profile(), SPANS, SCOPES)
    assert r["clock_slack_ms"] == pytest.approx(25e-6)
    assert (r["modules"], r["forwards"]) == (1, 1)
    assert r["forward_ms"] == {"wait": pytest.approx(25e-6),
                               "module": pytest.approx(600e-6),
                               "return": pytest.approx(0.0)}
    # on the device clock (host - 5005): window -105..995, busy 100..400
    # and 600..700; the step's phases run -105..795, arrival_wait after
    assert r["window_s"] == pytest.approx(1100e-9)
    assert r["busy_s"] == pytest.approx(400e-9)
    idle = dict(r["idle_by_label"])
    assert idle == {"batch.admit": pytest.approx(50e-9),
                    "batch.stack": pytest.approx(100e-9),
                    "engine.to_device": pytest.approx(30e-9),
                    "engine.forward": pytest.approx(225e-9),
                    "engine.from_device": pytest.approx(15e-9),
                    "batch.finish": pytest.approx(80e-9),
                    "arrival_wait": pytest.approx(200e-9)}
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # each gap named after the span holding most of it
    assert r["idle_gaps"] == [["arrival_wait", pytest.approx(295e-9)],
                              ["batch.stack", pytest.approx(205e-9)],
                              ["engine.forward", pytest.approx(200e-9)]]
    ops = dict(r["device_ops"])
    assert ops == {"l02.conv1_2/_conv2d_winograd_core.5": pytest.approx(250e-9),
                   "l15.fc6/fusion.2": pytest.approx(100e-9),
                   "unscoped/copy.1": pytest.approx(50e-9)}
    assert [n for n, _ in r["device_layers"]] == ["l02.conv1_2", "l15.fc6",
                                                  "unscoped"]


def test_timeline_takes_the_innermost_span():
    segs = trace_phases.timeline([("engine.step", 0, 100), ("batch.stack", 10, 30),
                                  ("python.gc", 20, 25), ("window", -5, 200)])
    assert segs == [[0, 10, "engine.step"], [10, 20, "batch.stack"],
                    [20, 25, "python.gc"], [25, 30, "batch.stack"],
                    [30, 100, "engine.step"]]
    starts = [s[0] for s in segs]
    assert dict(trace_phases.split(-10, 15, segs, starts)) == {
        "none": 10, "engine.step": 10, "batch.stack": 5}


def test_reduce_without_scopes_names_every_op_unscoped():
    r = trace_phases.reduce(profile(), SPANS)
    assert all(n.startswith("unscoped/") for n, _ in r["device_ops"])


def test_layer_of():
    assert trace_phases.layer_of(SCOPES[CONV]) == "l02.conv1_2"
    assert trace_phases.layer_of("jit(fwd)/l07.conv5_3/jit(f)/mul:") == "l07.conv5_3"
    assert trace_phases.layer_of("jit(fwd)/reduce_max:") == "unscoped"
    assert trace_phases.layer_of(None) == "unscoped"


def test_phase_means_and_host_time():
    ph = trace_phases.phase_ms_per_step(SPANS)
    assert ph["engine.step"] == pytest.approx(900e-6)
    assert ph["engine.forward"] == pytest.approx(625e-6)
    assert ph["coverage"] == pytest.approx(1.0)
    assert trace_phases.host_ms_per_step(SPANS) == pytest.approx(275e-6)


def test_a_step_whose_forward_failed_did_not_serve():
    failed = [r if r[0] != "engine.forward" else (*r[:4], True) for r in SPANS]
    assert trace_phases.served_steps(failed) == []
    assert trace_phases.host_ms_per_step(failed) is None


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", ["host_ms_per_step.offline",
                                  "host_ms_per_step.sync1"])
def test_host_ms_per_step_readers(name):
    read = _reader(name)
    # two served steps: 900 - 625 ns of host time, then 1000 - 600 ns
    two = SPANS + [("engine.step", 6000, 7000, 0, False),
                   ("engine.forward", 6100, 6700, 9, False)]
    assert read({"program_spans": two}) == pytest.approx((275e-6 + 400e-6) / 2)
    # a program without the hook: nothing to read
    assert read({"trace": None}) is None
    assert read({"program_spans": []}) is None


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    from conftest import tiny_spec

    return tiny_spec(tmp_path_factory.mktemp("cfg"))


@pytest.mark.parametrize("name", ["alexnet.sync1", "alexnet.offline"])
def test_phases_tool_on_the_cpu(tiny, name):
    import phases

    out = phases.run_phases(tiny, name, 2**33 + 11, 0.3, False,
                            require_chip=False)
    host = f"host_ms_per_step.{name.split('.')[1]}"
    assert out["metrics"][host] > 0
    ph = out["phases_ms_per_step"]
    assert set(trace_phases.PHASES) <= set(ph)
    assert ph["coverage"] >= 0.95
    assert {"engine.quantize_weights", "engine.plan", "engine.jit",
            "engine.warmup"} <= set(out["setup_phases_s"])
    assert out["window"]["jax.compile"] == 0      # every bucket warmed up


# -- traces taken on one v5e with the program's spans and layer scopes ------------

#: Harness spans, the step's phases and the program's events: every name a
#: gap may carry.
LABELS = set(trace_phases.HOST_SPANS) | {"none"}


@pytest.fixture(scope="module", params=["alexnet_sync1", "alexnet_offline"])
def chip(request, tmp_path_factory):
    """A few steps of the cell traced by ``bench/phases.py --save``."""
    import gzip
    import json
    import shutil

    import trace_reduce

    data = BENCH / "tests" / "data"
    path = tmp_path_factory.mktemp("trace") / f"{request.param}.xplane.pb"
    with gzip.open(data / f"{request.param}.phases.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    rec = json.loads((data / f"{request.param}.phases.spans.json").read_text())
    scopes = trace_phases.op_scopes(str(path))
    return NS(name=request.param, profile=trace_reduce.load(str(path)),
              spans=rec["spans"], forwards=rec["forwards"], scopes=scopes,
              reduced=trace_phases.reduce(trace_reduce.load(str(path)),
                                          rec["spans"], scopes))


def test_chip_gaps_carry_phase_labels(chip):
    r = chip.reduced
    labels = {n for n, _ in r["idle_gaps"]} | {n for n, _ in r["idle_by_label"]}
    assert labels <= LABELS
    assert labels & set(trace_phases.PHASES)
    # no gap over 1 ms is put down to the step's own time between phases
    assert not [t for n, t in r["idle_gaps"] if n == "engine.step" and t > 1e-3]
    idle = sum(t for _, t in r["idle_by_label"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_chip_every_conv_kernel_maps_to_a_conv_layer(chip):
    import re

    import trace_reduce

    pallas = [op for op in chip.scopes if trace_reduce.is_pallas(op)]
    assert pallas
    for op in pallas:
        # the kernels' op names come from their jitted wrappers
        assert re.fullmatch(r"_conv2d_(systolic|implicit|winograd)_core\.\d+",
                            trace_reduce.op_name(op)), op
        assert re.fullmatch(r"l\d\d\.conv\d", trace_phases.layer_of(chip.scopes[op]))
    named = [n for n, _ in chip.reduced["device_ops"]]
    assert all(re.match(r"(l\d\d\.\w+|unscoped)/", n) for n in named)
    layers = dict(chip.reduced["device_layers"])
    assert {"l00.conv1", "l08.fc6"} <= set(layers)
    assert layers.get("unscoped", 0) < 0.1 * sum(layers.values())


#: Each phase PERF.md section 5 puts device idle time down to through the
#: aligned clock, with the idle ms per step the fixture reads at least (the
#: forward's wait for its input and dispatch; the batch's stacking).
ATTRIBUTED = {"alexnet_sync1": {"engine.forward": 1.0},
              "alexnet_offline": {"batch.stack": 30.0}}


def test_chip_clock_slack_is_below_the_phases_given_time(chip):
    r = chip.reduced
    steps = len(trace_phases.served_steps(chip.spans))
    assert r["modules"] == r["forwards"] == steps
    idle = dict(r["idle_by_label"])
    for phase, floor_ms in ATTRIBUTED[chip.name].items():
        assert 1e3 * idle[phase] / steps >= floor_ms
        assert r["clock_slack_ms"] < floor_ms
    # the one-sided bound: each module ends before its forward returns; the
    # forward less its module needs no alignment at all
    f = r["forward_ms"]
    assert f["return"] >= 0 and f["module"] > 0
    ph = trace_phases.phase_ms_per_step(chip.spans)
    assert f["wait"] + f["return"] == pytest.approx(
        ph["engine.forward"] - f["module"], rel=1e-6)


def test_chip_phases_cover_the_step(chip):
    ph = trace_phases.phase_ms_per_step(chip.spans)
    assert set(trace_phases.PHASES) <= set(ph)
    assert ph["coverage"] >= 0.95
    assert trace_phases.host_ms_per_step(chip.spans) \
        == pytest.approx(ph["engine.step"] - ph["engine.forward"])


def test_chip_trace_reduce_reads_the_new_trace_as_before(chip):
    """The harness's reduction, fed the harness's spans, is unchanged by the
    program's: the same busy time as the phase reduction."""
    import trace_reduce

    harness = [r[:3] for r in chip.spans
               if r[0] in trace_reduce.HOST_SPANS + (trace_reduce.WINDOW_SPAN,)]
    old = trace_reduce.reduce(chip.profile, harness, chip.forwards)
    assert old["busy_s"] == pytest.approx(chip.reduced["busy_s"], rel=1e-6)
    assert old["window_s"] == pytest.approx(chip.reduced["window_s"], rel=1e-6)
