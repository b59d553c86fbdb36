"""ExecutionPlan / design-space explorer tests (ISSUE 8).

The load-bearing contract: plan-driven dispatch is BITWISE equal to the
heuristic auto dispatch it replaces -- for every CNN, under both integer
policies, eager and jitted and through the serving engine -- because on the
cached-weight int serving path every engine the planner may pick is exact
(PR4: implicit == im2col; PR6: winograd == both on eligible layers).  Plus
the artifact lifecycle: round-trip, schema/backend rejection, the
resolution chain, `planner --check`, and the single-call-site grep
contracts (select_conv_path lives ONLY in the planner's fallback scorer;
the dryrun roofline renderer lives ONLY in analysis/roofline.py).
"""
import dataclasses
import json
import pathlib
import re

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.core import planner
from repro.core.planner import (
    PlanArtifactError,
    check,
    explore,
    geometry_key,
    heuristic_path,
    heuristic_plan,
    load_plans,
    parse_geometry_key,
    plan_key,
    resolve_plan,
    save_plans,
)
from repro.core.precision import MatmulPolicy
from repro.core.substrate import path_supports_policy, validate_path_policy
from repro.models.cnn import (
    cnn_conv_geometries,
    cnn_forward,
    cnn_init,
    cnn_quantize_params,
)
from repro.serving.cnn_engine import CNNServeEngine, ImageRequest

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC = REPO / "src"

MODELS = ("alexnet", "vgg16", "vgg19")
INT_POLICIES = (MatmulPolicy.KOM_INT14, MatmulPolicy.SCHOOLBOOK_INT16)


def _small(name, policy):
    return reduced(get_config(name)).replace(policy=policy)


# ---------------------------------------------------------------------------
# Grep contracts: single definitions / single call sites.
# ---------------------------------------------------------------------------

def test_select_conv_path_single_call_site():
    """Path selection has ONE call site in src/: the planner's fallback
    scorer.  Everything else (conv2d auto, tuning.check, the benchmark
    tables) routes through heuristic_path."""
    calls = []
    for p in SRC.rglob("*.py"):
        for i, line in enumerate(p.read_text().splitlines(), 1):
            if re.search(r"(?<!def )select_conv_path\(", line):
                calls.append(f"{p.relative_to(REPO)}:{i}")
    assert calls == ["src/repro/core/planner.py:"
                     + calls[0].rsplit(":", 1)[1]], calls
    assert len(calls) == 1, calls


def test_dryrun_roofline_single_home():
    """benchmarks/roofline.py is retired; the dryrun table renderer is
    defined once, in src/repro/analysis/roofline.py."""
    assert not (REPO / "benchmarks" / "roofline.py").exists()
    defs = []
    for p in list(SRC.rglob("*.py")) + list((REPO / "benchmarks").glob("*.py")):
        for line in p.read_text().splitlines():
            if re.match(r"\s*def dryrun_markdown\(", line):
                defs.append(str(p.relative_to(REPO)))
    assert defs == ["src/repro/analysis/roofline.py"]


# ---------------------------------------------------------------------------
# Shared path x policy capability table.
# ---------------------------------------------------------------------------

def test_validate_path_policy():
    # im2col/auto honor every policy
    for pol in MatmulPolicy:
        validate_path_policy("im2col", pol)
        validate_path_policy("auto", pol)
        assert path_supports_policy("im2col", pol)
    # each engine refuses exactly the policies it cannot run exactly
    for path, bad in (("systolic", MatmulPolicy.BF16X3),
                      ("implicit", MatmulPolicy.NATIVE_BF16),
                      ("winograd", MatmulPolicy.FP32)):
        assert not path_supports_policy(path, bad)
        with pytest.raises(ValueError, match=path):
            validate_path_policy(path, bad)
    for pol in INT_POLICIES:
        for path in ("systolic", "implicit", "winograd"):
            validate_path_policy(path, pol)
    with pytest.raises(ValueError, match="unknown"):
        path_supports_policy("warp", MatmulPolicy.FP32)


def test_serve_launcher_uses_shared_guard():
    """--conv-path winograd --policy fp32 fails at arg-parse time through
    the ONE validate_path_policy refusal (no triplicated guard blocks)."""
    from repro.launch.serve import main
    with pytest.raises(SystemExit):
        main(["--arch", "alexnet", "--conv-path", "winograd",
              "--policy", "fp32"])
    # an explicit engine AND a plan are mutually exclusive
    with pytest.raises(SystemExit):
        main(["--arch", "alexnet", "--conv-path", "im2col",
              "--policy", "kom_int14", "--explore"])
    src = (SRC / "repro" / "launch" / "serve.py").read_text()
    assert src.count("validate_path_policy") >= 1
    assert "systolic_exact" not in src and "implicit_supported" not in src


# ---------------------------------------------------------------------------
# Geometry keys and the heuristic fallback.
# ---------------------------------------------------------------------------

def test_geometry_key_round_trip():
    g = dict(kh=11, kw=11, stride=4, h=227, cin=3, cout=96, padding="VALID")
    assert parse_geometry_key(geometry_key(**g)) == g
    with pytest.raises(ValueError):
        parse_geometry_key("not-a-key")


def test_heuristic_plan_reproduces_selector():
    """The fallback plan is per-call dispatch made explicit: entry paths ==
    select_conv_path choices, blocks left to the tuner (None), source tag
    'default' on every layer (no silent gap)."""
    for name in MODELS:
        for pol in INT_POLICIES:
            cfg = _small(name, pol)
            plan = heuristic_plan(cfg)
            geoms = {geometry_key(**g): g for g in cnn_conv_geometries(cfg)}
            assert set(plan.by_key) == set(geoms)
            for key, g in geoms.items():
                ent = plan.by_key[key]
                want = heuristic_path(
                    policy=pol, cached_weight=True,
                    **{k: v for k, v in g.items() if k != "h"})
                assert (ent.path, ent.block, ent.source) == \
                    (want, None, "default")


# ---------------------------------------------------------------------------
# The tentpole contract: plan-driven dispatch == heuristic auto, bitwise.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", MODELS)
@pytest.mark.parametrize("pol", INT_POLICIES, ids=lambda p: p.value)
def test_plan_bitwise_equals_auto(name, pol):
    """Eager, jitted, and engine-served logits under an EXPLORED plan (the
    design-space explorer's own joint choice, which may differ from the
    heuristic layer by layer) are bit-identical to heuristic auto."""
    cfg = _small(name, pol)
    plan = explore(cfg, model_only=True)
    assert all(e.source == "model" for e in plan.entries)
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    qp = cnn_quantize_params(params, cfg)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(
        (2, cfg.img_size, cfg.img_size, cfg.in_channels)), jnp.float32)
    # eager
    auto = cnn_forward(qp, cfg, x)
    planned = cnn_forward(qp, cfg, x, plan=plan)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(planned))
    # jitted (plan is a static pytree: threads through jit unchanged)
    jauto = jax.jit(lambda p, a: cnn_forward(p, cfg, a))(qp, x)
    jplan = jax.jit(lambda p, a: cnn_forward(p, cfg, a, plan=plan))(qp, x)
    np.testing.assert_array_equal(np.asarray(jauto), np.asarray(jplan))
    # through the serving engine (plan resolved ONCE at build)
    imgs = [np.asarray(x[i]) for i in range(2)]
    outs = {}
    for tag, kw in (("auto", {}), ("plan", {"plan": plan})):
        eng = CNNServeEngine(cfg, params, buckets=(2,), **kw)
        for uid, img in enumerate(imgs):
            eng.submit(ImageRequest(uid=uid, image=img))
        outs[tag] = eng.run()
    for uid in range(2):
        np.testing.assert_array_equal(outs["auto"][uid].logits,
                                      outs["plan"][uid].logits)


def test_engine_rejects_plan_with_explicit_path():
    cfg = _small("alexnet", MatmulPolicy.KOM_INT14)
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    plan = heuristic_plan(cfg)
    with pytest.raises(ValueError, match="mutually exclusive"):
        CNNServeEngine(cfg.replace(conv_path="im2col"), params,
                       buckets=(2,), plan=plan)


# ---------------------------------------------------------------------------
# Artifact lifecycle: round-trip, rejection, resolution chain.
# ---------------------------------------------------------------------------

def test_plan_round_trip_and_resolution(tmp_path, monkeypatch):
    from repro.core import tuning
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    cfg = _small("alexnet", MatmulPolicy.KOM_INT14)
    plan = explore(cfg, model_only=True)
    out = save_plans([plan])
    assert out == tmp_path / "plans" / f"{plan.backend}.json"
    # save -> load -> identical resolution (same entries, same plan)
    loaded = load_plans(out, backend=plan.backend)
    assert loaded[plan_key(cfg.name, cfg.policy)] == plan
    assert resolve_plan(cfg, backend=plan.backend) == plan
    # explicit plan wins; a plan for another (model, policy) is refused
    assert resolve_plan(cfg, plan) is plan
    other = _small("vgg16", MatmulPolicy.KOM_INT14)
    with pytest.raises(ValueError, match="vgg16"):
        resolve_plan(other, plan)
    # merging a second plan keeps the first
    plan2 = explore(other, model_only=True)
    save_plans([plan2])
    both = load_plans(out, backend=plan.backend)
    assert set(both) == {plan_key(cfg.name, cfg.policy),
                         plan_key(other.name, other.policy)}


def test_plan_schema_and_backend_rejection(tmp_path, monkeypatch):
    from repro.core import tuning
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    cfg = _small("alexnet", MatmulPolicy.KOM_INT14)
    plan = heuristic_plan(cfg)
    out = save_plans([plan])
    # backend mismatch: a plan tuned elsewhere must not drive dispatch here
    with pytest.raises(PlanArtifactError, match="backend"):
        load_plans(out, backend="tpu")
    with pytest.raises(PlanArtifactError, match="backend"):
        resolve_plan(cfg, plan, backend="tpu")
    # schema version mismatch: refuse, do not guess
    data = json.loads(out.read_text())
    data["schema"] = "execution-plan/v0"
    out.write_text(json.dumps(data))
    planner._load_plan_file.cache_clear()
    with pytest.raises(PlanArtifactError, match="schema"):
        load_plans(out, backend=plan.backend)
    # ...and the resolution chain falls back to the heuristic, not a crash
    assert resolve_plan(cfg, backend=plan.backend) == heuristic_plan(
        cfg, backend=plan.backend)
    # one artifact file holds ONE backend's plans
    with pytest.raises(ValueError, match="ONE backend"):
        save_plans([plan, dataclasses.replace(plan, backend="tpu")])


def test_resolve_plan_heuristic_tail(tmp_path, monkeypatch):
    """No artifact anywhere -> the chain bottoms out on the heuristic plan
    (source='default', block=None everywhere): pre-planner behavior."""
    from repro.core import tuning
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    cfg = _small("vgg16", MatmulPolicy.SCHOOLBOOK_INT16)
    plan = resolve_plan(cfg)
    assert plan == heuristic_plan(cfg)
    assert all(e.source == "default" and e.block is None
               for e in plan.entries)


# ---------------------------------------------------------------------------
# planner --check: committed artifacts validate in CI.
# ---------------------------------------------------------------------------

def test_committed_artifacts_pass_check():
    """The version-controlled benchmarks/tuned/plans/*.json are valid: CI
    runs the same entry point."""
    plans_dir = REPO / "benchmarks" / "tuned" / "plans"
    files = sorted(plans_dir.glob("*.json"))
    assert files, "a committed plan artifact per backend is required"
    assert check(files) == []
    for f in files:
        data = json.loads(f.read_text())
        assert data["schema"] == planner.PLAN_SCHEMA
        assert data["backend"] == f.stem
        for plan in data["plans"].values():
            for e in plan["layers"]:
                assert e["source"] in planner.SOURCES


def test_check_flags_violations(tmp_path):
    full = get_config("alexnet").replace(policy=MatmulPolicy.KOM_INT14)
    plan = heuristic_plan(full, backend="cpu")
    p = tmp_path / "cpu.json"

    def write(tampered):
        p.write_text(json.dumps({"schema": planner.PLAN_SCHEMA,
                                 "backend": "cpu",
                                 "plans": {"alexnet|kom_int14":
                                           tampered.to_json()}}))
        planner._load_plan_file.cache_clear()
        return check([p])

    # the untampered plan is clean
    assert write(plan) == []
    # coverage gap: a dropped layer is an ERROR, not a silent fallback
    gappy = dataclasses.replace(plan, entries=plan.entries[1:])
    assert any("NO entry" in e for e in write(gappy))
    # unknown source tag
    bad_src = dataclasses.replace(plan, entries=(
        dataclasses.replace(plan.entries[0], source="vibes"),
        *plan.entries[1:]))
    assert any("bad source" in e for e in write(bad_src))
    # an entry that matches no conv layer of the model
    extra = dataclasses.replace(plan, entries=plan.entries + (
        dataclasses.replace(plan.entries[0],
                            key=geometry_key(kh=9, kw=9, stride=1, h=5,
                                             cin=8, cout=8,
                                             padding="SAME")),))
    assert any("matches no conv layer" in e for e in write(extra))
    # backend stamp must match the filename
    q = tmp_path / "tpu.json"
    q.write_text(p.read_text())
    planner._load_plan_file.cache_clear()
    assert any("backend" in e for e in check([q]))


# ---------------------------------------------------------------------------
# Explorer output shape: sources, bounds, roofline annotation.
# ---------------------------------------------------------------------------

def test_explore_model_only_fields():
    cfg = _small("vgg16", MatmulPolicy.KOM_INT14)
    plan = explore(cfg, model_only=True)
    geoms = {geometry_key(**g) for g in cnn_conv_geometries(cfg)}
    assert set(plan.by_key) == geoms  # every layer covered, no silent gap
    for e in plan.entries:
        assert e.source == "model"
        assert e.est_us is not None and e.est_us > 0
        assert e.hbm_bytes and e.hbm_bytes > 0
        assert path_supports_policy(e.path, cfg.policy)
        if e.path in planner.TUNABLE_KINDS:
            assert e.block is not None
        if e.exactness_bound is not None:
            assert e.exactness_bound < 2**31


def test_annotate_plan_roofline():
    from repro.analysis.roofline import annotate_plan
    cfg = _small("alexnet", MatmulPolicy.KOM_INT14)
    plan = heuristic_plan(cfg)
    # pretend one entry was measured so the fraction engages
    entries = tuple(dataclasses.replace(e, est_us=100.0, source="measured")
                    for e in plan.entries)
    out = annotate_plan(dataclasses.replace(plan, entries=entries))
    for e in out.entries:
        assert e.roofline_us is not None and e.roofline_us > 0
        # stored roofline_us is rounded to ns; compare loosely
        assert e.roofline_frac == pytest.approx(e.roofline_us / 100.0,
                                                rel=0.05, abs=1e-5)
    # model-scored entries get the floor but no achievement fraction
    out2 = annotate_plan(plan)
    assert all(e.roofline_frac is None for e in out2.entries)


def _refused(*_a, **_k):
    raise RuntimeError("Mosaic failed to compile TPU kernel\ndetails")


def test_pruned_candidate_is_reported_without_verbose(monkeypatch, capsys):
    """A candidate engine that raises is pruned AND said, type and first
    line, even with verbose off: a kernel the chip's compiler refuses must
    not vanish silently from an explore run."""
    import repro.core.substrate as substrate
    monkeypatch.setattr(substrate, "conv2d", _refused)
    out = planner._measure_paths(
        ["implicit"], kh=3, kw=3, stride=1, h=8, cin=16, cout=8,
        padding="SAME", policy=MatmulPolicy.KOM_INT14, iters=1,
        verbose=False)
    assert out == {}
    assert ("implicit: pruned (RuntimeError: Mosaic failed to compile TPU "
            "kernel)") in capsys.readouterr().out
