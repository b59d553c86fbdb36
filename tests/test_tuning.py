"""The VMEM-aware conv tile autotuner (core/tuning.py).

Feasibility model sanity, default-block feasibility for every conv layer of
the paper's CNNs (the CI --check lane), persistent JSON cache round-trip
(the `pytest -m "not slow"` guard from ISSUE 4), and cache-driven
resolution with re-validation.
"""
import numpy as np

from repro.core import tuning
from repro.core.precision import MatmulPolicy
from repro.core.tuning import (
    TuneCache,
    VMEM_BUDGET,
    candidate_blocks,
    check,
    conv_layer_shapes,
    default_block,
    feasible,
    implicit_vmem_bytes,
    layer_key,
    resolve_block,
    systolic_vmem_bytes,
)
from repro.models.cnn import CNNConfig

VGG_DEEP = dict(kh=3, kw=3, stride=1, h=28, cin=512, cout=512)


def test_vmem_model_monotone_and_sane():
    small = implicit_vmem_bytes(kh=3, kw=3, stride=1, w_img=28, cin=512,
                                cout=512, bm=8, bc=128, bk=128,
                                variant="karatsuba")
    big = implicit_vmem_bytes(kh=3, kw=3, stride=1, w_img=28, cin=512,
                              cout=512, bm=8, bc=128, bk=512,
                              variant="karatsuba")
    assert 0 < small < big
    assert small < VMEM_BUDGET  # the default schedule must be servable
    # systolic model: whole-Cin taps, so deep layers cost more than shallow
    deep = systolic_vmem_bytes(kh=3, kw=3, stride=1, w_img=28, cin=512,
                               block_h=8, block_c=128, variant="karatsuba")
    thin = systolic_vmem_bytes(kh=3, kw=3, stride=1, w_img=28, cin=64,
                               block_h=8, block_c=128, variant="karatsuba")
    assert thin < deep


def test_feasibility_rules():
    ok, _ = feasible("implicit", **VGG_DEEP, variant="karatsuba",
                     base_bits=7, block=(8, 128, 512))
    del _
    assert ok
    # halo rule: bm*stride < kh-stride is rejected
    ok, why = feasible("implicit", kh=11, kw=11, stride=1, h=35, cin=3,
                       cout=8, variant="karatsuba", base_bits=7,
                       block=(8, 128, 3))
    assert not ok and "halo" in why
    # wrap-free rule: a K chunk too wide for one exact int32 step is rejected
    ok, why = feasible("implicit", kh=3, kw=3, stride=1, h=8, cin=2**15,
                       cout=8, variant="karatsuba", base_bits=7,
                       block=(8, 128, 2**15))
    assert not ok and "wrap" in why
    # a VMEM-absurd tile is rejected
    ok, why = feasible("implicit", kh=3, kw=3, stride=1, h=224, cin=4096,
                       cout=4096, variant="karatsuba", base_bits=7,
                       block=(32, 4096, 4096))
    assert not ok and "vmem" in why


def test_default_blocks_feasible_for_all_cnn_layers():
    """The heuristic schedule fits VMEM for every conv layer of the paper's
    three CNNs under every policy the engines run -- `check()` (the CI
    --check lane) returns no violations."""
    errors = check()
    assert errors == [], errors


def test_conv_layer_shapes_walk():
    from repro.configs import get_config
    shapes = conv_layer_shapes(get_config("vgg16"))
    assert {(s["cin"], s["cout"]) for s in shapes} >= {
        (3, 64), (64, 128), (256, 512), (512, 512)}
    assert all(s["kh"] == 3 for s in shapes)
    # AlexNet keeps its 11x11/s4 first layer in the work list
    ashapes = conv_layer_shapes(get_config("alexnet"))
    assert ashapes[0]["kh"] == 11 and ashapes[0]["stride"] == 4


def test_candidates_all_feasible():
    cands = candidate_blocks("implicit", **VGG_DEEP, variant="karatsuba",
                             base_bits=7)
    assert cands
    for block in cands:
        ok, why = feasible("implicit", kh=3, kw=3, stride=1, h=28,
                           cin=512, cout=512, variant="karatsuba",
                           base_bits=7, block=block)
        assert ok, (block, why)


def test_layer_key_stable_and_backend_scoped():
    k1 = layer_key("implicit", **VGG_DEEP, variant="karatsuba", base_bits=7,
                   backend="cpu")
    assert k1 == "implicit|karatsuba|b7|k3x3|s1|h28|cin512|cout512|cpu"
    k2 = layer_key("implicit", **VGG_DEEP, variant="karatsuba", base_bits=7,
                   backend="tpu")
    assert k1 != k2  # CPU-measured entries never leak onto TPU


def test_cache_round_trip(tmp_path):
    """The tuned-cache JSON round-trips (the not-slow CI guard)."""
    path = tmp_path / "default.json"
    cache = TuneCache(path)
    key = layer_key("implicit", **VGG_DEEP, variant="karatsuba", base_bits=7,
                    backend="cpu")
    cache.put(key, (8, 128, 256), us=123.4)
    cache.save()
    loaded = TuneCache.load(path)
    assert loaded.get(key) == {"block": [8, 128, 256], "us": 123.4,
                               "measured": True}
    # unknown keys miss cleanly; corrupt schema loads empty, not crashing
    assert loaded.get("nope") is None
    path.write_text('{"schema": "something-else", "entries": {"x": 1}}')
    assert TuneCache.load(path).entries == {}


def test_resolve_block_consults_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    tuning._load_cache.cache_clear()
    base = default_block("implicit", **VGG_DEEP, variant="karatsuba",
                         base_bits=7)
    # no cache file: the heuristic default
    assert resolve_block("implicit", **VGG_DEEP, variant="karatsuba",
                         base_bits=7) == base
    # a measured (feasible, non-default) entry wins
    cache = TuneCache(tmp_path / tuning.DEFAULT_CACHE_NAME)
    key = layer_key("implicit", **VGG_DEEP, variant="karatsuba", base_bits=7)
    cache.put(key, (16, 128, 128), us=1.0)
    cache.save()
    tuning._load_cache.cache_clear()
    assert resolve_block("implicit", **VGG_DEEP, variant="karatsuba",
                         base_bits=7) == (16, 128, 128)
    # an infeasible cached entry (stale hardware model) is ignored
    cache.put(key, (32, 4096, 4096), us=1.0)
    cache.save()
    tuning._load_cache.cache_clear()
    assert resolve_block("implicit", **VGG_DEEP, variant="karatsuba",
                         base_bits=7) == base
    tuning._load_cache.cache_clear()


def test_tune_config_writes_the_committed_cache(tmp_path, monkeypatch):
    """An engine-build sweep (``tune_config``) persists into the committed
    cache file, beside other backends' entries, and resolve_block reads
    its result back; no other file is written or read."""
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    tuning._load_cache.cache_clear()
    other = layer_key("implicit", **VGG_DEEP, variant="karatsuba",
                      base_bits=7, backend="tpu")
    committed = TuneCache(tmp_path / tuning.DEFAULT_CACHE_NAME)
    committed.put(other, (8, 128, 256), us=9.0)
    committed.save()
    cfg = CNNConfig("tiny", (("conv", 3, 8, 1), ("fc", 4)), img_size=8,
                    in_channels=8, policy=MatmulPolicy.KOM_INT14)
    tuning.tune_config(cfg, iters=1)
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        tuning.DEFAULT_CACHE_NAME]
    entries = TuneCache.load(tmp_path / tuning.DEFAULT_CACHE_NAME).entries
    assert entries[other]["block"] == [8, 128, 256]
    layer = conv_layer_shapes(cfg)[0]
    key = layer_key("implicit", **layer, variant="karatsuba", base_bits=7)
    assert key in entries
    assert resolve_block("implicit", **layer, variant="karatsuba",
                         base_bits=7) == tuple(entries[key]["block"])
    tuning._load_cache.cache_clear()


def test_tune_layer_measures_and_persists(tmp_path, monkeypatch):
    """A tiny measured sweep on this backend picks a feasible block and
    persists it under the backend-scoped key."""
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    tuning._load_cache.cache_clear()
    cache = TuneCache(tmp_path / tuning.DEFAULT_CACHE_NAME)
    layer = dict(kh=3, kw=3, stride=1, h=8, cin=16, cout=8)
    best = tuning.tune_layer("implicit", **layer, variant="karatsuba",
                             base_bits=7, iters=1, cache=cache)
    ok, why = feasible("implicit", kh=3, kw=3, stride=1, h=8, cin=16,
                       cout=8, variant="karatsuba", base_bits=7, block=best)
    assert ok, why
    cache.save()
    tuning._load_cache.cache_clear()
    assert resolve_block("implicit", **layer, variant="karatsuba",
                         base_bits=7) == tuple(best)
    ent = TuneCache.load(tmp_path / tuning.DEFAULT_CACHE_NAME).get(
        layer_key("implicit", **layer, variant="karatsuba", base_bits=7))
    assert ent is not None and ent["measured"] and ent["us"] > 0
    tuning._load_cache.cache_clear()


def test_hbm_traffic_model():
    """Streamed implicit-GEMM traffic beats the materialized patch matrix by
    roughly the tap count on deep layers (the ISSUE's HBM story)."""
    from repro.core.tuning import conv_hbm_bytes
    mat = conv_hbm_bytes("im2col", **VGG_DEEP, variant="karatsuba",
                         base_bits=7)
    stream = conv_hbm_bytes("implicit", **VGG_DEEP, variant="karatsuba",
                            base_bits=7)
    assert stream < mat
    assert mat / stream > 2.0  # kh*kw=9 taps, minus streaming refetch costs
    # The winograd entry models the transform trade HONESTLY: the compact
    # NHWC A source (no patch blowup) but 2x int16 4x4-plane weights
    # re-read per row block, so it sits above the streamed implicit path on
    # bytes -- its win is arithmetic (16 tile mults vs 36 MACs), which the
    # roofline model (analysis/roofline.py) accounts separately.
    wino = conv_hbm_bytes("winograd", **VGG_DEEP, variant="karatsuba",
                          base_bits=7)
    assert wino > stream
    arr = np.array([mat, stream, wino])
    assert (arr > 0).all()


def test_winograd_vmem_model_and_candidates():
    from repro.core.tuning import winograd_vmem_bytes
    thin = winograd_vmem_bytes(kh=3, kw=3, stride=1, w_img=28, cin=64,
                               cout=512, bt=4, bc=128, variant="karatsuba")
    deep = winograd_vmem_bytes(kh=3, kw=3, stride=1, w_img=28, cin=512,
                               cout=512, bt=4, bc=128, variant="karatsuba")
    assert 0 < thin < deep
    # the heuristic default must fit the budget for every VGG winograd layer
    block = default_block("winograd", **VGG_DEEP, variant="karatsuba",
                          base_bits=7)
    ok, why = feasible("winograd", **VGG_DEEP, variant="karatsuba",
                       base_bits=7, block=block)
    assert ok, why
    for cand in candidate_blocks("winograd", **VGG_DEEP, variant="karatsuba",
                                 base_bits=7):
        ok, why = feasible("winograd", **VGG_DEEP, variant="karatsuba",
                           base_bits=7, block=cand)
        assert ok, (cand, why)
    # non-winograd geometry and float variants are infeasible by rule
    ok, why = feasible("winograd", kh=5, kw=5, stride=1, h=28, cin=64,
                       cout=64, variant="karatsuba", base_bits=7,
                       block=(4, 128))
    assert not ok and "3x3" in why
    ok, why = feasible("winograd", kh=3, kw=3, stride=1, h=28, cin=64,
                       cout=64, variant="native", base_bits=7,
                       block=(4, 128))
    assert not ok and "int" in why


def test_stem_cin_threshold_schema(tmp_path, monkeypatch):
    """The thin-stem dispatch threshold lives in the tuner cache (ISSUE 6
    satellite): default preserved with no entry, per-backend override read
    by the planner's heuristic_path (the ONE select_conv_path call site --
    select_conv_path itself is a pure shape rule with no cache IO),
    malformed entries ignored."""
    from repro.core.planner import heuristic_path
    from repro.core.substrate import select_conv_path
    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    tuning._load_cache.cache_clear()
    # no cache: the committed default threshold
    assert tuning.stem_cin() == tuning.DEFAULT_STEM_CIN == 16
    thin = dict(kh=3, kw=3, stride=1, cin=8, cout=128, on_tpu=True,
                policy="kom_int14", cached_weight=True)
    assert heuristic_path(**thin) == "im2col"
    # a measured override re-routes dispatch without code changes
    cache = TuneCache(tmp_path / tuning.DEFAULT_CACHE_NAME)
    cache.put_stem(4)
    cache.save()
    tuning._load_cache.cache_clear()
    assert tuning.stem_cin() == 4
    got = heuristic_path(**thin)
    assert got != "im2col"  # cin=8 >= 4: now a streaming/transform engine
    # ...while the pure shape rule is unaffected by the cache (no IO)
    assert select_conv_path(**thin) == "im2col"
    # backend-scoped: another backend's entry does not apply here
    assert tuning.stem_cin(backend="fake") == tuning.DEFAULT_STEM_CIN
    # malformed entries fall back to the default instead of poisoning
    cache.entries[tuning.stem_key()] = {"cin": "eight"}
    cache.save()
    tuning._load_cache.cache_clear()
    assert tuning.stem_cin() == tuning.DEFAULT_STEM_CIN
    # explicit stem_cin argument bypasses the cache entirely
    assert heuristic_path(**thin, stem_cin=4) != "im2col"
    tuning._load_cache.cache_clear()


def test_tune_layer_reports_pruned_tiles(tmp_path, monkeypatch, capsys):
    """A tile whose call raises is pruned AND said, type and first line,
    even with verbose off; the sweep then falls back to the default."""
    from repro.kernels.conv2d import ops

    def refused(*_a, **_k):
        raise RuntimeError("Mosaic failed to compile TPU kernel\ndetails")

    monkeypatch.setenv(tuning.CACHE_ENV, str(tmp_path))
    monkeypatch.setattr(ops, "conv2d_implicit", refused)
    layer = dict(kh=3, kw=3, stride=1, h=8, cin=16, cout=8)
    best = tuning.tune_layer("implicit", **layer, variant="karatsuba",
                             base_bits=7, iters=1)
    assert best == default_block("implicit", **layer, variant="karatsuba",
                                 base_bits=7)
    assert ("pruned (RuntimeError: Mosaic failed to compile TPU kernel)"
            in capsys.readouterr().out)
