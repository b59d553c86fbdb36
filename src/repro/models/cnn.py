"""The paper's own CNNs -- AlexNet, VGG16, VGG19 -- on the systolic engine.

Every conv goes through the substrate's single ``conv2d`` entry point
(:func:`repro.core.substrate.conv2d`), which picks the im2col-GEMM, Pallas
systolic or implicit-GEMM path per layer shape and policy (the integer
serving path streams patches through the implicit GEMM -- no HBM im2col
materialization -- with tile schedules resolved per layer by the
:mod:`repro.core.tuning` autotuner); every FC goes through
``policy_linear``.  The paper's resource analysis (Tables 1-4:
3x3/5x5/7x7/11x11 kernels) is thus exercised end to end on one multiplier
substrate.

For the integer KOM policies, :func:`cnn_quantize_params` converts the float
weights into cached :class:`~repro.core.substrate.QWeight` leaves ONCE at
model build -- per-output-channel scales, int16 storage -- so the forward
pass quantizes only activations (DESIGN.md section 7.2).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.core.precision import MatmulPolicy, policy_linear
from repro.core.substrate import (QActivation, QWeight, conv2d,
                                  policy_int_spec, quantize_weight)
from repro.core.systolic import pool2d

#: Thin-stem floor for the pool_quant handoff: a consumer thinner than this
#: is on the im2col stem path anyway (see ``select_conv_path``), so the
#: producer must not hand it pre-quantized activations.
HANDOFF_MIN_CIN = 16


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    # layer spec: ("conv", k, cout, stride) | ("pool",) | ("fc", n)
    layers: Tuple[tuple, ...]
    img_size: int
    in_channels: int = 3
    n_classes: int = 1000
    policy: MatmulPolicy = MatmulPolicy.NATIVE_BF16
    # auto | im2col | systolic | implicit | winograd (substrate dispatch,
    # DESIGN.md 7.1/7.4/7.5; winograd needs an int policy + 3x3/s1 layers,
    # other shapes reroute to implicit)
    conv_path: str = "auto"
    family: str = "cnn"      # registry/launcher dispatch tag

    def replace(self, **kw) -> "CNNConfig":
        return dataclasses.replace(self, **kw)


def _vgg_layers(block_sizes: List[int]) -> Tuple[tuple, ...]:
    chans = [64, 128, 256, 512, 512]
    layers: List[tuple] = []
    for c, n in zip(chans, block_sizes):
        layers += [("conv", 3, c, 1)] * n + [("pool",)]
    layers += [("fc", 4096), ("fc", 4096), ("fc", 1000)]
    return tuple(layers)


ALEXNET = CNNConfig(
    "alexnet",
    (
        ("conv", 11, 96, 4), ("pool",),
        ("conv", 5, 256, 1), ("pool",),
        ("conv", 3, 384, 1), ("conv", 3, 384, 1), ("conv", 3, 256, 1), ("pool",),
        ("fc", 4096), ("fc", 4096), ("fc", 1000),
    ),
    img_size=227,
)
VGG16 = CNNConfig("vgg16", _vgg_layers([2, 2, 3, 3, 3]), img_size=224)
VGG19 = CNNConfig("vgg19", _vgg_layers([2, 2, 4, 4, 4]), img_size=224)


def cnn_reduced(cfg: CNNConfig, *, img_size: int | None = None,
                max_channels: int = 16, max_fc: int = 32,
                n_classes: int = 16) -> CNNConfig:
    """CPU-smoke-test twin of a CNN config: same topology, tiny widths.

    Keeps every layer (all kernel sizes/strides/pools of the full network,
    so the conv-path dispatch sees the same shapes-of-interest) but caps
    channel and FC widths.  AlexNet keeps its VALID 11x11/stride-4 first
    layer by defaulting to img_size=67; the VGGs shrink to 32 (five pools
    -> 1x1 feature map, as in the full network's 224 -> 7x7).
    """
    if img_size is None:
        img_size = 67 if cfg.name == "alexnet" else 32
    layers = []
    for spec in cfg.layers:
        if spec[0] == "conv":
            _, k, cout, stride = spec
            layers.append(("conv", k, min(cout, max_channels), stride))
        elif spec[0] == "fc":
            layers.append(("fc", min(spec[1], max_fc)))
        else:
            layers.append(spec)
    # the classifier head keeps its own width
    layers[-1] = ("fc", n_classes)
    return cfg.replace(layers=tuple(layers), img_size=img_size,
                       n_classes=n_classes)


def cnn_conv_geometries(cfg: CNNConfig) -> List[dict]:
    """Every conv layer's geometry, in layer order (the planner's work list).

    One dict per conv layer: ``{kh, kw, stride, h, cin, cout, padding}`` --
    the exact shape tuple :func:`cnn_forward` will call ``conv2d`` with,
    including AlexNet's VALID first layer.  This is THE walker of a
    ``CNNConfig``'s conv spine; the tuner (``conv_layer_shapes``), the
    planner (:mod:`repro.core.planner`) and the benchmark tables all derive
    their layer lists from it instead of re-implementing the h/cin
    evolution.
    """
    out: List[dict] = []
    h, cin = cfg.img_size, cfg.in_channels
    first = True
    for spec in cfg.layers:
        if spec[0] == "conv":
            _, k, cout, stride = spec
            padding = "VALID" if (cfg.name == "alexnet" and first) else "SAME"
            oh = ((h - k) // stride + 1) if padding == "VALID" \
                else -(-h // stride)
            first = False
            out.append(dict(kh=k, kw=k, stride=stride, h=h, cin=cin,
                            cout=cout, padding=padding))
            h, cin = oh, cout
        elif spec[0] == "pool":
            h = h // 2
        else:
            break
    return out


def cnn_layer_topology(cfg: CNNConfig) -> List[dict]:
    """:func:`cnn_conv_geometries` plus the fusion-relevant adjacency.

    Per conv POSITION (not per deduped geometry): the geometry dict plus
    ``pool_after`` (the next layer is the 2x2/s2 maxpool, so the ``pool``
    epilogue fusion applies here) and ``handoff_next`` (additionally, the
    conv AFTER that pool is a 3x3/s1/SAME layer wide enough for the
    ``pool_quant`` handoff).  The planner's fusion axis, ``planner
    --check``'s applicability validation and the whole-network traffic
    model all read this one walker instead of re-deriving adjacency.
    """
    geoms = cnn_conv_geometries(cfg)
    out: List[dict] = []
    gi = 0
    for i, spec in enumerate(cfg.layers):
        if spec[0] != "conv":
            continue
        g = geoms[gi]
        gi += 1
        pool_after = i + 1 < len(cfg.layers) and cfg.layers[i + 1] == ("pool",)
        nxt = cfg.layers[i + 2] if pool_after and i + 2 < len(cfg.layers) \
            else None
        handoff_next = bool(
            pool_after and nxt is not None and nxt[0] == "conv"
            and nxt[1] == 3 and nxt[3] == 1 and g["cout"] >= HANDOFF_MIN_CIN)
        out.append({**g, "pool_after": pool_after,
                    "handoff_next": handoff_next})
    return out


def cnn_layer_names(cfg: CNNConfig) -> List[str]:
    """The published name of each entry of ``cfg.layers``, in order.

    VGG numbers convs by block (``conv1_1`` ... ``conv5_3``), each pool after
    its block (``pool1`` ... ``pool5``); AlexNet numbers convs flat
    (``conv1`` ... ``conv5``), each pool after the conv before it (``pool1``,
    ``pool2``, ``pool5``).  FC layers continue the count (``fc6`` ...).
    """
    by_block = cfg.name.startswith("vgg")
    names: List[str] = []
    block, in_block, n_conv, n_fc = 1, 0, 0, 0
    for spec in cfg.layers:
        if spec[0] == "conv":
            n_conv += 1
            in_block += 1
            names.append(f"conv{block}_{in_block}" if by_block
                         else f"conv{n_conv}")
        elif spec[0] == "pool":
            names.append(f"pool{block}" if by_block else f"pool{n_conv}")
            block, in_block = block + 1, 0
        else:
            n_fc += 1
            names.append(f"fc{(block - 1 if by_block else n_conv) + n_fc}")
    return names


def cnn_layer_scopes(cfg: CNNConfig) -> List[str]:
    """``jax.named_scope`` of each layer in :func:`cnn_forward`: ``l<i>.<name>``.

    ``i`` (two digits) is the layer's position in ``cfg.layers``, so a
    profiler op under ``l02.conv1_2/...`` joins the layer's shape with no
    name table.  The scopes reach only the HLO metadata, never the ops.
    """
    return [f"l{i:02d}.{n}" for i, n in enumerate(cnn_layer_names(cfg))]


def cnn_init(cfg: CNNConfig, key, dtype=jnp.float32):
    params = []
    cin = cfg.in_channels
    h = cfg.img_size
    feat = None
    first_conv = True
    for spec in cfg.layers:
        key, sub = jax.random.split(key)
        if spec[0] == "conv":
            _, k, cout, stride = spec
            fan = k * k * cin
            params.append({
                "w": (jax.random.normal(sub, (k, k, cin, cout), dtype)
                      / fan**0.5).astype(dtype),
                "b": jnp.zeros((cout,), dtype),
            })
            cin = cout
            if cfg.name == "alexnet" and first_conv:
                h = (h - k) // stride + 1       # VALID first layer
            else:
                h = -(-h // stride)             # SAME
            first_conv = False
        elif spec[0] == "pool":
            params.append({})
            h = h // 2
        else:  # fc
            _, n = spec
            if feat is None:
                feat = h * h * cin
            params.append({
                "w": (jax.random.normal(sub, (feat, n), dtype) / feat**0.5
                      ).astype(dtype),
                "b": jnp.zeros((n,), dtype),
            })
            feat = n
    return params


def cnn_quantize_params(params, cfg: CNNConfig):
    """Quantize every conv/FC weight ONCE, per-output-channel.

    Returns the params pytree with float "w" leaves replaced by cached
    :class:`QWeight` (int16 values + per-cout f32 scales) when ``cfg.policy``
    is an integer KOM policy; float policies return ``params`` unchanged.
    The forward pass then quantizes only activations -- no per-forward
    whole-tensor weight requantization.
    """
    spec = policy_int_spec(cfg.policy)
    if spec is None:
        return params
    _, base_bits = spec
    out = []
    for p in params:
        if "w" in p and not isinstance(p["w"], QWeight):
            out.append({**p, "w": quantize_weight(p["w"], base_bits=base_bits)})
        else:
            out.append(p)
    return out


def _handoff_consumer_ok(cfg: CNNConfig, params, i: int) -> bool:
    """True iff conv position ``i``'s pool_quant handoff has a taker.

    The layer after position ``i``'s pool must be a 3x3/s1/SAME conv on
    the cached-QWeight serving path with cin >= HANDOFF_MIN_CIN -- the
    shape/policy conditions under which :func:`conv2d` accepts a
    :class:`QActivation`.
    """
    j = i + 2
    if j >= len(cfg.layers) or cfg.layers[j][0] != "conv":
        return False
    _, k2, _, stride2 = cfg.layers[j]
    _, _, cout_i, _ = cfg.layers[i]
    return (k2 == 3 and stride2 == 1 and cout_i >= HANDOFF_MIN_CIN
            and isinstance(params[j]["w"], QWeight))


def cnn_forward(params, cfg: CNNConfig, x, plan=None, *, fuse=True):
    """x: (n, H, W, C) image batch -> (n, n_classes) logits.

    ``params`` may hold float weights or cached QWeight leaves (from
    :func:`cnn_quantize_params`); both route through the same substrate.

    ``plan``: an :class:`~repro.core.planner.ExecutionPlan` fixing each
    conv layer's engine + tile schedule.  ``None`` with
    ``cfg.conv_path == "auto"`` resolves the chain ONCE here (committed
    artifact for this (model, policy, backend), else the heuristic plan
    that reproduces per-call auto dispatch exactly); an explicit
    ``cfg.conv_path`` overrides any plan.  Plan entries apply only to
    layers actually on the cached-weight serving path -- float weights
    under an integer policy keep the trainable im2col STE dispatch --
    and layers the plan does not cover (e.g. a reduced twin's shrunken
    geometries against a full-size artifact) fall back to auto.

    Plan entries with ``fusion`` "pool"/"pool_quant" fold the FOLLOWING
    maxpool (and the next layer's activation quantization) into the conv
    epilogue where the fusion actually applies: plan entries are keyed by
    geometry, which dedups positions, so the fusion only fires at
    positions the topology supports (implicit path, a pool next, and for
    pool_quant an eligible 3x3/s1 consumer -- DESIGN.md section 7.7).
    ``fuse=False`` runs the UNFUSED reference pipeline for the same plan
    (separate conv -> pool2d -> handoff_quantize calls); the two are
    bitwise equal, which the fused-dataflow tests assert per model.
    """
    use_plan = cfg.conv_path == "auto"
    if use_plan and plan is None:
        from repro.core.planner import resolve_plan
        plan = resolve_plan(cfg)
    spec_int = policy_int_spec(cfg.policy)
    int_policy = spec_int is not None
    first_conv = True
    skip_pool = False        # the previous conv already pooled in-epilogue
    quant_after_pool = None  # unfused reference: quantize after pool2d
    scopes = cnn_layer_scopes(cfg)
    for i, spec in enumerate(cfg.layers):
        p = params[i]
        with jax.named_scope(scopes[i]):
            if spec[0] == "conv":
                _, k, cout, stride = spec
                padding = "VALID" if (cfg.name == "alexnet" and first_conv) else "SAME"
                first_conv = False
                path, block, fusion = cfg.conv_path, None, "bias_relu"
                if use_plan and plan is not None \
                        and (not int_policy or isinstance(p["w"], QWeight)):
                    ent = plan.lookup(kh=k, kw=k, stride=stride, h=x.shape[1],
                                      cin=x.shape[3], cout=cout, padding=padding)
                    if ent is not None:
                        path, block, fusion = ent.path, ent.block, ent.fusion
                if isinstance(x, QActivation):
                    # A handoff input is an implicit-engine contract; the
                    # entry's block still applies when it planned implicit.
                    if path != "implicit":
                        path, block = "implicit", None
                do_pool = (fusion in ("pool", "pool_quant") and path == "implicit"
                           and i + 1 < len(cfg.layers)
                           and cfg.layers[i + 1] == ("pool",))
                do_quant = (do_pool and fusion == "pool_quant" and int_policy
                            and _handoff_consumer_ok(cfg, params, i))
                # One fused call per conv layer: bias add + ReLU (and the dequant
                # scale under integer policies) ride the conv epilogue instead of
                # three HBM round-trips (DESIGN.md section 7.3).
                if fuse and do_pool:
                    x = conv2d(x, p["w"], stride=stride, padding=padding,
                               policy=cfg.policy, path=path, block=block,
                               bias=p["b"], activation="relu",
                               pool=(2, 2, "VALID"),
                               quantize_next=spec_int[1] if do_quant else None)
                    skip_pool = True
                else:
                    x = conv2d(x, p["w"], stride=stride, padding=padding,
                               policy=cfg.policy, path=path, block=block,
                               bias=p["b"], activation="relu")
                    if do_pool and do_quant:
                        quant_after_pool = spec_int[1]
            elif spec[0] == "pool":
                if skip_pool:
                    skip_pool = False
                else:
                    x = pool2d(x, window=2, stride=2, kind="max")
                    if quant_after_pool is not None:
                        from repro.kernels.conv2d import handoff_quantize
                        x = handoff_quantize(x, base_bits=quant_after_pool)
                        quant_after_pool = None
            else:
                if x.ndim == 4:
                    x = x.reshape(x.shape[0], -1)
                x = policy_linear(x, p["w"], policy=cfg.policy) + p["b"]
                # Positional check: every FC but the classifier head gets ReLU.
                # (Comparing specs by VALUE would skip ReLU on any hidden FC whose
                # spec equals the classifier's, e.g. duplicate ("fc", n) layers.)
                if i != len(cfg.layers) - 1:
                    x = jax.nn.relu(x)
    return x


def cnn_loss(params, cfg: CNNConfig, x, labels):
    logits = cnn_forward(params, cfg, x)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
