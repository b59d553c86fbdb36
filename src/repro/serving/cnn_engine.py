"""Batched CNN serving engine: the paper's AlexNet/VGG16/VGG19, production-shaped.

The FPGA accelerator literature (Shen et al.'s resource partitioning, the
Abdelouahab et al. survey) gets CNN throughput from *fixed-shape* batched
pipelines with weights resident in quantized form.  This engine is that
discipline on the KOM substrate:

  * **Continuous, SLO-aware admission** -- requests join the shared
    :class:`~repro.serving.scheduler.RequestQueue` with an optional
    ``deadline`` (absolute) or named SLO class (budget resolved at submit);
    the :class:`~repro.serving.scheduler.Microbatcher` admits
    earliest-deadline-first into a small set of batch buckets (default
    1/4/16/64), zero-padding each microbatch up to the bucket its
    timing-history cost model picks (padding fraction traded against the
    projected step time, DESIGN.md 9.2).  Overdue requests are rejected
    with typed ``Expired`` results, never served late.  Submission is
    continuous -- feed the queue between steps; nothing drains to empty
    first.  The jitted forward only ever sees ``len(buckets)`` distinct
    shapes: after :meth:`warmup` (which also seeds the per-bucket timing
    history) every step is a jit cache hit.
  * **Quantize-once weights** -- under the integer KOM policies the float
    params are converted to cached :class:`~repro.core.substrate.QWeight`
    leaves (int16 values + per-output-channel scales) ONCE at engine build
    via :func:`~repro.models.cnn.cnn_quantize_params`; each step quantizes
    activations only, with per-row scales so a request's logits are
    bit-identical whatever batch-mates or padding it is served with
    (DESIGN.md section 9).
  * **Fused conv epilogue** -- the forward it serves is
    :func:`~repro.models.cnn.cnn_forward`, whose conv layers issue ONE fused
    ``conv2d(..., bias=..., activation="relu")`` call each (dequant scale +
    bias + ReLU in the conv epilogue, DESIGN.md section 7.3); the engine
    needs no knowledge of the fusion and serves bitwise-identical logits to
    the unfused pipeline under the integer policies.
  * **Images put on the device at submit** -- ``submit`` copies each
    image to the device as a flat float32 vector while fewer than
    :attr:`~CNNServeEngine.stage_limit` requests wait (later ones are
    copied when admitted, inside the step); the jitted forward stacks the
    bucket's rows, with one resident zero image in every pad slot, and
    reshapes them on the device.  So a step stacks no batch on the host
    and waits on no batch copy; the copies at submit run on the caller's
    thread between steps, not under them.
  * **Data parallelism** -- pass a ``launch.mesh`` mesh and the batch axis,
    stacked from the rows inside the jit, is sharded over its data axes
    via ``shard_map`` (params replicated);
    buckets are rounded up to multiples of the data-parallel degree so
    every shard sees a full slice.  Unpadding/gather stays on host.
  * **Planned conv dispatch** -- the engine resolves a whole-network
    :class:`~repro.core.planner.ExecutionPlan` ONCE at build (explicit
    ``plan=`` > committed ``benchmarks/tuned/plans/<backend>.json``
    artifact > heuristic fallback identical to per-call auto dispatch) and
    the jitted forward serves each conv layer on its planned engine + tile
    schedule; layers the plan leaves to the tuner still resolve their
    Pallas tiles through :mod:`repro.core.tuning` at trace time, and
    ``tune=True`` runs the measured sweep for this config's layer shapes
    at engine build and persists the argmin (DESIGN.md sections 7.4/7.6).
  * **Accounting** -- per-request latency stamps from the queue plus
    per-step bucket occupancy roll up into :meth:`stats` (images/sec, p95
    latency, padding overhead), the serving analogue of
    ``benchmarks/table_convnets.py``'s per-layer cost rows.

Typical use::

    cfg = get_config("alexnet", policy=MatmulPolicy.KOM_INT14)
    params = cnn_init(cfg, jax.random.PRNGKey(0))
    eng = CNNServeEngine(cfg, params, buckets=(1, 4, 16))
    for uid, img in enumerate(images):
        eng.submit(ImageRequest(uid=uid, image=img))
    done = eng.run()             # {uid: ImageRequest with .logits/.label}
    print(eng.stats())
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.substrate import policy_int_spec
from repro.models.cnn import CNNConfig, cnn_forward, cnn_quantize_params
from repro.serving.scheduler import (BatchContractError, EngineDownError,
                                     IncompleteRunError, Microbatcher,
                                     RetryPolicy)
from repro.serving.spans import span


@dataclasses.dataclass
class ImageRequest:
    uid: int
    image: np.ndarray                     # (H, W, C) float32
    logits: Optional[np.ndarray] = None   # (n_classes,) set at completion
    label: Optional[int] = None           # argmax(logits)
    deadline: Optional[float] = None      # absolute, engine clock domain
    slo: Optional[str] = None             # named class -> budget at submit


class CNNServeEngine:
    """Serve batched image-classification requests for a :class:`CNNConfig`."""

    def __init__(self, cfg: CNNConfig, params, *,
                 buckets: Sequence[int] = (1, 4, 16, 64),
                 mesh=None, prequantize: bool | None = None,
                 tune: bool = False, plan=None,
                 slo_budgets: Optional[dict] = None,
                 clock=None, retry: Optional[RetryPolicy] = None,
                 faults=None, advance=None):
        self.cfg = cfg
        if tune:
            # Measured tile sweep for THIS config's conv layers on THIS
            # backend, persisted to the autotuner cache -- the jitted
            # forward below then picks the tuned (bm, bc, bk)/block_h/
            # block_c per layer through tuning.resolve_block.  Without
            # `tune` the engine still consults any previously persisted
            # cache (benchmarks/tuned/default.json) at trace time.
            from repro.core.tuning import tune_config
            tune_config(cfg)
        # Integer-KOM policies: weights become cached QWeight leaves ONCE
        # here; every step then quantizes activations only.
        spec = policy_int_spec(cfg.policy)
        if prequantize is None:
            prequantize = spec is not None
        if prequantize and spec is not None:
            with span("engine.quantize_weights"):
                params = jax.block_until_ready(cnn_quantize_params(params, cfg))
        self.params = params
        # The whole-network ExecutionPlan, resolved ONCE at engine build
        # (explicit `plan` > committed benchmarks/tuned/plans/<backend>.json
        # artifact > the heuristic fallback that reproduces per-call auto
        # dispatch exactly); the jitted forward closes over it so every
        # conv layer's engine + tile schedule is fixed at trace time.  An
        # explicit cfg.conv_path overrides any plan (engine A/B lanes).
        self.plan = None
        if cfg.conv_path == "auto":
            from repro.core.planner import resolve_plan
            with span("engine.plan"):
                self.plan = resolve_plan(cfg, plan)
        elif plan is not None:
            raise ValueError(
                f"explicit conv_path={cfg.conv_path!r} and an ExecutionPlan "
                "are mutually exclusive -- drop one")
        self.mesh = mesh
        self._dp_axes: tuple = ()
        dp = 1
        if mesh is not None:
            from repro.launch.mesh import dp_axes
            self._dp_axes = dp_axes(mesh)
            for a in self._dp_axes:
                dp *= mesh.shape[a]
        self.dp = dp
        # buckets rounded up to the data-parallel degree: every mesh slice
        # gets a full (possibly padded) batch shard
        buckets = sorted({-(-int(b) // dp) * dp for b in buckets})
        # -- resilience wiring (DESIGN.md section 9.8) --
        # health ladder: healthy -> degraded (OOM drops the largest bucket,
        # then reroutes the plan to the exact materialized fallback) ->
        # down (nothing left to shed; pending requests failed typed).
        self.health = "healthy"
        self.degrade_log: List[str] = []
        self._fallback_plan_active = False
        self.faults = None
        run_clock = clock
        if faults is not None:
            from repro.serving.faults import FaultInjector
            inj = (faults if isinstance(faults, FaultInjector)
                   else FaultInjector(faults, clock=(clock or time.monotonic)))
            if inj._clock is None:
                inj._clock = clock or time.monotonic
            self.faults = inj
            # latency spikes skew the injector's clock: the batcher must
            # live in the same (warped) clock domain
            run_clock = inj.now
        kw = {} if run_clock is None else {"clock": run_clock}
        h, c = cfg.img_size, cfg.in_channels
        self._zero_row = jnp.zeros((h * h * c,), jnp.float32)
        self.staged_rows = 0      # images put on the device at submit
        self.late_rows = 0        # puts at admission (again on a retry)
        self.batcher = Microbatcher(buckets, slo_budgets=slo_budgets,
                                    retry=retry, advance=advance,
                                    on_fault=self._on_fault,
                                    assemble=self._assemble, **kw)
        with span("engine.jit"):
            self._forward = jax.jit(self._make_forward())
        self._serve_fn = (self.faults.wrap(self._run_batch)
                          if self.faults is not None else self._run_batch)

    def _make_forward(self):
        cfg, plan = self.cfg, self.plan
        shape = (-1, cfg.img_size, cfg.img_size, cfg.in_channels)

        def fwd(params, x):
            return cnn_forward(params, cfg, x, plan=plan)

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from repro.launch.mesh import shard_map_unchecked
            batch_spec = P(self._dp_axes, None, None, None)
            # params replicated (P() prefix over the whole tree, QWeight
            # leaves included); only the image batch axis is sharded.
            fwd = shard_map_unchecked(
                fwd, self.mesh,
                in_specs=(P(), batch_spec),
                out_specs=P(self._dp_axes, None),
            )

        def forward(params, rows):
            # the bucket's flat rows, stacked behind a barrier so the
            # reshape lays the batch out whole (pushed into each row, it
            # pads every row's size-1 batch axis to 128 lanes)
            x = jax.lax.optimization_barrier(jnp.stack(rows))
            return fwd(params, jnp.reshape(x, shape))

        return forward

    @property
    def buckets(self) -> tuple:
        return self.batcher.buckets

    @property
    def stage_limit(self) -> int:
        """Pending requests beyond which an image is put on the device only
        when it is admitted: twice the largest bucket, so the next batch
        and the one after it wait on the device while a deep backlog does
        not."""
        return 2 * self.batcher.buckets[-1]

    # -- admission -----------------------------------------------------------

    def submit(self, req: ImageRequest) -> None:
        """Queue one image for serving.

        The image is read at submit: it is copied to the device here,
        unless :attr:`stage_limit` requests already wait, in which case it
        is copied when the request is admitted.  Do not write into the
        array after submitting it.
        """
        if self.health == "down":
            raise EngineDownError(
                f"{self.cfg.name} engine is down; submit to a healthy "
                f"engine (the dispatcher skips down engines)")
        img = np.asarray(req.image, np.float32)
        h = self.cfg.img_size
        if img.shape != (h, h, self.cfg.in_channels):
            raise ValueError(
                f"{self.cfg.name} serves ({h}, {h}, {self.cfg.in_channels}) "
                f"images, got {img.shape}")
        staged = len(self.batcher.queue) < self.stage_limit
        if staged:
            img = self._stage(img, "engine.stage")
        self.batcher.submit(req, img, deadline=req.deadline, slo=req.slo)
        self.staged_rows += staged

    @property
    def expired(self):
        """Typed :class:`~repro.serving.scheduler.Expired` rejections."""
        return self.batcher.queue.expired

    @property
    def failed(self):
        """Typed :class:`~repro.serving.scheduler.Failed` quarantines."""
        return self.batcher.queue.failed

    @property
    def request_queue(self):
        """The shared scheduler queue (dispatcher protocol)."""
        return self.batcher.queue

    def has_work(self) -> bool:
        return bool(len(self.batcher.queue))

    def urgency(self) -> tuple:
        """(earliest deadline, earliest submit) across pending requests."""
        return self.batcher.queue.urgency()

    # -- health ---------------------------------------------------------------

    def _degrade(self) -> bool:
        """Shed capacity after an OOM-shaped failure; False = nothing left.

        The ladder: retire the largest (memory-hungriest) jit bucket shape
        while more than one remains, then reroute the whole plan to the
        materialized im2col fallback (smallest live-VMEM footprint, honors
        every policy, bitwise-equal under the integer policies -- DESIGN.md
        sections 7.6/9.8) and rebuild the jitted forward.  Each rung keeps
        the engine serving, degraded; when both are exhausted the engine
        goes down.
        """
        dropped = self.batcher.drop_largest_bucket()
        if dropped is not None:
            self.health = "degraded"
            self.degrade_log.append(f"dropped bucket {dropped}")
            return True
        if self.plan is not None and not self._fallback_plan_active:
            from repro.core.planner import materialized_fallback_plan
            self.plan = materialized_fallback_plan(self.plan)
            self._fallback_plan_active = True
            self._forward = jax.jit(self._make_forward())
            self.health = "degraded"
            self.degrade_log.append("rerouted plan to materialized im2col")
            return True
        self.mark_down("degraded-mode options exhausted after OOM")
        return False

    def _on_fault(self, kind: str, exc: BaseException, uids) -> bool:
        """Microbatcher fault hook; True aborts the batch (engine down)."""
        if self.health == "down":
            return True
        if kind != "oom":
            return False          # transient: let the retry policy handle it
        return not self._degrade()

    def mark_down(self, reason: str = "engine marked down") -> list:
        """Transition to ``down``: pending requests are failed TYPED.

        Returns the new :class:`~repro.serving.scheduler.Failed` results;
        nothing is silently lost (``done + expired + failed == submitted``
        still holds) and further submits raise :class:`EngineDownError`.
        """
        self.health = "down"
        return self.batcher.fail_pending(EngineDownError(reason))

    # -- execution -----------------------------------------------------------

    @staticmethod
    def _stage(image: np.ndarray, name: str) -> jax.Array:
        """Copy one image to the device, flat: the same float32 bytes, with
        no 3-wide minor axis for the chip's tiling to pad."""
        with span(name):
            return jax.device_put(image.reshape(-1))

    def _assemble(self, rows: list, bucket: int) -> tuple:
        """The forward's batch: the admitted rows on the device (those
        past the staging limit are put there now), then the resident zero
        image in every pad slot -- one signature per bucket."""
        if len(rows) > bucket:
            raise BatchContractError(f"{len(rows)} rows exceed bucket {bucket}")
        out = []
        for row in rows:
            if isinstance(row, np.ndarray):
                row = self._stage(row, "engine.stage_late")
                self.late_rows += 1
            out.append(row)
        return tuple(out) + (self._zero_row,) * (bucket - len(rows))

    def _run_batch(self, rows: tuple) -> np.ndarray:
        """One bucket through the jitted forward, once its rows' copies to
        the device have landed."""
        with span("engine.to_device"):
            rows = jax.block_until_ready(rows)
        with span("engine.forward"):
            out = jax.block_until_ready(self._forward(self.params, rows))
        with span("engine.from_device"):
            return np.asarray(out)

    def warmup(self) -> None:
        """Compile every bucket shape up front (steady-state = cache hits).

        Also seeds the batcher's per-bucket service-time history with a
        post-compile timed call per bucket, so the very first scheduling
        decisions run the cost model instead of flying blind.  Spans:
        ``engine.warmup`` holds ``engine.warmup.b<bucket>.first`` (the
        compile or persistent-cache load) and ``engine.warmup.b<bucket>.timed``
        for each bucket.
        """
        import time as _time

        with span("engine.warmup"):
            for b in self.batcher.buckets:
                zeros = self._assemble([], b)
                with span(f"engine.warmup.b{b}.first"):
                    jax.block_until_ready(self._forward(self.params, zeros))
                with span(f"engine.warmup.b{b}.timed"):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(self._forward(self.params, zeros))
                    self.batcher.record_service(b, _time.perf_counter() - t0)

    def step(self) -> List[ImageRequest]:
        """Serve one microbatch; returns the requests completed by it."""
        if self.health == "down":
            raise EngineDownError(f"{self.cfg.name} engine is down")
        completed = self.batcher.step(self._serve_fn)
        with span("batch.finish"):
            out = []
            for req, logits in completed:
                req.logits = logits
                req.label = int(np.argmax(logits))
                out.append(req)
        return out

    def run(self, max_steps: int = 10_000) -> Dict[int, ImageRequest]:
        """Drain the queue (mixed request streams welcome); returns done.

        Raises :class:`~repro.serving.scheduler.IncompleteRunError` when
        ``max_steps`` cuts the drain off with requests still pending -- the
        old silent partial return read as "complete" and lost the tail.
        Expired requests are NOT an error: they land in :attr:`expired`
        as typed results.
        """
        steps = 0
        while len(self.batcher.queue) and steps < max_steps:
            self.step()
            steps += 1
        if len(self.batcher.queue):
            raise IncompleteRunError(
                self.batcher.queue.done,
                [r.uid for r in self.batcher.queue.pending], max_steps)
        return self.batcher.queue.done

    # -- accounting -----------------------------------------------------------

    def stats(self) -> dict:
        """Latency/throughput roll-up, images/sec included."""
        s = self.batcher.stats()
        s["images_done"] = s.pop("requests_done")
        s["images_per_s"] = s.pop("throughput_rps")
        s["buckets"] = self.batcher.buckets
        s["data_parallel"] = self.dp
        s["staged_rows"] = self.staged_rows
        s["late_rows"] = self.late_rows
        s["health"] = self.health
        s["degrade_log"] = list(self.degrade_log)
        if self.faults is not None:
            s["faults"] = self.faults.stats()
        return s
