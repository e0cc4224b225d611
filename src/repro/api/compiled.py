"""``compile(model, params, options) -> CompiledModel`` — the facade core.

One call runs the whole co-design lifecycle the paper argues must be a
single decision: plan (per-layer ConvPlans + whole-network layout elision,
warm v4 cache) → prepare (bn fold, block padding, offline Winograd weight
pre-transform — all outside the jit) → jit (sharded ``run_network`` per
batch shape).  The result exposes the four verbs serving needs:

  .run(x)          jitted inference at x's batch size (compiled shapes are
                   cached per batch; ``options.batch`` is compiled eagerly)
  .serve(...)      a CNNServingEngine (bucket ladder) / ServingEngine
                   (continuous batching) built *from* this compilation —
                   no re-plumbing of planner, cache, buckets, or mesh
  .plan_report()   the resolved co-design decisions, machine-readable
  .save()/load()   persist the option surface + model identity; the plan
                   cache (v4) carries the tuning, so load() re-tunes nothing

LM configs (the transformer/recurrent zoo) compile through the same entry
point: ``run`` is the jitted full-sequence forward, ``serve`` the
continuous-batching engine's prefill/decode path.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Sequence, Tuple

from repro.api.model import CNNModel, as_model, is_lm_config
from repro.api.options import ExecutionOptions
from repro.spans import span

SAVE_FORMAT = "repro.api/1"


def _jnp_dtype(name: str):
    import jax.numpy as jnp

    return jnp.dtype(name)


class CompiledModel:
    """Common surface of a compiled model; ``compile`` returns a subclass."""

    model: Any
    params: Any
    options: ExecutionOptions

    def run(self, x):
        raise NotImplementedError

    def __call__(self, x):
        return self.run(x)

    def serve(self, **kw):
        raise NotImplementedError

    def plan_report(self) -> Dict[str, Any]:
        raise NotImplementedError

    def verify_report(self, batch: Optional[int] = None,
                      level: Optional[str] = None):
        raise NotImplementedError(
            f"{type(self).__name__} does not support static plan verification"
        )

    def save(self, path: Optional[str] = None) -> str:
        raise NotImplementedError

    def _save_payload(self, kind: str, model_desc: Dict[str, Any],
                      path: Optional[str]) -> str:
        payload = {
            "format": SAVE_FORMAT,
            "kind": kind,
            "model": model_desc,
            "options": self.options.to_json(),
        }
        if path is None:
            base = os.path.dirname(self.options.cache_path or "") or "."
            path = os.path.join(
                base, f"{model_desc.get('name', 'model')}.compiled.json"
            )
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        return path


class CompiledCNN(CompiledModel):
    """A CNN compiled end-to-end: NetworkPlan + NetworkExecutor per batch.

    ``compile`` plans ``options.batch`` eagerly (the cold-start tunes land
    in the v4 cache immediately); other batch sizes — ``run`` on a new
    batch, ``serve``'s bucket ladder — plan and jit on first use and are
    cached, so the compiled-shape set stays bounded and explicit.
    """

    def __init__(
        self,
        model: CNNModel,
        params: Sequence[Dict],
        options: ExecutionOptions,
        planner=None,
        devices: Optional[Sequence[Any]] = None,
        calibration: Optional[Any] = None,
    ):
        self.model = model
        self.params = list(params)
        self.options = options
        # int8 activation-scale calibration batch (B, H, W, C) fp32; None
        # uses a deterministic synthetic batch (core/quant.py).  Unused —
        # and free — when no layer resolves to int8.
        self.calibration = calibration
        # Ownership decides persistence: a planner we created is ours to
        # save; a caller-supplied (possibly shared) planner keeps its own
        # persistence discipline — compiling must not rewrite its cache
        # file as a side effect.
        self._own_planner = planner is None
        self.planner = planner if planner is not None else options.make_planner()
        self._devices = devices
        self._netplans: Dict[int, Any] = {}
        self._executors: Dict[int, Any] = {}
        self._pipeplans: Dict[int, Any] = {}
        self._pipe_executors: Dict[int, Any] = {}
        # Eager by design: compile() means the default batch is planned and
        # its executor prepared (params folded/padded/pre-transformed) —
        # cold-start tunes land in the v4 cache now, not at first request.
        self._executor_for(options.batch)
        self.save_plans()

    # -- planning -------------------------------------------------------------

    def network_plan(self, batch: Optional[int] = None):
        """The (cached) whole-network plan for one batch size."""
        from repro.core.netplan import plan_network

        b = int(batch) if batch is not None else self.options.batch
        if b not in self._netplans:
            self._netplans[b] = plan_network(
                self.model.layers, *self.model.input_hw, self.planner,
                in_channels=self.model.in_channels, batch=b,
                dtype=self.options.dtype,
            )
        return self._netplans[b]

    def executor(self, batch: Optional[int] = None):
        """The (cached) jitted NetworkExecutor for one batch size."""
        from repro.core.netplan import NetworkExecutor

        b = int(batch) if batch is not None else self.options.batch
        if b not in self._executors:
            netplan = self.network_plan(b)
            devices = self._devices
            if devices is None and not self.options.shard_batch:
                import jax

                devices = jax.devices()[:1]
            self._executors[b] = NetworkExecutor(
                netplan, self.params, interpret=self.options.interpret,
                devices=devices, pretransform=self.options.pretransform,
                calibration=self.calibration,
            )
            # Persistence stays with the *burst*, not the bucket: __init__,
            # run(), and the serving engine call save_plans() once after
            # their planning is done — a cold bucket ladder costs one cache
            # merge+write, not one per executor.
            if self.options.validate != "off":
                from repro.analysis import PlanVerificationError

                report = self.verify_report(
                    batch=b, level=self.options.validate
                )
                if not report.ok:
                    del self._executors[b]
                    raise PlanVerificationError(report)
        return self._executors[b]

    def pipeline_plan(self, batch: Optional[int] = None):
        """The (cached) cost-balanced stage partition for one batch size.

        Requires ``options.pipeline_stages >= 2``.  Warm-cached in the v6
        plan cache keyed by (network digest, n_stages, chip, dtype) —
        ``planner.pipeline_hits`` counts reconstructions that re-partitioned
        nothing.
        """
        from repro.core.netplan import plan_pipeline

        if self.options.pipeline_stages < 2:
            raise ValueError(
                "pipeline_plan() requires ExecutionOptions("
                f"pipeline_stages=...) >= 2, got "
                f"{self.options.pipeline_stages}"
            )
        b = int(batch) if batch is not None else self.options.batch
        if b not in self._pipeplans:
            self._pipeplans[b] = plan_pipeline(
                self.model.layers, *self.model.input_hw, self.planner,
                self.options.pipeline_stages,
                in_channels=self.model.in_channels, batch=b,
                dtype=self.options.dtype, netplan=self.network_plan(b),
            )
        return self._pipeplans[b]

    def pipeline_executor(self, batch: Optional[int] = None):
        """The (cached) jitted PipelineExecutor for one batch size."""
        from repro.distributed.pipeline import PipelineExecutor

        b = int(batch) if batch is not None else self.options.batch
        if b not in self._pipe_executors:
            pipeplan = self.pipeline_plan(b)
            if self.options.validate != "off":
                # The partition has its own static legality contract
                # (verify_pipeline).  At validate='kernel'/'full' the
                # per-stage forwards are also traced at microbatch size and
                # the kernel-interior passes run over every stage's
                # pallas_calls — the prepared params come from an interpret
                # NetworkExecutor, the same subject verify_report() uses.
                from repro.analysis import (
                    PlanVerificationError,
                    verify_pipeline,
                )

                lvl = (
                    "kernel" if self.options.validate in ("kernel", "full")
                    else "plan"
                )
                kw = {}
                if lvl == "kernel":
                    from repro.core.netplan import NetworkExecutor

                    ex = self._executors.get(b) or NetworkExecutor(
                        self.network_plan(b), self.params, interpret=True,
                        devices=self._devices,
                        pretransform=self.options.pretransform,
                        calibration=self.calibration,
                    )
                    kw = dict(
                        params=ex.params, pretransformed=ex.pretransformed
                    )
                report = verify_pipeline(
                    self.network_plan(b), pipeplan, name=self.model.name,
                    level=lvl, **kw,
                )
                if not report.ok:
                    raise PlanVerificationError(report)
            n_micro = (
                None if self.options.microbatch == "auto"
                else int(self.options.microbatch)
            )
            self._pipe_executors[b] = PipelineExecutor(
                self.network_plan(b), pipeplan, self.params,
                interpret=self.options.interpret, devices=self._devices,
                pretransform=self.options.pretransform,
                calibration=self.calibration, n_micro=n_micro,
            )
        return self._pipe_executors[b]

    def _executor_for(self, batch: Optional[int] = None):
        """The executor ``run()``/serving dispatch to: the pipeline one when
        ``pipeline_stages`` is set, the data-parallel one otherwise."""
        if self.options.pipeline_stages >= 2:
            return self.pipeline_executor(batch)
        return self.executor(batch)

    def verify_report(self, batch: Optional[int] = None,
                      level: Optional[str] = None):
        """Statically verify this compilation (repro.analysis).

        Runs the plan verifier over the executor's *prepared* state — the
        exact params and pretransform flags the jitted forward consumes —
        and returns the structured ``VerifyReport`` (findings + per-kernel
        footprint/traffic metrics).  ``level`` defaults to 'full' (trace
        the forward and run every pass); pass 'plan' for the trace-free
        subset or 'kernel' for the kernel-interior proofs only (race /
        bounds / accum / int8 overflow).  Independent of
        ``options.validate``: that option makes compilation *gate* on
        this report, this method just produces it.
        """
        from repro.analysis import verify_network

        lvl = level if level not in (None, "off") else "full"
        b = int(batch) if batch is not None else self.options.batch
        netplan = self.network_plan(b)
        if lvl == "plan":
            return verify_network(
                netplan, level="plan",
                vmem_budget=self.options.vmem_budget,
                name=self.model.name,
            )
        # Build (or reuse) the executor outside the validate gate: its
        # prepared params are the verification subject.
        if b in self._executors:
            ex = self._executors[b]
        else:
            from repro.core.netplan import NetworkExecutor

            ex = NetworkExecutor(
                netplan, self.params, interpret=True,
                devices=self._devices,
                pretransform=self.options.pretransform,
                calibration=self.calibration,
            )
        return verify_network(
            netplan, ex.params, pretransformed=ex.pretransformed,
            level=lvl, vmem_budget=self.options.vmem_budget,
            name=self.model.name,
        )

    def save_plans(self, force: bool = False) -> None:
        """Persist the planner's v4 cache when there is something to write.

        No-op unless this compilation owns the planner (caller-supplied
        planners manage their own persistence) and new tunes/network
        entries landed since the last save — so planning bursts cost one
        merge+write, not one per bucket.
        """
        if not self._own_planner or not self.planner.cache_path:
            return
        if force or getattr(self.planner, "_dirty", True):
            self.planner.save()

    # -- the four verbs -------------------------------------------------------

    def run(self, x):
        """Jitted whole-network inference on an (B, H, W, C) batch.

        Records host spans (``repro.spans``): ``run`` and, inside it,
        ``run.asarray`` (the input cast), ``run.executor`` (the executor
        lookup and ``save_plans``) and ``run.call`` (the executor call, up
        to the jitted dispatch)."""
        import jax.numpy as jnp

        with span("run"):
            # input_dtype, not dtype: under int8 the batch stays fp32 and
            # is quantized per layer inside the executor.
            with span("run.asarray"):
                x = jnp.asarray(x, _jnp_dtype(self.options.input_dtype))
            if x.ndim != 4:
                raise ValueError(
                    f"run() expects (B, H, W, C), got shape {tuple(x.shape)}"
                )
            with span("run.executor"):
                executor = self._executor_for(int(x.shape[0]))
                self.save_plans()   # no-op unless this batch tuned new plans
            with span("run.call"):
                return executor(x)

    def serve(self, buckets: Optional[Tuple[int, ...]] = None, **kw):
        """A CNNServingEngine over this compilation's bucket ladder.

        Everything else the engine needs (impl, interpret, dtype, mesh,
        planner, cache, resilience policy — ``max_queue``,
        ``default_deadline_s``, ``fallback``, ``retries``) comes from this
        compilation — that is the point.  ``engine.health()`` reports the
        resilience state; ``kw`` passes test hooks (``clock=``, ``faults=``,
        ``probe_after=``) through to the engine.
        """
        from repro.serving.cnn_engine import CNNServingEngine

        return CNNServingEngine.from_compiled(self, buckets=buckets, **kw)

    def plan_report(self, batch: Optional[int] = None) -> Dict[str, Any]:
        """The resolved co-design decisions, machine-readable.

        One row per conv layer: algorithm, impl, kernel blocks, predicted
        (or measured) seconds, plan provenance, and whether the layer's
        output boundary was elided (padded channels flow to the next
        pallas_call).  Plus planner/network cache counters — a warm process
        reports ``tunes == 0``.

        With ``pipeline_stages`` set, every layer row gains a ``stage``
        column and the report a ``pipeline`` block: stage bounds,
        per-stage predicted seconds, the resolved microbatch count, the
        modeled bubble fraction and end-to-end latency.
        """
        netplan = self.network_plan(batch)
        pipeplan = (
            self.pipeline_plan(batch)
            if self.options.pipeline_stages >= 2 else None
        )

        def stage_of(index: int):
            if pipeplan is None:
                return None
            for si, (a, z) in enumerate(pipeplan.stage_bounds):
                if a <= index < z:
                    return si
            return None

        rows = []
        for s in netplan.steps:
            if s.plan is None:
                continue
            row = {
                "index": s.index,
                "algorithm": s.plan.algorithm.value,
                "impl": s.plan.impl,
                "dtype": s.plan.dtype,
                "kernel": getattr(s.layer, "kernel", None),
                "stride": getattr(s.layer, "stride", None),
                "in_hw": list(s.in_hw),
                "kernel_blocks": list(s.plan.kernel_blocks),
                "predicted_s": s.plan.predicted_s,
                "source": s.plan.source,
                "winograd_fused": s.plan.winograd_fused,
                "taps_folded": s.plan.taps_folded,
                "elided": not s.out_layout.trivial,
            }
            if pipeplan is not None:
                row["stage"] = stage_of(s.index)
            rows.append(row)
        report = {
            "model": self.model.name,
            "kind": "cnn",
            "batch": netplan.batch,
            "impl": netplan.impl,
            "dtype": netplan.dtype_name,
            "elided_boundaries": netplan.elided_boundaries,
            "predicted_total_s": sum(r["predicted_s"] for r in rows),
            "layers": rows,
            "tunes": self.planner.stats["tunes"],
            "hits": self.planner.stats["hits"],
            "network_hits": self.planner.network_hits,
            "pipeline_hits": self.planner.pipeline_hits,
        }
        if pipeplan is not None:
            report["pipeline"] = {
                "n_stages": pipeplan.n_stages,
                "stage_bounds": [list(b) for b in pipeplan.stage_bounds],
                "stage_seconds": list(pipeplan.stage_seconds),
                "n_micro": pipeplan.n_micro,
                "bubble_fraction": pipeplan.bubble_fraction(),
                "modeled_latency_s": pipeplan.modeled_latency_s(),
            }
        return report

    def save(self, path: Optional[str] = None) -> str:
        """Persist this compilation: plan cache (the tuning) + a small JSON
        artifact (model identity + the full option surface).  ``load``
        reconstructs with zero re-tunes."""
        self.save_plans()
        return self._save_payload(
            "cnn",
            {
                "name": self.model.name,
                "digest": self.model.digest,
                "input_hw": list(self.model.input_hw),
                "in_channels": self.model.in_channels,
            },
            path,
        )


class CompiledLM(CompiledModel):
    """An LM config compiled through the same facade: jitted full-sequence
    forward for ``run``, the continuous-batching engine for ``serve``."""

    def __init__(self, cfg, params, options: ExecutionOptions):
        import jax

        from repro.models import transformer as tf

        self.model = cfg
        self.params = params
        self.options = options
        self._tf = tf
        self._fwd = jax.jit(lambda p, batch: tf.forward(cfg, p, batch)[0])

    def run(self, tokens):
        """Full-sequence logits.  ``tokens``: (B, S) int32, or a model-input
        dict for frontend architectures (audio frames, vision patches)."""
        import jax.numpy as jnp

        batch = tokens if isinstance(tokens, dict) else {
            "tokens": jnp.asarray(tokens, jnp.int32)
        }
        return self._fwd(self.params, batch)

    def serve(self, batch_size: Optional[int] = None, capacity: int = 256,
              **engine_opts):
        """A continuous-batching ServingEngine (prefill/decode) for this
        model.  ``batch_size`` defaults to the largest option bucket."""
        from repro.serving.engine import ServingEngine

        return ServingEngine.from_compiled(
            self, batch_size=batch_size, capacity=capacity, **engine_opts,
        )

    def plan_report(self) -> Dict[str, Any]:
        return {
            "model": self.model.name,
            "kind": "lm",
            "num_layers": self.model.num_layers,
            "layer_pattern": list(self.model.pattern_layers),
            "supports_decode": self.model.supports_decode,
            "dtype": self.options.dtype,
        }

    def save(self, path: Optional[str] = None) -> str:
        return self._save_payload("lm", {"name": self.model.name}, path)


def compile(  # noqa: A001 - deliberate: repro.compile is the public verb
    model: Any,
    params: Any,
    options: Optional[ExecutionOptions] = None,
    *,
    input_hw: Optional[Tuple[int, int]] = None,
    in_channels: int = 3,
    name: Optional[str] = None,
    planner=None,
    devices: Optional[Sequence[Any]] = None,
    calibration: Optional[Any] = None,
) -> CompiledModel:
    """The single public entry point: plan → prepare → jit, once.

    ``model``: a ``CNNModel`` (configs export them: ``vgg16.MODEL``,
    ``yolov3.TINY_MODEL``), an LM ``ModelConfig``, or a bare CNN layer
    table plus ``input_hw``.  ``options`` defaults to ``ExecutionOptions()``
    (pure-JAX impl, cost-model planning, persistent cache).  ``planner``
    and ``devices`` are runtime resources (not serialized): pass a shared
    Planner to pool caches across compilations, or an explicit device list
    to pin the batch mesh.  ``calibration`` is an optional fp32 batch used
    to calibrate int8 activation scales when ``options.dtype == 'int8'``
    (None = deterministic synthetic batch); ignored otherwise.
    """
    m = as_model(model, input_hw=input_hw, in_channels=in_channels, name=name)
    opts = options if options is not None else ExecutionOptions()
    if is_lm_config(m):
        return CompiledLM(m, params, opts)
    return CompiledCNN(
        m, params, opts, planner=planner, devices=devices,
        calibration=calibration,
    )


def load(
    path: str,
    model: Any,
    params: Any,
    *,
    input_hw: Optional[Tuple[int, int]] = None,
    in_channels: int = 3,
    planner=None,
    devices: Optional[Sequence[Any]] = None,
) -> CompiledModel:
    """Rebuild a CompiledModel from a ``save()`` artifact.

    The artifact stores the option surface and the model identity; the v4
    plan cache (``options.cache_path``) holds the tuning, so a warm load
    re-tunes nothing.  Raises ``ValueError`` when ``model`` does not match
    the saved identity (layer-table digest for CNNs, config name for LMs).
    """
    with open(path) as f:
        data = json.load(f)
    if data.get("format") != SAVE_FORMAT:
        raise ValueError(
            f"{path}: not a {SAVE_FORMAT} artifact "
            f"(format={data.get('format')!r})"
        )
    opts = ExecutionOptions.from_json(data.get("options", {}))
    saved = data.get("model", {})
    if data.get("kind") == "cnn" and input_hw is None and saved.get(
        "input_hw"
    ):
        # The artifact records the geometry; a bare layer table inherits it
        # rather than demanding it twice.  (A CNNModel descriptor keeps its
        # own — mismatches are rejected below, with guidance.)
        input_hw = tuple(saved["input_hw"])
        in_channels = int(saved.get("in_channels", in_channels))
    m = as_model(model, input_hw=input_hw, in_channels=in_channels)
    if data.get("kind") == "cnn":
        if not isinstance(m, CNNModel):
            raise ValueError(f"{path} was saved from a CNN; got {type(m)}")
        if saved.get("digest") and saved["digest"] != m.digest:
            raise ValueError(
                f"{path}: saved layer-table digest {saved['digest']} does "
                f"not match the provided model ({m.digest}) — same artifact, "
                f"different network"
            )
        # Geometry is identity too: plans are (H, W, C)-keyed, so a silent
        # mismatch would cold-retune everything instead of loading warm.
        if saved.get("input_hw") and tuple(saved["input_hw"]) != tuple(
            m.input_hw
        ):
            raise ValueError(
                f"{path}: saved at input_hw {tuple(saved['input_hw'])} but "
                f"the provided model targets {tuple(m.input_hw)} — pass "
                f"model.with_input_hw({tuple(saved['input_hw'])}) (or omit "
                f"input_hw to inherit the artifact's)"
            )
        if saved.get("in_channels") and int(saved["in_channels"]) != int(
            m.in_channels
        ):
            raise ValueError(
                f"{path}: saved with in_channels={saved['in_channels']}, "
                f"provided model has {m.in_channels}"
            )
    elif data.get("kind") == "lm" and getattr(m, "name", None) != saved.get("name"):
        raise ValueError(
            f"{path}: saved LM config {saved.get('name')!r} does not "
            f"match the provided {getattr(m, 'name', None)!r}"
        )
    return compile(m, params, opts, planner=planner, devices=devices)
