"""ExecutionOptions: the one co-design surface of the `repro.api` facade.

The paper's argument is that algorithm choice, blocking, and hardware
parameters must be decided *together*; before this facade those decisions
were scattered across ~10 uncoordinated kwargs (``conv2d``'s routing
arguments, the planner's policy fields, the executor's interpret/devices,
the serving engine's bucket ladder).  ``ExecutionOptions`` is the single
frozen record of every knob that changes how a compiled model executes —
hashable, JSON round-trippable (``save()``/``load()`` ride it), and the
only thing ``repro.compile`` needs besides the model and its params.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from repro.core.planner import DEFAULT_CACHE_PATH, _dtype_name

_IMPLS = ("jax", "pallas")
_MODES = ("cost", "measure")
_DTYPES = ("float32", "bfloat16", "float16", "int8")
_VALIDATE = ("off", "plan", "kernel", "full")
_FALLBACK = ("ladder", "off")


@dataclasses.dataclass(frozen=True)
class ExecutionOptions:
    """Every execution decision for one compiled model, in one place.

    Planning policy (forwarded to the ``Planner`` / v4 plan cache):
      impl            'jax' (pure jnp) or 'pallas' (TPU kernels).
      mode            'cost' (analytic VMEM model) or 'measure' (time each
                      eligible algorithm on the live backend).
      cache_path      persistent v4 plan-cache JSON (None = no persistence).
      vmem_budget     VMEM bytes for block autotuning (None = chip default).
      fuse_epilogue   bias + activation fused into the kernels' output stage.

    Execution:
      interpret       run Pallas kernels in interpret mode (None = auto:
                      interpret off-TPU).
      pretransform    apply the offline Winograd weight transform during
                      parameter preparation (paper §VII.A excludes it from
                      timing); the flag is carried explicitly — never
                      sniffed from weight shapes.
      batch           the batch size compiled eagerly by ``compile``.
      buckets         the serving bucket ladder (``CompiledModel.serve``).
      shard_batch     shard the batch over all visible devices when the
                      batch divides the device count (shard_map mesh).
      pipeline_stages layer-pipelined multi-chip execution: split the
                      network into this many contiguous stages (0 = off,
                      the default).  The partition is cost-balanced from
                      the planner's per-layer predicted seconds
                      (core/netplan.partition_network), cached in the v6
                      plan cache, and executed GPipe-style over a 1-D
                      'stage' device mesh — each stage's devices hold only
                      that stage's prepared params.  Needs at least
                      ``pipeline_stages`` visible devices at executor build
                      time.
      microbatch      microbatch count for the pipeline schedule: 'auto'
                      (default — the cost-model chooser minimizing modeled
                      latency = per-tick max-stage time summed over the
                      fill/steady/drain ticks plus per-tick overhead) or a
                      fixed positive count that must divide the batch.
                      Ignored while ``pipeline_stages`` is 0.
      dtype           execution dtype name ('float32', 'bfloat16', 'int8').
                      'int8' requests quantized inference: the planner
                      resolves it per layer (a layer where int8 does not
                      win stays fp32), weights are quantized offline with
                      per-output-channel scales, and inputs stay fp32
                      (see ``input_dtype``) — activations are quantized at
                      each int8 layer's entry.
      validate        compile-time plan verification (repro.analysis):
                      'off' (default), 'plan' (layout decisions + modeled
                      VMEM footprints under budget, no tracing), 'kernel'
                      (trace the forward and prove the kernel-interior
                      properties of every pallas_call — write-disjoint
                      output index maps, block windows inside operand
                      bounds, accumulator init/flush guards, int8 overflow
                      certification), or 'full' (everything: the plan
                      byte passes — structure / VMEM / traffic / elision /
                      dtype — plus the kernel-interior suite).  Any error
                      finding raises ``PlanVerificationError`` before the
                      executor can run.

    Serving resilience (serving/resilience.py; all inert at the defaults):
      max_queue       bounded admission: ``submit`` raises a typed
                      ``Backpressure`` once the queue holds this many
                      requests (None = unbounded, the pre-resilience
                      behavior).
      default_deadline_s
                      default per-request latency budget in seconds; an
                      expired request is evicted with a ``DeadlineExceeded``
                      result instead of being served stale.  Per-request
                      ``submit(deadline_s=...)`` overrides.  None = no
                      deadline.
      fallback        'ladder' routes executor failures down the degradation
                      ladder (pallas → pallas-interpret → pure-XLA fp32
                      reference; jit → eager decode for LMs) behind a
                      per-bucket circuit breaker; 'off' fails requests on
                      the first unrecovered fault instead of degrading.
      retries         transient-failure retries per ladder rung before
                      descending (>= 0).
    """

    impl: str = "jax"
    mode: str = "cost"
    interpret: Optional[bool] = None
    cache_path: Optional[str] = DEFAULT_CACHE_PATH
    vmem_budget: Optional[int] = None
    fuse_epilogue: bool = True
    pretransform: bool = True
    batch: int = 1
    buckets: Tuple[int, ...] = (1, 4, 8)
    shard_batch: bool = True
    pipeline_stages: int = 0
    microbatch: Any = "auto"            # 'auto' | positive int
    dtype: str = "float32"
    validate: str = "off"
    max_queue: Optional[int] = None
    default_deadline_s: Optional[float] = None
    fallback: str = "ladder"
    retries: int = 1

    def __post_init__(self) -> None:
        if self.validate not in _VALIDATE:
            raise ValueError(
                f"validate must be one of {_VALIDATE}, got {self.validate!r}"
            )
        if self.impl not in _IMPLS:
            raise ValueError(f"impl must be one of {_IMPLS}, got {self.impl!r}")
        if self.mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES}, got {self.mode!r}")
        if self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {self.batch}")
        if not self.buckets or any(int(b) <= 0 for b in self.buckets):
            raise ValueError(
                f"buckets must be a non-empty tuple of positive batch "
                f"sizes, got {self.buckets!r}"
            )
        # Normalize: buckets sorted+deduped, dtype to its canonical name —
        # options that mean the same thing compare (and hash) equal.
        object.__setattr__(
            self, "buckets", tuple(sorted({int(b) for b in self.buckets}))
        )
        object.__setattr__(self, "dtype", _dtype_name(self.dtype))
        if self.dtype not in _DTYPES:
            raise ValueError(
                f"dtype must be one of {_DTYPES}, got {self.dtype!r}"
            )
        if self.fallback not in _FALLBACK:
            raise ValueError(
                f"fallback must be one of {_FALLBACK}, got {self.fallback!r}"
            )
        if self.retries < 0:
            raise ValueError(f"retries must be >= 0, got {self.retries}")
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                f"max_queue must be None or >= 1, got {self.max_queue}"
            )
        if self.pipeline_stages < 0 or self.pipeline_stages == 1:
            raise ValueError(
                f"pipeline_stages must be 0 (off) or >= 2, got "
                f"{self.pipeline_stages}"
            )
        if self.microbatch != "auto" and (
            not isinstance(self.microbatch, int) or self.microbatch < 1
        ):
            raise ValueError(
                f"microbatch must be 'auto' or a positive int, got "
                f"{self.microbatch!r}"
            )
        if self.default_deadline_s is not None and self.default_deadline_s <= 0:
            raise ValueError(
                f"default_deadline_s must be None or > 0, got "
                f"{self.default_deadline_s}"
            )

    @property
    def input_dtype(self) -> str:
        """The dtype ``run()``/serving cast incoming batches to.

        int8 is an *internal* execution precision: callers hand in fp32
        images and quantization happens per layer against calibrated
        scales, so the input-facing dtype stays float32.  Casting the
        input batch itself to int8 would destroy it.
        """
        return "float32" if self.dtype == "int8" else self.dtype

    def replace(self, **changes: Any) -> ExecutionOptions:
        return dataclasses.replace(self, **changes)

    # -- persistence (CompiledModel.save()/load() ride this) -----------------

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["buckets"] = list(self.buckets)
        return d

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> ExecutionOptions:
        known = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in d.items() if k in known}
        if "buckets" in kwargs:
            kwargs["buckets"] = tuple(kwargs["buckets"])
        return cls(**kwargs)

    # -- the planner this option set implies ----------------------------------

    def make_planner(self):
        """A Planner carrying exactly this option set's policy fields.

        ``autosave=False``: the facade persists once per planning burst
        (one merge+write for a whole network / bucket ladder), not once per
        layer miss.
        """
        from repro.core.planner import Planner

        return Planner(
            mode=self.mode,
            impl=self.impl,
            cache_path=self.cache_path,
            vmem_budget=self.vmem_budget,
            autosave=False,
            fuse_epilogue=self.fuse_epilogue,
        )
