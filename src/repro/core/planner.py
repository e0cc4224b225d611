"""Per-layer convolution planner with a persistent autotuning cache.

The paper's central finding is that the convolution algorithm *and* its
blocking are per-layer, per-chip decisions (§VII + the follow-up co-design
paper): the same 3x3 layer wants Winograd at high resolution and im2col+GEMM
deep in the network, and the best BLIS-style block sizes shift with the
layer's GEMM dims and the chip's cache budget.  The repo's ingredients — the
selector (conv_spec/codesign), the VMEM cost model (vmem_model) and the
Pallas kernels — used to re-derive that decision on every ``conv2d`` call.

This module makes the co-design decision **once per (layer, shape, chip,
dtype)** and caches it:

  ConvPlan   frozen record of one decision: algorithm, impl, the GEMM-level
             ``BlockConfig`` the autotuner chose, the kernel-level block
             tuple the Pallas wrappers consume, and the predicted (or
             measured) seconds.
  Planner    resolves plans.  ``mode='cost'`` drives the vmem_model
             autotuner + roofline (fast, deterministic, no hardware);
             ``mode='measure'`` times candidate algorithms on the current
             backend and keeps the winner (the paper's empirical per-layer
             selection, §VII.A).  Plans persist in a JSON cache keyed by
             (spec, input shape, chip, dtype, impl, mode, VMEM budget) so a
             warm process — or the next process — re-tunes nothing.

Every downstream consumer threads through here: ``core.conv2d`` accepts a
plan (or a planner to look one up), ``kernels/conv_ops`` forwards the plan's
block sizes to the Pallas kernels, and the api facade (``repro.compile``)
resolves whole networks ahead of time (see benchmarks/e2e_cnn.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import warnings
from typing import Any, Dict, List, Optional, Tuple

from repro.core.conv_spec import ConvAlgorithm, ConvSpec, select_algorithm
from repro.core.vmem_model import BlockConfig, GemmShape, autotune_gemm
from repro.hw import V5E, ChipSpec
from repro.util import ceil_to

# v6 adds the "pipelines" section — stage partitions for layer-pipelined
# multi-chip execution (written by core/netplan.plan_pipeline), keyed by
# (network digest, n_stages, chip, dtype) so a warm process re-partitions
# nothing.  The "plans"/"networks" schemas are unchanged from v5, but the
# version gates the whole file, so v5 caches re-tune once.
# v5: plans carry a per-layer ``dtype`` — the *execution* precision the
# tuner resolved, which under an int8 request can legitimately be float32
# (the quantization policy keeps a layer fp32 when the modeled traffic win
# is below threshold or the Winograd error budget fails).  Traffic/footprint
# accounting became itemsize-aware (fp32 output writes under int8 operands),
# shifting modeled times and block tuples, so v4 caches are invalidated.
# v4 added the "networks" section — whole-network entries (written by
# core/netplan.plan_network) recording per-layer plans after network-level
# adjustment plus the inter-layer layout-elision decisions, so a warm
# process rebuilds a NetworkPlan with zero re-tunes.  v7: the VMEM model
# counts Mosaic's (8, 128) tile padding and internal scratch and the im2col
# kernel tiles rows (not whole images), so v6 kernel blocks are invalid.
# v8: an im2col plan may fold its taps into one GEMM contraction
# (``ConvPlan.taps_folded``), which changes the algorithm, blocks and
# layouts of narrow-input layers; a v7 plan or network entry would be read
# back as tap-wise, so v7 caches re-tune once.
PLAN_CACHE_VERSION = 8

# Default on-disk location (overridable per Planner and via environment).
DEFAULT_CACHE_PATH = os.environ.get(
    "REPRO_PLAN_CACHE", os.path.join(".cache", "conv_plans.json")
)

# ---------------------------------------------------------------------------
# Cache corruption recovery
#
# A cache file that fails ``json.load`` used to be silently treated as a
# cold start — and then *clobbered* by the next save, destroying the one
# artifact that could explain what went wrong (and every salvageable tune
# in it).  Instead: quarantine the corrupt bytes (rename to
# ``<path>.corrupt-<pid>``, never overwritten), warn once per path per
# process, and salvage every top-level "plans"/"networks" entry that still
# parses — a truncated tail loses the last few entries, not the whole tune
# history.

# Paths already warned about in this process (warn once, not per Planner).
_QUARANTINE_WARNED: set = set()


def _salvage_section(text: str, name: str) -> Dict[str, Any]:
    """Best-effort recovery of one top-level ``"name": {...}`` JSON section.

    The cache is written with ``indent=1, sort_keys=True``, so a top-level
    section opens as ``\\n "name": {`` — the indent-anchored pattern cannot
    collide with same-named keys nested inside opaque network entries.  From
    the opening brace, ``raw_decode`` walks ``"key": value`` pairs one at a
    time and keeps everything that parses; the first undecodable span (the
    truncation/garbage point) ends the walk.
    """
    anchor = f'\n "{name}": {{'
    start = text.find(anchor)
    if start >= 0:
        pos = start + len(anchor)
    else:
        # Fallback for caches not written by us (compact or re-indented).
        import re

        m = re.search(r'"%s"\s*:\s*\{' % re.escape(name), text)
        if m is None:
            return {}
        pos = m.end()
    decoder = json.JSONDecoder()
    out: Dict[str, Any] = {}
    n = len(text)
    while pos < n:
        while pos < n and text[pos] in " \t\r\n,":
            pos += 1
        if pos >= n or text[pos] == "}":
            break
        if text[pos] != '"':
            break
        try:
            key, end = decoder.raw_decode(text, pos)
            pos = end
            while pos < n and text[pos] in " \t\r\n":
                pos += 1
            if pos >= n or text[pos] != ":":
                break
            pos += 1
            while pos < n and text[pos] in " \t\r\n":
                pos += 1
            value, end = decoder.raw_decode(text, pos)
            pos = end
        except (json.JSONDecodeError, ValueError):
            break
        out[str(key)] = value
    return out


def salvage_cache_text(text: str) -> Dict[str, Any]:
    """Recover whatever top-level structure still parses from corrupt cache
    bytes: the version/chip scalars plus every intact "plans"/"networks"
    entry before the corruption point."""
    data: Dict[str, Any] = {}
    for scalar in ("version", "chip"):
        sec = _salvage_section_scalar(text, scalar)
        if sec is not None:
            data[scalar] = sec
    data["plans"] = _salvage_section(text, "plans")
    data["networks"] = _salvage_section(text, "networks")
    data["pipelines"] = _salvage_section(text, "pipelines")
    return data


def _salvage_section_scalar(text: str, name: str) -> Optional[Any]:
    import re

    m = re.search(r'"%s"\s*:\s*' % re.escape(name), text)
    if m is None:
        return None
    try:
        value, _ = json.JSONDecoder().raw_decode(text, m.end())
    except (json.JSONDecodeError, ValueError):
        return None
    return value


def _quarantine_cache(path: str, text: Optional[str]) -> Dict[str, Any]:
    """Move a corrupt cache aside and salvage what parses.

    The quarantined copy is never overwritten: if ``<path>.corrupt-<pid>``
    already exists (two corruption events in one process lifetime), a
    ``-N`` counter suffix picks a fresh name.  Returns the salvaged data
    (possibly empty) for the caller to merge.
    """
    dest = f"{path}.corrupt-{os.getpid()}"
    n = 1
    while os.path.exists(dest):
        dest = f"{path}.corrupt-{os.getpid()}-{n}"
        n += 1
    try:
        os.replace(path, dest)
    except OSError:
        dest = None     # the file vanished or is unmovable; still salvage
    salvaged = salvage_cache_text(text) if text else {}
    if (
        salvaged.get("plans")
        or salvaged.get("networks")
        or salvaged.get("pipelines")
    ):
        # sort_keys writes "version" last, so truncation usually eats it.
        # Entries still go through per-entry validation on load
        # (ConvPlan.from_json try/except; network records validate in
        # netplan) — a wrong-version survivor is dropped there, not here.
        salvaged.setdefault("version", PLAN_CACHE_VERSION)
    n_entries = (
        len(salvaged.get("plans", {}))
        + len(salvaged.get("networks", {}))
        + len(salvaged.get("pipelines", {}))
    )
    if path not in _QUARANTINE_WARNED:
        _QUARANTINE_WARNED.add(path)
        warnings.warn(
            f"plan cache {path!r} is corrupt"
            + (f"; quarantined to {dest!r}" if dest else "")
            + f"; salvaged {n_entries} entr{'y' if n_entries == 1 else 'ies'}"
            f" (cold re-tune covers the rest)",
            RuntimeWarning,
            stacklevel=3,
        )
    return salvaged


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """One resolved co-design decision for one conv layer at one shape.

    ``block`` is the autotuned GEMM-level BlockConfig (the paper's Table II
    block sizes, VMEM in the role of L2).  ``kernel_blocks`` is what the
    Pallas wrappers actually consume — (bm, bn, bk) for the direct GEMM and
    for tap folding, (toh, bc, bo) for the fused im2col kernel, (bt, bc, bo)
    for the Winograd pipeline.  ``taps_folded`` marks the im2col realization
    that builds the patch matrix once from the input's logical channels and
    runs one GEMM over K = kh*kw*C (kernels/im2col_gemm/ops.py), chosen for
    inputs narrower than a lane block.  ``predicted_s`` is modeled seconds
    in cost mode and measured wall seconds in measure mode (``source`` says
    which).
    """

    algorithm: ConvAlgorithm
    impl: str
    block: BlockConfig
    kernel_blocks: Tuple[int, int, int]
    predicted_s: float
    source: str = "cost_model"          # cost_model | measured
    fused_epilogue: bool = False        # bias+activation fused in the kernel
    winograd_fused: bool = False        # single-pass Winograd megakernel
                                        # (vs the 3-pass V/M-via-HBM pipeline)
    dtype: str = "float32"              # resolved execution precision; under
                                        # an int8 request this may stay
                                        # 'float32' (quantization policy)
    taps_folded: bool = False           # im2col as patch matrix + one GEMM

    def to_json(self) -> Dict[str, Any]:
        return {
            "algorithm": self.algorithm.value,
            "impl": self.impl,
            "block": [self.block.bm, self.block.bn, self.block.bk],
            "kernel_blocks": list(self.kernel_blocks),
            "predicted_s": self.predicted_s,
            "source": self.source,
            "fused_epilogue": self.fused_epilogue,
            "winograd_fused": self.winograd_fused,
            "dtype": self.dtype,
            "taps_folded": self.taps_folded,
        }

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> ConvPlan:
        return cls(
            algorithm=ConvAlgorithm(d["algorithm"]),
            impl=d["impl"],
            block=BlockConfig(*d["block"]),
            kernel_blocks=tuple(d["kernel_blocks"]),
            predicted_s=float(d["predicted_s"]),
            source=d.get("source", "cost_model"),
            fused_epilogue=bool(d.get("fused_epilogue", False)),
            winograd_fused=bool(d.get("winograd_fused", False)),
            dtype=d.get("dtype", "float32"),
            taps_folded=bool(d.get("taps_folded", False)),
        )


def plan_key(
    spec: ConvSpec,
    h: int,
    w: int,
    batch: int,
    chip: str,
    dtype: str,
    impl: str,
    mode: str = "cost",
    vmem_budget: Optional[int] = None,
    fuse_epilogue: bool = False,
    winograd_fused: Optional[bool] = None,
) -> str:
    """Canonical cache key: every field that changes the decision.

    ``winograd_fused`` is the planner's *policy* (None = auto: the tuner
    picks fused vs 3-pass; True/False = forced), not the resolved decision —
    an auto planner must never reuse a plan tuned under a forced policy.
    """
    return "|".join(
        [
            chip,
            dtype,
            impl,
            mode,
            f"e{int(fuse_epilogue)}",
            f"wf{'a' if winograd_fused is None else int(winograd_fused)}",
            f"v{vmem_budget if vmem_budget is not None else 0}",
            f"b{batch}",
            f"h{h}w{w}",
            f"ci{spec.in_channels}co{spec.out_channels}",
            f"k{spec.kh}x{spec.kw}",
            f"s{spec.stride[0]}x{spec.stride[1]}",
            f"p{spec.padding[0]}x{spec.padding[1]}",
            f"d{spec.dilation[0]}x{spec.dilation[1]}",
            spec.algorithm.value,
        ]
    )


def _dtype_name(dtype) -> str:
    """'float32' from jnp.float32 / np.dtype / str alike (no jax import)."""
    name = getattr(dtype, "__name__", None) or getattr(dtype, "name", None)
    return name if name is not None else str(dtype)


def _dtype_bytes(dtype) -> int:
    """Element size for planning, via the cost model's single itemsize map."""
    from repro.core.vmem_model import itemsize

    return itemsize(_dtype_name(dtype))


def _folded_bk(k: int, bk: int, lane: int) -> int:
    """The K block of a folded GEMM over ``k`` taps x channels, at most the
    autotuned ``bk``: all of ``k`` in one full-dimension block when it fits
    (no K pad: XLA writes the patch matrix in one pass), else the largest
    lane multiple dividing ``k`` rounded up to the lane width."""
    kp = ceil_to(k, lane)
    if kp <= bk:
        return k
    return max(d for d in range(lane, bk + 1, lane) if kp % d == 0)


def _eligible_algorithms(spec: ConvSpec) -> List[ConvAlgorithm]:
    """Candidate set for measure mode (forced specs collapse to one)."""
    if spec.algorithm not in (ConvAlgorithm.AUTO, ConvAlgorithm.AUTO_COST):
        return [spec.algorithm]
    if spec.kernel_size == (1, 1) and spec.stride == (1, 1):
        return [ConvAlgorithm.DIRECT, ConvAlgorithm.IM2COL_GEMM]
    if (
        spec.kernel_size == (3, 3)
        and spec.stride == (1, 1)
        and spec.dilation == (1, 1)
    ):
        return [ConvAlgorithm.WINOGRAD, ConvAlgorithm.IM2COL_GEMM]
    return [ConvAlgorithm.IM2COL_GEMM]


class Planner:
    """Resolves and caches ConvPlans.

    Lookup order: in-memory dict -> persistent JSON cache -> tune (cost model
    or microbenchmark) and write back.  ``stats`` counts ``hits`` (memory or
    disk) and ``tunes`` (cache misses that ran the autotuner); a warm cache
    means ``tunes == 0``.
    """

    def __init__(
        self,
        hw: ChipSpec = V5E,
        mode: str = "cost",
        impl: str = "jax",
        cache_path: Optional[str] = DEFAULT_CACHE_PATH,
        vmem_budget: Optional[int] = None,
        measure_reps: int = 3,
        autosave: bool = True,
        fuse_epilogue: bool = False,
        winograd_fused: Optional[bool] = None,
    ):
        if mode not in ("cost", "measure"):
            raise ValueError(f"mode must be 'cost' or 'measure', got {mode!r}")
        self.hw = hw
        self.mode = mode
        self.impl = impl
        # Plans record the fusion decision so consumers (cnn_forward) apply
        # the epilogue inside the kernel exactly when the plan was tuned
        # that way; keyed separately in the cache.
        self.fuse_epilogue = fuse_epilogue
        # Winograd realization policy: None lets the tuner choose between
        # the single-pass fused megakernel and the 3-pass pipeline (cost
        # mode compares modeled traffic; measure mode on the pallas impl
        # times both); True/False forces one realization.
        self.winograd_fused = winograd_fused
        self.cache_path = cache_path
        self.vmem_budget = vmem_budget if vmem_budget is not None else hw.vmem_bytes
        self.measure_reps = measure_reps
        # autosave=False defers persistence to an explicit save() — use for
        # bulk planning (plan_layers over a deep net) to avoid a locked
        # read-merge-rewrite of the cache file on every miss.
        self.autosave = autosave
        self._dirty = False
        self._plans: Dict[str, ConvPlan] = {}
        # Whole-network entries (core/netplan.plan_network): opaque JSON
        # records keyed by the caller's network key.  Persisted alongside
        # the per-layer plans in the same versioned cache file.
        self._networks: Dict[str, Any] = {}
        # Stage-partition entries (core/netplan.plan_pipeline): opaque JSON
        # records keyed by (network digest, n_stages, chip, dtype) — a warm
        # load re-partitions nothing.
        self._pipelines: Dict[str, Any] = {}
        self.network_hits = 0
        self.pipeline_hits = 0
        self.stats = {"hits": 0, "tunes": 0}
        if cache_path and os.path.exists(cache_path):
            self._load()

    # -- persistence ---------------------------------------------------------

    def _load(self) -> None:
        try:
            # errors="replace": corrupt bytes may not even be UTF-8; decode
            # what we can and let the JSON layer (or salvage) sort it out.
            with open(self.cache_path, errors="replace") as f:
                text = f.read()
        except OSError:
            return  # unreadable cache is a cold start, not an error
        try:
            data = json.loads(text)
            if not isinstance(data, dict):
                raise json.JSONDecodeError("top level is not an object",
                                           text, 0)
        except json.JSONDecodeError:
            # Corrupt cache: quarantine the bytes (never clobber them on
            # the next save) and salvage every entry that still parses.
            data = _quarantine_cache(self.cache_path, text)
        if data.get("version") != PLAN_CACHE_VERSION:
            return
        for key, d in data.get("plans", {}).items():
            try:
                self._plans[key] = ConvPlan.from_json(d)
            except (KeyError, ValueError, TypeError):
                continue
        nets = data.get("networks", {})
        if isinstance(nets, dict):
            self._networks.update(nets)
        pipes = data.get("pipelines", {})
        if isinstance(pipes, dict):
            self._pipelines.update(pipes)

    def save(self) -> None:
        """Atomically write the cache (tmp file + rename).

        Merges with whatever is on disk first (ours wins on key collision) so
        concurrent planners tuning different layers converge to the union
        instead of clobbering each other's entries; a sidecar flock makes the
        read-merge-write sequence race-free where flock exists.
        """
        if not self.cache_path:
            return
        d = os.path.dirname(self.cache_path) or "."
        os.makedirs(d, exist_ok=True)
        lock = open(self.cache_path + ".lock", "w")  # noqa: SIM115  (closed in finally)
        try:
            with contextlib.suppress(ImportError):
                # non-POSIX: best-effort, merge still helps
                import fcntl

                fcntl.flock(lock, fcntl.LOCK_EX)
            plans: Dict[str, Any] = {}
            networks: Dict[str, Any] = {}
            pipelines: Dict[str, Any] = {}
            if os.path.exists(self.cache_path):
                disk: Dict[str, Any] = {}
                with contextlib.suppress(OSError):
                    with open(self.cache_path, errors="replace") as f:
                        disk_text = f.read()
                    try:
                        disk = json.loads(disk_text)
                        if not isinstance(disk, dict):
                            raise json.JSONDecodeError(
                                "top level is not an object", disk_text, 0
                            )
                    except json.JSONDecodeError:
                        # A concurrent writer crashed mid-save (or the file
                        # rotted): quarantine + salvage, same as _load —
                        # the merge keeps every entry that still parses
                        # instead of silently discarding the disk state.
                        disk = _quarantine_cache(self.cache_path, disk_text)
                if disk.get("version") == PLAN_CACHE_VERSION:
                    p = disk.get("plans", {})
                    nw = disk.get("networks", {})
                    pp = disk.get("pipelines", {})
                    if isinstance(p, dict):
                        plans.update(p)
                    if isinstance(nw, dict):
                        networks.update(nw)
                    if isinstance(pp, dict):
                        pipelines.update(pp)
            plans.update({k: p.to_json() for k, p in self._plans.items()})
            networks.update(self._networks)
            pipelines.update(self._pipelines)
            payload = {
                "version": PLAN_CACHE_VERSION,
                "chip": self.hw.name,
                "plans": plans,
                "networks": networks,
                "pipelines": pipelines,
            }
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f, indent=1, sort_keys=True)
                os.replace(tmp, self.cache_path)
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise
        finally:
            lock.close()
        self._dirty = False

    def __len__(self) -> int:
        return len(self._plans)

    # -- network-level entries (consumed by core/netplan) --------------------

    def network_entry(self, key: str) -> Optional[Dict[str, Any]]:
        """The stored whole-network record for ``key``, or None (cold).

        ``network_hits`` is NOT counted here: the consumer
        (core/netplan.plan_network) increments it only after the entry
        validates and reconstructs — a corrupt record that falls back to
        replanning must not report warm persistence.
        """
        return self._networks.get(key)

    def put_network_entry(self, key: str, entry: Dict[str, Any]) -> None:
        """Store a whole-network record (must be plain JSON-able data)."""
        self._networks[key] = entry
        if self.autosave:
            self.save()
        else:
            self._dirty = True

    # -- pipeline-partition entries (consumed by core/netplan) ---------------

    def pipeline_entry(self, key: str) -> Optional[Dict[str, Any]]:
        """The stored stage-partition record for ``key``, or None (cold).

        Like ``network_entry``, ``pipeline_hits`` is incremented by the
        consumer (core/netplan.plan_pipeline) only after the entry validates
        — a corrupt record that falls back to re-partitioning must not
        report warm persistence.
        """
        return self._pipelines.get(key)

    def put_pipeline_entry(self, key: str, entry: Dict[str, Any]) -> None:
        """Store a stage-partition record (must be plain JSON-able data)."""
        self._pipelines[key] = entry
        if self.autosave:
            self.save()
        else:
            self._dirty = True

    # -- planning ------------------------------------------------------------

    def plan(
        self,
        spec: ConvSpec,
        h: int,
        w: int,
        batch: int = 1,
        dtype: Any = "float32",
    ) -> ConvPlan:
        """The plan for one layer at one input shape; tunes on first miss."""
        key = plan_key(
            spec, h, w, batch, self.hw.name, _dtype_name(dtype), self.impl,
            self.mode, self.vmem_budget, self.fuse_epilogue,
            self.winograd_fused,
        )
        cached = self._plans.get(key)
        if cached is not None:
            self.stats["hits"] += 1
            return cached
        self.stats["tunes"] += 1
        if _dtype_name(dtype) == "int8":
            # Quantization is a *policy* decision, not a measurement: the
            # accuracy budget and the traffic threshold come from the model
            # either way, so measure mode delegates too.
            plan = self._tune_int8(spec, h, w, batch)
        elif self.mode == "measure":
            plan = self._tune_measured(spec, h, w, batch, dtype)
        else:
            plan = self._tune_cost_model(spec, h, w, batch, dtype)
        self._plans[key] = plan
        if self.autosave:
            self.save()
        else:
            self._dirty = True
        return plan

    def _resolve_blocks(
        self,
        spec: ConvSpec,
        algo: ConvAlgorithm,
        h: int,
        w: int,
        batch: int,
        dtype_bytes: int,
        winograd_fused: bool = True,
        taps_folded: bool = False,
    ) -> Tuple[BlockConfig, Tuple[int, int, int]]:
        """(GEMM BlockConfig, kernel block tuple) for one algorithm choice.

        The BlockConfig is autotuned on the GEMM exactly as the kernel runs
        it (direct: (B*OH*OW, O, C); im2col: K = kh*kw*C; winograd: the
        per-position tuple multiply (tiles, O, C)).  Winograd kernel blocks
        (bt, bc, bo) are autotuned per realization — the fused megakernel's
        M-accumulator scratch (8*8*bt*bo*4 bytes) is budgeted alongside the
        tile and weight blocks, so the fused and 3-pass variants can land on
        different tuples.  Tap folding runs the GEMM kernel itself, on the
        same BlockConfig, with bk the folded K or a divisor of it.
        """
        oh, ow = spec.out_hw(h, w)
        cin, cout = spec.in_channels, spec.out_channels
        if algo is ConvAlgorithm.WINOGRAD:
            tiles = batch * -(-oh // 6) * -(-ow // 6)
            shape = GemmShape(tiles, cout, cin)
        elif algo is ConvAlgorithm.DIRECT:
            shape = GemmShape(batch * oh * ow, cout, cin)
        else:
            shape = GemmShape(batch * oh * ow, cout, spec.kh * spec.kw * cin)
        cfg, _ = autotune_gemm(shape, self.hw, self.vmem_budget, dtype_bytes)
        # Clamp to the padded problem so tiny layers don't over-pad.
        cfg = BlockConfig(
            min(cfg.bm, ceil_to(shape.m, self.hw.sublanes)),
            min(cfg.bn, ceil_to(shape.n, self.hw.lane_width)),
            min(cfg.bk, ceil_to(shape.k, self.hw.lane_width)),
        )
        if algo is ConvAlgorithm.WINOGRAD:
            from repro.core.vmem_model import autotune_winograd_blocks

            kernel_blocks, _ = autotune_winograd_blocks(
                shape.m, cin, cout, self.hw, self.vmem_budget, dtype_bytes,
                fused=winograd_fused,
            )
        elif taps_folded:
            kernel_blocks = (cfg.bm, cfg.bn,
                             _folded_bk(shape.k, cfg.bk, self.hw.lane_width))
        elif algo is ConvAlgorithm.IM2COL_GEMM:
            from repro.kernels.im2col_gemm.ops import pick_blocks

            kernel_blocks = pick_blocks(
                oh, ow, cin, cout, spec.kh, spec.kw, *spec.stride,
                dtype_bytes, vmem_budget=self.vmem_budget,
            )
        else:
            kernel_blocks = (cfg.bm, cfg.bn, cfg.bk)
        return cfg, kernel_blocks

    def _tune_cost_model(
        self, spec: ConvSpec, h: int, w: int, batch: int, dtype
    ) -> ConvPlan:
        """Analytic decision: codesign routing + vmem_model block autotune.

        On the Pallas path, a layer whose input is narrower than a lane
        block is priced once more at the work each realization really does
        (``codesign.predict_narrow_conv_time``): the algorithm chosen above
        (the fused im2col kernel's taps, or Winograd unless the spec forces
        it, over lane-padded channels) against folding the taps into one
        patch-matrix GEMM, and the cheaper runs.  Other layers' decisions
        and prices are those of ``predict_conv_time`` alone.
        """
        from repro.core.codesign import (
            narrow_input,
            predict_conv_time,
            predict_narrow_conv_time,
            select_algorithm_by_cost,
        )

        dtype_bytes = _dtype_bytes(dtype)
        if spec.algorithm in (ConvAlgorithm.AUTO, ConvAlgorithm.AUTO_COST):
            # Selection must model the Winograd realization this planner's
            # policy would actually run: a forced-3-pass planner competes
            # im2col against the 3-pass pipeline, not the megakernel.
            # Batch matters too: the im2col-vs-winograd crossover shifts as
            # activation traffic amortizes the weight term.
            algo = select_algorithm_by_cost(
                spec, h, w, self.hw, dtype_bytes,
                winograd_fused=(self.winograd_fused
                                if self.winograd_fused is not None else True),
                batch=batch,
            )
        else:
            algo = select_algorithm(spec)
        wf = False
        if algo is ConvAlgorithm.WINOGRAD:
            if self.winograd_fused is None:
                # Auto: the megakernel wins whenever its eliminated V/M
                # round-trips beat the 3-pass pipeline's modeled time.
                wf = predict_conv_time(
                    spec, h, w, algo, self.hw, dtype_bytes, batch,
                    winograd_fused=True,
                ) <= predict_conv_time(
                    spec, h, w, algo, self.hw, dtype_bytes, batch,
                    winograd_fused=False,
                )
            else:
                wf = self.winograd_fused
        foldable = algo is ConvAlgorithm.IM2COL_GEMM or (
            algo is ConvAlgorithm.WINOGRAD and spec.algorithm in
            (ConvAlgorithm.AUTO, ConvAlgorithm.AUTO_COST))
        folded = False
        if (self.impl == "pallas" and foldable
                and narrow_input(spec, self.hw)):
            t_fold = predict_narrow_conv_time(
                spec, h, w, ConvAlgorithm.IM2COL_GEMM, self.hw, dtype_bytes,
                batch, taps_folded=True,
            )
            folded = t_fold < predict_narrow_conv_time(
                spec, h, w, algo, self.hw, dtype_bytes, batch,
                winograd_fused=wf,
            )
            if folded:
                algo, wf = ConvAlgorithm.IM2COL_GEMM, False
        cfg, kernel_blocks = self._resolve_blocks(
            spec, algo, h, w, batch, dtype_bytes, winograd_fused=wf,
            taps_folded=folded,
        )
        t = t_fold if folded else predict_conv_time(
            spec, h, w, algo, self.hw, dtype_bytes, batch, winograd_fused=wf
        )
        return ConvPlan(
            algorithm=algo,
            impl=self.impl,
            block=cfg,
            kernel_blocks=kernel_blocks,
            predicted_s=t,
            source="cost_model",
            fused_epilogue=self.fuse_epilogue,
            winograd_fused=wf,
            dtype=_dtype_name(dtype),
            taps_folded=folded,
        )

    def _tune_int8(self, spec: ConvSpec, h: int, w: int, batch: int) -> ConvPlan:
        """Per-layer int8-vs-fp32 decision under an int8 request.

        A layer quantizes only when both policy gates pass (core/quant.py):

          1. the modeled int8 im2col/direct GEMM HBM bytes are at most half
             its fp32 bytes (``int8_worthwhile``) — otherwise the bytes win
             does not pay for the quantization noise (e.g. the cin=3 stem);
          2. the int8 candidate's modeled time actually beats the fp32 plan
             that would otherwise run — an fp32 Winograd layer genuinely
             competes with int8 im2col (the 64/9x weight-traffic inflation
             vs the 4x operand shrink), so the roofline decides.

        Winograd itself is never an int8 candidate unless the F(6, 3)
        transform-stage error budget holds (``winograd_int8_budget_ok`` —
        it does not), so an int8 3x3 layer runs im2col+GEMM.  The returned
        plan's ``dtype`` records the resolved precision; the executor
        quantizes exactly the layers whose plan says 'int8'.
        """
        from repro.core.codesign import predict_conv_time
        from repro.core.quant import int8_worthwhile, winograd_int8_budget_ok

        fp32_plan = self._tune_cost_model(spec, h, w, batch, "float32")
        if not int8_worthwhile(spec, h, w, batch):
            return fp32_plan
        if spec.kernel_size == (1, 1) and spec.stride == (1, 1):
            algo = ConvAlgorithm.DIRECT
        elif (
            fp32_plan.algorithm is ConvAlgorithm.WINOGRAD
            and winograd_int8_budget_ok()
        ):
            algo = ConvAlgorithm.WINOGRAD
        else:
            algo = ConvAlgorithm.IM2COL_GEMM
        wf = fp32_plan.winograd_fused if algo is ConvAlgorithm.WINOGRAD else False
        t_int8 = predict_conv_time(
            spec, h, w, algo, self.hw, 1, batch, winograd_fused=wf
        )
        if t_int8 >= fp32_plan.predicted_s:
            return fp32_plan
        cfg, kernel_blocks = self._resolve_blocks(
            spec, algo, h, w, batch, 1, winograd_fused=wf
        )
        return ConvPlan(
            algorithm=algo,
            impl=self.impl,
            block=cfg,
            kernel_blocks=kernel_blocks,
            predicted_s=t_int8,
            source="cost_model",
            fused_epilogue=self.fuse_epilogue,
            winograd_fused=wf,
            dtype="int8",
        )

    def _tune_measured(
        self, spec: ConvSpec, h: int, w: int, batch: int, dtype
    ) -> ConvPlan:
        """Empirical decision: time each eligible algorithm, keep the winner.

        This is the paper's §VII.A methodology (measure both, pick per layer)
        run on whatever backend is active; on CPU it times the jitted pure-JAX
        paths, on TPU the Pallas kernels when ``impl='pallas'``.
        """
        import time

        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.core.conv2d import conv2d

        dtype_bytes = _dtype_bytes(dtype)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(batch, h, w, spec.in_channels)), dtype)
        wts = jnp.asarray(
            rng.normal(size=(spec.kh, spec.kw, spec.in_channels, spec.out_channels))
            * 0.05,
            dtype,
        )
        # A fuse_epilogue planner stamps plans that will replay with the
        # bias+activation kernel variants — time those same variants, not
        # the bias-less ones (the costs differ per output-stage shape).
        epi = None
        if self.fuse_epilogue:
            from repro.core.conv_spec import Epilogue

            epi = Epilogue(
                bias=jnp.asarray(
                    rng.normal(size=(spec.out_channels,)), dtype
                ),
                activation="relu",
            )
        from repro.core.codesign import narrow_input

        best: Tuple[Optional[ConvPlan], float] = (None, float("inf"))
        candidates = []
        for algo in _eligible_algorithms(spec):
            if algo is ConvAlgorithm.WINOGRAD:
                if self.winograd_fused is not None:
                    candidates.append((algo, self.winograd_fused, False))
                elif self.impl == "pallas":
                    # Both realizations exist only on the Pallas path: time
                    # the fused megakernel against the 3-pass pipeline.
                    candidates += [(algo, True, False), (algo, False, False)]
                else:
                    candidates.append((algo, True, False))
            else:
                candidates.append((algo, False, False))
                if (algo is ConvAlgorithm.IM2COL_GEMM and self.impl == "pallas"
                        and narrow_input(spec, self.hw)):
                    # Tap folding exists only on the Pallas path.
                    candidates.append((algo, False, True))
        for algo, wf, tf in candidates:
            cfg, kernel_blocks = self._resolve_blocks(
                spec, algo, h, w, batch, dtype_bytes, winograd_fused=wf,
                taps_folded=tf,
            )
            candidate = ConvPlan(
                algorithm=algo,
                impl=self.impl,
                block=cfg,
                kernel_blocks=kernel_blocks,
                predicted_s=0.0,
                source="measured",
                fused_epilogue=self.fuse_epilogue,
                winograd_fused=wf,
                dtype=_dtype_name(dtype),
                taps_folded=tf,
            )
            fn = jax.jit(
                lambda a, b, p=candidate: conv2d(a, b, spec, plan=p,
                                                 epilogue=epi)
            )
            try:
                jax.block_until_ready(fn(x, wts))  # compile + warm
                times = []
                for _ in range(self.measure_reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(x, wts))
                    times.append(time.perf_counter() - t0)
                t = float(np.median(times))
            except Exception:
                continue  # an algorithm that fails to run is never the plan
            if t < best[1]:
                best = (dataclasses.replace(candidate, predicted_s=t), t)
        if best[0] is None:
            # Every candidate failed (e.g. no backend): fall back to the model.
            return self._tune_cost_model(spec, h, w, batch, dtype)
        return best[0]
