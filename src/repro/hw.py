"""Target-hardware constants (TPU v5e) used by the co-design model, the
roofline analysis, and the benchmarks.

v5e is the target chip.  The cost model plans against these constants
(playing the role gem5 plays in the paper); a run on a real device looks
its chip up by ``device_kind`` in ``CHIPS`` (``chip_for``), and a device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str = "tpu_v5e"
    peak_flops_bf16: float = 197e12      # FLOP/s per chip (given)
    peak_flops_fp32: float = 98.5e12     # assumed 1/2 bf16 (no published figure)
    peak_flops_int8: float = 394e12      # assumed 2x bf16 (published: 393e12)
    hbm_bandwidth: float = 819e9         # B/s per chip (given)
    hbm_bytes: int = 16 * 1024**3        # 16 GiB HBM
    ici_link_bandwidth: float = 50e9     # B/s per link (given)
    ici_links: int = 4                   # 2D torus on v5e: 4 links/chip
    vmem_bytes: int = 16 * 1024**2       # ~16 MiB VMEM per core (sweepable)
    mxu_dim: int = 128                   # systolic array is 128x128
    sublanes: int = 8                    # VREG second-minor granularity
    lane_width: int = 128                # VREG minor (lane) granularity
    grid_step_overhead_s: float = 0.3e-6 # per-grid-step issue/DMA overhead


V5E = ChipSpec()

#: Chips a device run may report against, keyed by ``jax.Device.device_kind``.
#: Published for v5e (Google Cloud, "TPU v5e"): 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB HBM at 819 GB/s.  The fp32 and int8 peaks in ``ChipSpec`` are
#: the cost model's ratios to bf16, not those figures: use them to rank
#: plans, never as a roofline a chip measurement is reported against.
CHIPS = {"TPU v5 lite": V5E}


def chip_for(device) -> ChipSpec:
    """The ``ChipSpec`` of a live TPU device; raises for anything else."""
    if device.platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's first device is {device.platform!r} "
            f"({device.device_kind!r})"
        )
    try:
        return CHIPS[device.device_kind]
    except KeyError:
        raise RuntimeError(
            f"unknown TPU kind {device.device_kind!r}: add its peaks to "
            f"repro.hw.CHIPS (known: {sorted(CHIPS)})"
        ) from None


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A logical device mesh + its physical wiring for collective modeling."""

    shape: tuple
    axes: tuple

    @property
    def num_chips(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshSpec(shape=(16, 16), axes=("data", "model"))
MULTI_POD = MeshSpec(shape=(2, 16, 16), axes=("pod", "data", "model"))
