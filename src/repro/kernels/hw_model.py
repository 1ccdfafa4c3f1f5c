"""The hardware model: one set of machine constants for every consumer.

``benchmarks/roofline.py`` (arch-level roofline terms) and
``kernels/variants.py`` (the per-kernel analytical cost model) must agree
on what the machine can do — peak FLOP rate, HBM bandwidth, VMEM
capacity, MXU/VPU geometry — or a kernel the cost model calls
compute-bound would look memory-bound in the roofline table.  Both read
from here; nothing else in the repo hard-codes a TFLOP/s.

Rows are keyed by the ``device_kind`` JAX reports, and each names its
source.  :func:`hardware_for` picks the row of the device in use: a TPU
whose kind has no row raises rather than borrowing another chip's
numbers.  On the CPU the Pallas kernels run in interpret mode as a
stand-in for the TPU they are tiled for, so selection prices them with
the v5e row, by name — those are the v5e's published numbers, never a
measurement of the CPU.

A row describes a chip:

* one MXU of 128x128 ALUs — matmul operands want every contracting /
  non-contracting tile dimension at (a multiple of) 128;
* a VPU of (8, 128) lanes for elementwise work;
* the scoped VMEM a kernel may use, shared by every in-flight block and
  the pipeline's double buffers — the cost model's *validity* constraint;
* per-``pallas_call`` launch overhead, the constant that makes the
  reference implementation win for degenerate shapes (the launch,
  dispatch and grid-step constants are modelled, not measured).

``HardwareModel`` is a frozen dataclass so a test can carry its own
instance (``with_vmem``).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict

import jax


@dataclass(frozen=True)
class HardwareModel:
    """Per-chip machine constants consumed by cost model + roofline."""

    kind: str                        # jax.Device.device_kind
    source: str                      # where the published numbers come from
    peak_flops: float                # bf16 MXU FLOP/s
    hbm_bw: float                    # HBM bytes/s
    hbm_bytes: int                   # HBM capacity
    link_bw: float                   # ICI bytes/s per link
    vpu_flops: float = 12.3e12       # f32 elementwise FLOP/s (8x128 VPU)
    vmem_bytes: int = 16 * 2**20     # scoped VMEM a kernel may use
    mxu_dim: int = 128               # systolic array edge
    vpu_sublanes: int = 8            # VREG is (8, 128)
    vpu_lanes: int = 128
    # fixed cost of entering a pallas_call (grid setup, prologue DMAs);
    # the reference implementation instead pays one fused-XLA dispatch
    kernel_launch_s: float = 2e-6
    xla_dispatch_s: float = 5e-7
    # per-grid-step sequencing overhead (scalar core bookkeeping + DMA
    # issue between steps that the pipeline cannot fully hide)
    grid_step_s: float = 5e-9

    def with_vmem(self, vmem_bytes: int) -> "HardwareModel":
        """The same chip with a different VMEM budget (tests/property
        checks shrink it to watch the valid variant set contract)."""
        return replace(self, vmem_bytes=vmem_bytes)


TPU_V5E = HardwareModel(
    kind="TPU v5 lite",
    source=("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "16 GiB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links"),
    peak_flops=197e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 2**30,
    link_bw=50e9,
)

HARDWARE: Dict[str, HardwareModel] = {TPU_V5E.kind: TPU_V5E}


def hardware_for(device=None) -> HardwareModel:
    """The row for ``device`` (default: JAX's first device).

    CPU: the v5e row (interpret mode stands in for it).  TPU: the row of
    its ``device_kind``; an unknown kind raises.  Any other platform
    raises — the Pallas kernels target TPUs only."""
    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return TPU_V5E
    if device.platform == "tpu":
        try:
            return HARDWARE[device.device_kind]
        except KeyError:
            raise ValueError(
                f"no hardware row for device_kind {device.device_kind!r}; "
                f"add one to repro.kernels.hw_model.HARDWARE with its "
                f"source (known: {sorted(HARDWARE)})") from None
    raise ValueError(f"no hardware model for platform {device.platform!r}")


def mxu_efficiency(hw: HardwareModel, *tile_dims: int) -> float:
    """Fraction of MXU peak a matmul with these tile dims can sustain.

    Each dimension below the systolic edge wastes the proportional slice
    of the array (a 64-wide operand occupies half the 128 columns); full
    multiples are free.  Dims are clamped to [1, mxu_dim] before the
    ratio, so 256 is as good as 128 — alignment, not size, is what pays.
    """
    eff = 1.0
    for d in tile_dims:
        d = max(1, min(int(d), hw.mxu_dim))
        eff *= d / hw.mxu_dim
    return max(eff, 1e-6)
