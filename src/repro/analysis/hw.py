"""Hardware constants.

TPU v5e numbers are fixed by the project brief (roofline constants):
197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.

The paper-device table reproduces paper Table II verbatim — it drives the
Table III/IV/V reproduction benchmarks.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class TpuChip:
    name: str = "tpu-v5e"
    peak_bf16_flops: float = 197e12        # brief-fixed, MXU peak
    # VPU f32 peak (stencils are VPU work; the MXU is unused by a star
    # stencil).  Not published for v5e; assumption documented in DESIGN.md:
    # 1024 lanes x FMA x 4 ALUs x ~1.67 GHz ~= 13.7 TFLOP/s.
    peak_vpu_f32_flops: float = 13.7e12
    hbm_bytes_per_s: float = 819e9          # brief-fixed
    ici_link_bytes_per_s: float = 50e9      # brief-fixed, per link
    ici_links: int = 4                      # 2D torus on v5e: 4 links/chip
    hbm_bytes: int = 16 * 1024**3           # 16 GiB HBM
    vmem_bytes: int = 128 * 1024**2         # 128 MiB VMEM per core
    # Planner budget: leave headroom for pipeline double-buffering + compiler
    # temporaries.
    vmem_budget_bytes: int = 96 * 1024**2


V5E = TpuChip()

#: Chips by ``jax.Device.device_kind``.  A TPU whose kind is missing here
#: is an error (:func:`chip_for_kind`), never a silent V5E default.
CHIPS = {"TPU v5 lite": V5E}


def chip_for_kind(device_kind: str) -> TpuChip:
    """The :class:`TpuChip` of a TPU device kind; raises for unknown kinds."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no TpuChip entry for device kind {device_kind!r} (known: "
            f"{sorted(CHIPS)}); add its constants to "
            f"repro.analysis.hw.CHIPS") from None


@dataclasses.dataclass(frozen=True)
class PaperDevice:
    """A row of paper Table II."""

    name: str
    peak_gflops: float          # single-precision
    mem_bw_gbps: float
    tdp_watt: float
    flop_per_byte: float


# Paper Table II, verbatim.
PAPER_DEVICES = {
    "arria10": PaperDevice("Arria 10 GX 1150", 1450.0, 34.1, 70.0, 42.522),
    "xeon": PaperDevice("Xeon E5-2650 v4", 700.0, 76.8, 105.0, 9.115),
    "xeonphi": PaperDevice("Xeon Phi 7210F", 5325.0, 400.0, 235.0, 13.313),
    "gtx580": PaperDevice("GTX 580", 1580.0, 192.4, 244.0, 8.212),
    "gtx980ti": PaperDevice("GTX 980 Ti", 6900.0, 336.6, 275.0, 20.499),
    "p100": PaperDevice("Tesla P100", 9300.0, 720.9, 250.0, 12.901),
}

ARRIA10_DSPS = 1518           # paper §V.A
ARRIA10_MEM_CTRL_MHZ = 266.0  # paper §VI.A
