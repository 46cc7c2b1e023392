"""What the stencil must do, from the cell's shapes alone, and the chip's peaks.

The counts depend only on the configuration and the traffic, never on the
plan, so they stay the same whatever implements the stencil:

* flops per cell update: one multiply per tap and one add per neighbour
  tap, the paper's Table I (8r+1 in 2D, 12r+1 in 3D for a star);
* bytes per call: one read and one write of the grid.

The peaks come from ``bench/peaks.json``, keyed by ``device_kind``; a kind
that is not there is an error.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def flops_per_cell(n_neighbour_taps: int) -> int:
    return 2 * n_neighbour_taps + 1


def cells(config: dict) -> int:
    return math.prod(config["grid"])


def bytes_per_call(config: dict) -> int:
    itemsize = np.dtype(config["program"]["dtype"]).itemsize
    return 2 * cells(config) * itemsize


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict,
                     chips: int):
    """The least time ``chips`` chips could take: the larger of the VPU
    and the HBM bounds, and which of the two it is."""
    t_vpu = flops / (chips * peak["peak_vpu_f32_flops"])
    t_hbm = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_vpu, "vpu") if t_vpu >= t_hbm else (t_hbm, "hbm")
