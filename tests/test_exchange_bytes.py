"""``CompiledStencil.exchange_bytes_per_superstep`` counts what the mesh
exchange sends: the byte sizes of the ``collective-permute`` ops in the
sharded fused run's lowered HLO (a subprocess on 4 fake CPU devices), as
the ``repro.compile`` span does, and nothing on one device."""

import json

import pytest

import repro
from repro import obs
from repro.core.program import StencilProgram


@pytest.mark.parametrize("case", ["2d_r4_4x1_clamp",
                                  "2d_r2_box_2x2_periodic",
                                  "3d_r1_2x2x1_batched"])
def test_exchange_bytes_match_the_collective_permutes(dist_runner, case):
    out = json.loads(dist_runner("exchange_bytes.py", case, devices=4,
                                 timeout=300).strip().splitlines()[-1])
    sizes, per = out["permute_bytes"], out["reported"]
    split = sum(n > 1 for n in out["decomp"])
    # two strips per split axis in each superstep the HLO holds
    assert len(sizes) == 2 * split * out["supersteps_in_hlo"]
    assert sum(sizes) == per * out["supersteps_in_hlo"]
    # the compile span carries the same bytes, and one exchange a superstep
    assert out["span"] == {"exchange_bytes_per_superstep": per,
                           "exchanges_per_call": out["supersteps_per_call"]}


def test_one_device_has_no_exchange():
    program = StencilProgram(ndim=2, radius=4)
    with obs.profile() as rec:
        cs = repro.stencil(program).compile((64, 256), steps=8)
    assert cs.exchange_bytes_per_superstep is None
    (span,) = rec.spans("compile")
    assert "exchange_bytes_per_superstep" not in span
    assert "exchanges_per_call" not in span
