"""Quickstart: optimize a systolic-array floorplan in ~20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import (
    BusActivity,
    SystolicArrayGeometry,
    compare_sym_asym,
    optimal_aspect_power,
    profile_gemm,
)
from repro.compile_cache import configure_compile_cache

configure_compile_cache()

# 1. the paper's array: 32x32 PEs, int16 operands, 37-bit partial sums
geom = SystolicArrayGeometry.paper_32x32()

# 2. measure switching activity by streaming a real (quantized) GEMM through
#    the weight-stationary dataflow: post-ReLU activations (zeros + folded-
#    Gaussian magnitudes) and zero-mean weights, int16-quantized
from repro.core.quant import quantize_symmetric
from repro.core.workloads import synth_activations, synth_weights

acts = quantize_symmetric(synth_activations(512, 256, density=0.5), 16).values
weights = quantize_symmetric(synth_weights(256, 64), 16).values
profile = profile_gemm(acts, weights, rows=32, cols=32, b_h=16, b_v=37)
print(f"measured activity: a_h={profile.a_h:.3f}  a_v={profile.a_v:.3f}")

# 3. the optimal PE aspect ratio (paper Eq. 6) and what it saves
act = profile.as_bus_activity()
print(f"optimal W/H = {optimal_aspect_power(geom, act):.2f}  (square = 1.0)")
c = compare_sym_asym(geom, act)
print(
    f"interconnect power: {c.sym.interconnect_w*1e3:.2f} mW (square) -> "
    f"{c.asym.interconnect_w*1e3:.2f} mW (asymmetric), "
    f"saving {c.interconnect_saving*100:.1f}% interconnect / "
    f"{c.total_saving*100:.2f}% total"
)

# 4. the paper's own operating point reproduces its headline numbers
paper = compare_sym_asym(geom, BusActivity.paper_resnet50())
print(
    f"paper operating point: W/H={paper.aspect_opt:.2f}, "
    f"interconnect saving {paper.interconnect_saving*100:.1f}% (paper: 9.1%), "
    f"total {paper.total_saving*100:.1f}% (paper: 2.1%)"
)
