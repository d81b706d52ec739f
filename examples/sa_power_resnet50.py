"""Full paper reproduction: ResNet50 Table-I layers through the complete
pipeline — synthetic ImageNet-statistics activations -> int16 quantization ->
WS-dataflow switching profile -> floorplan optimization -> Fig. 4/5 report,
then the same savings re-derived from the segment-level layout engine
(explicit wire geometry) side by side with the closed form.

    PYTHONPATH=src python examples/sa_power_resnet50.py
"""

from repro.compile_cache import configure_compile_cache
from repro.core.energy import (
    average_comparison,
    calibration_split_arr,
    compare_sym_asym,
)
from repro.core.floorplan import BusActivity, SystolicArrayGeometry, optimal_aspect_power
from repro.core.switching import combine_profiles, profile_cache_info
from repro.core.systolic import schedule_gemm
from repro.core.workloads import RESNET50_TABLE1, conv_to_gemm, profile_network
from repro.layout import LayoutPowerConfig, evaluate_layout_space, segment_bus_power

configure_compile_cache()

geom = SystolicArrayGeometry.paper_32x32()

print("profiling Table-I layers on the 32x32 WS array (int16)...")
print("(one batched pipeline call: exact full-stream profiles, a couple of")
print(" fused device programs for the whole network; cached)")
profiles, stats = profile_network(RESNET50_TABLE1, return_stats=True)
print(
    f"  scheduler: {stats.buckets} device program(s), {stats.tasks} tasks, "
    f"{stats.cache_hits} cache hits"
)
for layer, p in zip(RESNET50_TABLE1, profiles):
    g = conv_to_gemm(layer)
    s = schedule_gemm(g.m, g.k, g.n, 32, 32)
    print(
        f"  {layer.name}: GEMM {g.m}x{g.k}x{g.n:5d}  a_h={p.a_h:.3f} a_v={p.a_v:.3f}"
        f"  zeros={p.input_zero_fraction:.2f}  cycles={s.total_cycles}"
        f"  util={s.utilization:.2f}"
    )

avg = combine_profiles(profiles)
design = avg.as_bus_activity()
print(f"\naverage simulated activities: a_h={avg.a_h:.3f} a_v={avg.a_v:.3f}")
print(f"(paper measured on ImageNet:  a_h=0.220 a_v=0.360)")
print(f"design aspect ratio W/H = {optimal_aspect_power(geom, design):.2f}")

print("\nper-layer power, symmetric vs asymmetric floorplan:")
comps = []
for layer, p in zip(RESNET50_TABLE1, profiles):
    c = compare_sym_asym(geom, p.as_bus_activity(), design_act=design)
    comps.append(c)
    print(
        f"  {layer.name}: interconnect {c.sym.interconnect_w*1e3:7.2f} -> "
        f"{c.asym.interconnect_w*1e3:7.2f} mW  ({c.interconnect_saving*100:5.1f}%)"
        f"   total {c.sym.total_w*1e3:7.2f} -> {c.asym.total_w*1e3:7.2f} mW"
        f"  ({c.total_saving*100:4.1f}%)"
    )

agg = average_comparison(comps)
print(
    f"\nAVERAGE: interconnect saving {agg['interconnect_saving']*100:.2f}% "
    f"(paper: 9.1%), total saving {agg['total_saving']*100:.2f}% (paper: 2.1%)"
)

paper = compare_sym_asym(geom, BusActivity.paper_resnet50())
print(
    f"paper-calibrated point:    {paper.interconnect_saving*100:.2f}% / "
    f"{paper.total_saving*100:.2f}%  at W/H={paper.aspect_opt:.2f}"
)

# --- segment-level layout engine: the closed form, re-derived from explicit
# wire geometry (every PE placed, every hop enumerated, per-segment roll-up).
print("\nsegment-level vs closed-form savings (uniform rectangle):")
print(f"{'layer':>6} {'closed int%':>12} {'segment int%':>13} "
      f"{'closed tot%':>12} {'segment tot%':>13}")
aspect = optimal_aspect_power(geom, design)
max_rel = 0.0
seg_sym_sum = seg_asym_sum = seg_tot_sym = seg_tot_asym = 0.0
for layer, p, c in zip(RESNET50_TABLE1, profiles, comps):
    act = p.as_bus_activity()
    seg_sym = segment_bus_power("uniform", geom, act, 1.0)
    seg_asym = segment_bus_power("uniform", geom, act, aspect)
    fixed, compute = calibration_split_arr(seg_sym)
    s_int = 1.0 - (seg_asym + fixed) / (seg_sym + fixed)
    s_tot = 1.0 - (seg_asym + fixed + compute) / (seg_sym + fixed + compute)
    max_rel = max(max_rel, abs(seg_sym - c.sym.bus_w) / c.sym.bus_w,
                  abs(seg_asym - c.asym.bus_w) / c.asym.bus_w)
    seg_sym_sum += seg_sym + fixed
    seg_asym_sum += seg_asym + fixed
    seg_tot_sym += seg_sym + fixed + compute
    seg_tot_asym += seg_asym + fixed + compute
    print(f"{layer.name:>6} {c.interconnect_saving*100:12.2f} {s_int*100:13.2f} "
          f"{c.total_saving*100:12.2f} {s_tot*100:13.2f}")
print(
    f"AVERAGE closed-form {agg['interconnect_saving']*100:.2f}% / "
    f"{agg['total_saving']*100:.2f}%  —  segment-level "
    f"{(1 - seg_asym_sum / seg_sym_sum)*100:.2f}% / "
    f"{(1 - seg_tot_asym / seg_tot_sym)*100:.2f}%  "
    f"(bus-power rel err {max_rel:.1e}: Eq. 5/6 is a verified special case)"
)

# Beyond the closed form: under a die-envelope constraint an elongated array
# cannot realize the Eq. 6 optimum as a uniform rectangle — folded layouts can.
from repro.core.design_space import DesignSpace  # noqa: E402

tall = DesignSpace(rows=(8,), cols=(128,), input_bits=(16,))
cfg = LayoutPowerConfig(max_envelope_aspect=4.0)
lev = evaluate_layout_space(
    tall.expand(), avg.a_h, avg.a_v,
    layouts=("uniform", "serpentine4", "pods2x2"), cfg=cfg,
)
import numpy as np  # noqa: E402

p_uni = float(lev.bus_power_robust[0, 0])
best_i = int(np.argmin(lev.bus_power_robust[:, 0]))  # rank on bus power
p_best = float(lev.bus_power_robust[best_i, 0])
print(
    f"\n8x128 array under a 4:1 die-envelope limit: best layout = "
    f"{lev.layouts[best_i]} (bus power {p_best*1e3:.2f} mW vs uniform "
    f"{p_uni*1e3:.2f} mW, -{(1 - p_best / p_uni)*100:.1f}%)"
)
print(f"profile cache: {profile_cache_info()}")
