"""Design-space exploration: measured activities -> jitted engine -> Pareto.

Expands a declarative DesignSpace (geometry x input bits x WS/OS dataflow x
bus-invert), maps measured Table-I activity profiles onto it (one profiling
pass per activity class — (rows, b_h, b_v) for WS, geometry-free (b_h, b_v)
for OS — feeds the whole cols/coding cross product), evaluates every point
in one jitted program, and prints the Pareto frontier over (workload bus
power, array area, worst-case regret), split by dataflow.

OS vertical activities are MEASURED from the W-operand column streams; the
final section re-evaluates the grid under the retired ``a_v := a_h``
approximation and lists the design points whose ranking moved the most.

Run:  PYTHONPATH=src python examples/design_space_explore.py

With ``--store DIR`` the main evaluation runs through the checkpointed,
guard-validated sweep runner: chunks are committed to a crash-safe
content-addressed store as they finish, so a killed run (try it —
``--max-chunks 2`` stands in for kill -9, exiting after two chunks) resumes
bit-identically.  ``--resume`` asserts the run actually served chunks from
the store; ``--report PATH`` writes the machine-readable validation report
plus a sha256 digest of every result array (two runs that print the same
digest produced bit-identical physics).

Kill-and-resume end to end:
    python examples/design_space_explore.py --store /tmp/sw --max-chunks 2
    python examples/design_space_explore.py --store /tmp/sw --resume
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core.design_space import DesignSpace, evaluate_design_space
from repro.core.workloads import RESNET50_TABLE1, measured_design_activities

configure_compile_cache()

ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
ap.add_argument("--store", default=None, metavar="DIR",
                help="chunk store directory: run checkpointed + resumable")
ap.add_argument("--resume", action="store_true",
                help="require at least one chunk served from --store")
ap.add_argument("--chunk-size", type=int, default=16)
ap.add_argument("--max-chunks", type=int, default=None, metavar="N",
                help="stop after N fresh chunks (simulates a killed run)")
ap.add_argument("--report", default=None, metavar="PATH",
                help="write the sweep validation report as JSON")
ap.add_argument("--objective", choices=("jpo",), default=None,
                help="jpo: rank layout families on fused fleet J/op "
                     "(utilization + spill/trunk traffic + static power) and "
                     "list the points where the J/op winner differs from the "
                     "bus-power winner")
ap.add_argument("--model", default=None, metavar="ARCH",
                help="serving co-design: expand this config (see "
                     "repro.configs.registry ARCH_IDS) through the traffic "
                     "model into a MAC-share-weighted GEMM job set and answer "
                     "J/token over the same grid (requires --objective jpo)")
ap.add_argument("--traffic", default="decode_heavy", metavar="PRESET",
                help="traffic preset for --model (decode_heavy, "
                     "prefill_heavy, balanced)")
args = ap.parse_args()

if args.model is not None and args.objective != "jpo":
    ap.error("--model requires --objective jpo (J/token is priced J/op)")

sweep = None
if args.store is not None:
    from repro.core.sweep import SweepConfig

    sweep = SweepConfig(
        chunk_size=args.chunk_size, store=args.store, max_chunks=args.max_chunks
    )
elif args.resume or args.max_chunks is not None:
    ap.error("--resume/--max-chunks require --store")


def _write_report(report, digest=None, objective_report=None):
    doc = {"digest": digest, "report": report.as_dict()}
    if objective_report is not None:
        doc["objective"] = objective_report.as_dict()
    if args.report:
        with open(args.report, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote sweep report to {args.report}")


def _digest(ev) -> str:
    from repro.core.sweep import _DESIGN_FIELDS

    h = hashlib.sha256()
    for f in _DESIGN_FIELDS:
        h.update(np.ascontiguousarray(getattr(ev, f)).tobytes())
    return h.hexdigest()[:16]


def _jpo_digest(jev) -> str:
    h = hashlib.sha256()
    for f in ("feasible", "utilization", "j_per_mac", "j_per_mac_robust",
              "bus_power_robust", "overhead_w"):
        h.update(np.ascontiguousarray(np.asarray(getattr(jev, f))).tobytes())
    return h.hexdigest()[:16]

space = DesignSpace(
    rows=(16, 32),
    cols=(8, 16, 32, 64, 128),
    input_bits=(16,),
    dataflows=("WS", "OS"),
    bus_invert=(False, True),
)
grid = space.expand()
layers = RESNET50_TABLE1[:3]

print(f"design space: {grid.n_points} points "
      f"(rows {space.rows} x cols {space.cols} x {space.dataflows} "
      f"x BI {space.bus_invert})")
a_h, a_v, stats = measured_design_activities(grid, layers, return_stats=True)
print(f"measured {len(layers)} layers via {stats.jobs} profiling jobs "
      f"({stats.passes} device passes, {stats.cache_hits} cache hits)")

if sweep is None:
    ev = evaluate_design_space(grid, a_h, a_v)
else:
    from repro.core.sweep import SweepInterrupted

    try:
        ev = evaluate_design_space(grid, a_h, a_v, sweep=sweep)
    except SweepInterrupted as stop:
        # the kill -9 stand-in: committed chunks survive in the store;
        # rerunning with the same --store picks up exactly where this left off
        print(f"\ninterrupted on purpose: {stop}")
        print(f"partial sweep: {stop.report.summary()}")
        _write_report(stop.report)
        sys.exit(0)
    rep = ev.sweep_report
    print(f"sweep: {rep.summary()}")
    if args.resume and rep.chunks_resumed == 0:
        sys.exit("--resume: no chunks were served from the store")
    if args.objective is None:
        # with --objective the report is written at the end, with the
        # objective digest folded in, so resume CI covers both paths
        _write_report(rep, _digest(ev))
        print(f"results digest: {_digest(ev)}")
# Throughput-aware frontier: bus energy per MAC (small arrays win — narrower
# accumulators) vs MACs/cycle (big arrays win) vs worst-case regret.
mask = ev.pareto(("bus_energy_per_mac_j", "neg_macs_per_cycle", "max_regret"))
idx = np.flatnonzero(mask)
idx = idx[np.argsort(-ev.neg_macs_per_cycle[idx])]
os_mask = np.asarray(grid.dataflow_os, bool)

n_ws = int((mask & ~os_mask).sum())
n_os = int((mask & os_mask).sum())
print(f"\nPareto frontier, energy/MAC vs throughput vs regret "
      f"({len(idx)} of {grid.n_points} points — winner split: "
      f"{n_ws} WS / {n_os} OS):")
print(f"{'config':>22} {'W/H*':>6} {'fJ/MAC':>8} {'MACs/cyc':>9} {'regret':>8}")
for i in idx:
    print(
        f"{grid.describe(int(i)):>22} {float(ev.aspect_robust[i]):6.2f} "
        f"{float(ev.bus_energy_per_mac_j[i])*1e15:8.2f} "
        f"{-int(ev.neg_macs_per_cycle[i]):9d} "
        f"{float(ev.max_regret[i])*100:7.2f}%"
    )

i32 = int(np.flatnonzero(
    (grid.rows == 32) & (grid.cols == 32) & ~grid.bus_invert & ~os_mask
)[0])
print(
    f"\npaper operating point {grid.describe(i32)}: "
    f"robust W/H*={float(ev.aspect_robust[i32]):.2f}, "
    f"interconnect saving {float(ev.interconnect_saving[i32])*100:.1f}%, "
    f"total {float(ev.total_saving[i32])*100:.1f}% vs square"
)

# --- what measuring OS actually changed ------------------------------------
# Re-evaluate under the retired approximation (OS a_v copied from a_h) and
# rank every point by robust bus power in both worlds.
a_v_approx = np.where(os_mask[None, :], a_h, a_v)
ev_apx = evaluate_design_space(grid, a_h, a_v_approx)
delta = np.abs(a_v - a_v_approx)[:, os_mask]
rank = np.argsort(np.argsort(ev.bus_power_robust))
rank_apx = np.argsort(np.argsort(ev_apx.bus_power_robust))
moved = np.flatnonzero(rank != rank_apx)
print(f"\nretired a_v := a_h approximation on {int(os_mask.sum())} OS points: "
      f"mean |delta a_v| = {float(delta.mean()):.4f}, "
      f"max = {float(delta.max()):.4f}")
print(f"{len(moved)} of {grid.n_points} points change bus-power rank once OS "
      f"activities are measured; top design points by |rank move| + robust-"
      f"aspect shift:")
shift = np.abs(np.log(ev.aspect_robust) - np.log(ev_apx.aspect_robust))
score = np.abs(rank - rank_apx) + shift
top = np.argsort(-score)[:5]
print(f"{'config':>22} {'rank(apx)':>10} {'rank(meas)':>11} "
      f"{'W/H*(apx)':>10} {'W/H*(meas)':>11}")
for i in top:
    print(
        f"{grid.describe(int(i)):>22} {int(rank_apx[i]):10d} {int(rank[i]):11d} "
        f"{float(ev_apx.aspect_robust[i]):10.2f} {float(ev.aspect_robust[i]):11.2f}"
    )

# --- the layout-family axis: beyond the uniform rectangle -------------------
# The closed form can only describe uniform rectangles.  The segment-level
# engine (repro.layout) evaluates every point under every floorplan family —
# here with a 4:1 die-envelope constraint, the physical regime in which
# folded/serpentine and multi-pod layouts exist in the first place.
from repro.core.design_space import evaluate_layout_design_space  # noqa: E402
from repro.layout import LayoutPowerConfig  # noqa: E402

lspace = DesignSpace(
    rows=(8, 16, 32),
    cols=(32, 64, 128),
    input_bits=(16,),
    dataflows=("WS", "OS"),
    layouts=("uniform", "serpentine2", "serpentine4", "pods2x2"),
)
lgrid = lspace.expand()
la_h, la_v = measured_design_activities(lgrid, layers)
lev = evaluate_layout_design_space(
    lspace, la_h, la_v, cfg=LayoutPowerConfig(max_envelope_aspect=4.0)
)

print(f"\nlayout families x {lgrid.n_points} geometry points under a 4:1 "
      f"die-envelope limit ({', '.join(lev.layouts)}):")
# per (workload, point): which family minimizes that workload's bus power?
# (infeasible cells are +inf, so a plain argmin is total and never raises)
win = np.argmin(np.where(np.isfinite(lev.bus_power_opt), lev.bus_power_opt, np.inf), axis=1)
names = np.asarray(lev.layouts)
for li, name in enumerate(lev.layouts):
    print(f"  {name:>12}: best for {int((win == li).sum()):3d} of {win.size} "
          f"(workload, point) cells")
non_uniform = int((win != 0).sum())
assert non_uniform > 0, "expected at least one non-uniform winner"
w_i, p_i = np.unravel_index(
    np.argmax(lev.bus_power_opt[:, 0, :] / np.min(
        np.where(np.isfinite(lev.bus_power_opt), lev.bus_power_opt, np.inf), axis=1)),
    (la_h.shape[0], lgrid.n_points),
)
li = int(win[w_i, p_i])
p_uni = float(lev.bus_power_opt[w_i, 0, p_i])
p_best = float(lev.bus_power_opt[w_i, li, p_i])
print(
    f"largest win: workload {layers[int(w_i)].name} on {lgrid.describe(int(p_i))} "
    f"-> {names[li]} saves {(1 - p_best / p_uni)*100:.1f}% bus power vs the "
    f"uniform rectangle (W/H* {float(lev.aspect_opt[w_i, li, p_i]):.2f} vs "
    f"{float(lev.aspect_opt[w_i, 0, p_i]):.2f})"
)

# --- fused fleet J/op: fleets of pods vs the monolithic array ---------------
# Bus power alone says nothing about how well a GEMM fills the array.  The
# fused objective prices total J per useful MAC — wire + clock + calibrated
# static power divided through partition-model utilization, plus the spill
# and trunk words the pod partitioning moves — in the same jitted program,
# so fleets (k x k pods) and monoliths rank on delivered work.
if args.objective == "jpo":
    from repro.core.objective import evaluate_fleet_objective  # noqa: E402
    from repro.core.workloads import conv_to_gemm  # noqa: E402
    from repro.layout import pod_layouts  # noqa: E402

    JPO_FAMILIES = ("uniform", "serpentine2") + pod_layouts((2, 4))
    gemms = [conv_to_gemm(c) for c in layers]
    jkw = {}
    if sweep is not None:
        from repro.core.sweep import SweepConfig  # noqa: E402

        jkw["sweep"] = SweepConfig(chunk_size=args.chunk_size, store=args.store)
    jev = evaluate_fleet_objective(
        grid, a_h, a_v, gemms, layouts=JPO_FAMILIES, **jkw
    )
    print(f"\nfleet J/op: {len(gemms)} ResNet GEMMs x {grid.n_points} points "
          f"x families ({', '.join(jev.layouts)})")
    if sweep is not None:
        print(f"objective sweep: {jev.sweep_report.summary()}")

    jnames = np.asarray(jev.layouts)
    bus_win = jev.best_layout
    jpo_win = jev.best_layout_jpo
    is_pod = np.array([n.startswith("pods") for n in jev.layouts])
    print(f"{'family':>12} {'bus-power wins':>15} {'J/op wins':>10}")
    for li, name in enumerate(jev.layouts):
        print(f"{name:>12} {int((bus_win == li).sum()):15d} "
              f"{int((jpo_win == li).sum()):10d}")
    print(f"{'pod fleets':>12} {int(is_pod[bus_win].sum()):15d} "
          f"{int(is_pod[jpo_win].sum()):10d}   (vs monolithic families)")

    flips = np.flatnonzero(bus_win != jpo_win)
    assert len(flips) >= 1, "J/op never disagrees with bus power"
    jr = np.asarray(jev.j_per_mac_robust)
    gain = jr[bus_win[flips], flips] / jr[jpo_win[flips], flips] - 1.0
    order = flips[np.argsort(-gain)]
    print(f"\n{len(flips)} of {grid.n_points} points flip winner once "
          f"utilization + spill/trunk traffic are priced; largest J/op wins:")
    print(f"{'config':>22} {'bus-power pick':>15} {'J/op pick':>10} "
          f"{'J/op saved':>11}")
    for p in order[:5]:
        saved = 1.0 - jr[jpo_win[p], p] / jr[bus_win[p], p]
        print(f"{grid.describe(int(p)):>22} {jnames[bus_win[p]]:>15} "
              f"{jnames[jpo_win[p]]:>10} {saved*100:10.1f}%")

    if sweep is not None:
        digest = f"{_digest(ev)}+{_jpo_digest(jev)}"
        _write_report(rep, digest, objective_report=jev.sweep_report)
        print(f"results digest: {digest}")

# --- serving co-design: J/token for a model at a traffic mix ----------------
# The Table-I CNN layers never see decode-time skinny GEMMs, MoE expert
# batches, or a prefill:decode MAC split.  The serving subsystem expands a
# model config through a seeded traffic model into a MAC-share-weighted GEMM
# job set and prices J/token on the SAME grid and layout families, so the
# decode-regime optimum is directly comparable to the CNN one above.
if args.model is not None:
    from repro.serving import codesign, regime_best_cell  # noqa: E402

    other = "prefill_heavy" if args.traffic != "prefill_heavy" else "decode_heavy"
    models = [args.model]
    for m in ("mixtral_8x7b", "qwen3_8b", "jamba_v01_52b"):
        if m not in models:
            models.append(m)
    models = models[:3]
    presets = (args.traffic, other)

    print(f"\nserving co-design: J/token on the same {grid.n_points}-point "
          f"grid x families ({', '.join(JPO_FAMILIES)})")
    print(f"{'model':>16} {'traffic':>14} {'J/token':>10} "
          f"{'best cell':>26} {'W/H*':>6}")
    results = {}
    for m in models:
        for t in presets:
            r = codesign(m, t, space=space, layouts=JPO_FAMILIES, sweep=None)
            results[(m, t)] = r
            li, pi = r.best_cell
            print(f"{m:>16} {t:>14} {r.j_per_token:10.3e} "
                  f"{r.describe_cell((li, pi)):>26} "
                  f"{float(np.asarray(r.eval.aspect_robust)[li, pi]):6.2f}")

    # decode-regime optimum vs the Table-I CNN optimum (same grid/families:
    # jev above IS the CNN reference eval)
    r = results[(args.model, args.traffic)]
    dec_cell = regime_best_cell(r.eval, r.jobset, "decode")
    jr_cnn = np.asarray(jev.j_per_mac_robust)
    cnn_cell = tuple(int(i) for i in
                     np.unravel_index(np.argmin(jr_cnn), jr_cnn.shape))
    asp_dec = float(np.asarray(r.eval.aspect_robust)[dec_cell])
    asp_cnn = float(np.asarray(jev.aspect_robust)[cnn_cell])
    fam_flips = int((np.argmin(np.asarray(r.eval.j_per_mac_robust), axis=0)
                     != np.argmin(jr_cnn, axis=0)).sum())
    print(f"\ndecode-regime optimum ({args.model}, {args.traffic}): "
          f"{r.describe_cell(dec_cell)}, robust W/H* {asp_dec:.3f}")
    print(f"Table-I CNN optimum on the same grid:  "
          f"{r.describe_cell(cnn_cell)}, robust W/H* {asp_cnn:.3f}")
    print(f"{fam_flips} of {grid.n_points} points pick a different layout "
          f"family under the serving mix than under the CNN layers")
    differs = (dec_cell != cnn_cell
               or abs(asp_dec - asp_cnn) / asp_cnn > 0.02)
    assert differs, (
        "decode-regime optimum matches the CNN optimum in cell AND aspect — "
        "the serving workload axis is not moving the design answer")
    if dec_cell != cnn_cell:
        print("=> the decode regime picks a DIFFERENT (layout, point) cell "
              "than the CNN layers")
    else:
        print(f"=> same grid cell, but the decode mix re-shapes it: robust "
              f"W/H* {asp_dec:.3f} vs {asp_cnn:.3f} for the CNN layers "
              f"({(asp_dec / asp_cnn - 1) * 100:+.1f}% aspect shift)")
