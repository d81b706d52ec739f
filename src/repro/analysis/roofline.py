"""Three-term roofline model for TPU v5e (target hardware of the dry-run).

  compute    t = HLO_FLOPs   / (chips * 197e12)   [bf16 peak per chip]
  memory     t = HLO_bytes   / (chips * 819e9)    [HBM BW per chip]
  collective t = coll_bytes  / (chips * 50e9)     [ICI per link]

FLOPs/bytes come from ``compiled.cost_analysis()`` of the *partitioned*
module, i.e. per-device numbers; multiplying by chips gives the global terms
the formulas above expect, so the per-device form used here is equivalent.

MODEL_FLOPS (the useful-work yardstick) is 6*N*D for training and 2*N*D for
inference, with N = active FLOP-bearing params (experts scaled by top_k/E,
input embedding excluded) and D = tokens processed by the step.
"""

from __future__ import annotations

import dataclasses

from repro.configs.params import count_params


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    name: str = "tpu_v5e"
    peak_flops: float = 197e12  # bf16 FLOP/s per chip
    hbm_bw: float = 819e9  # bytes/s per chip
    ici_bw: float = 50e9  # bytes/s per link

V5E = HardwareModel()


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    t_compute: float
    t_memory: float
    t_collective: float
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    chips: int
    model_flops: float
    hlo_flops_global: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / global HLO FLOPs — remat/dispatch waste detector."""
        return self.model_flops / self.hlo_flops_global if self.hlo_flops_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU upper bound: useful-FLOP time / bound time."""
        t_useful = self.model_flops / (self.chips * V5E.peak_flops)
        return t_useful / self.bound_time if self.bound_time else 0.0

    def as_dict(self) -> dict:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "chips": self.chips,
            "model_flops": self.model_flops,
            "hlo_flops_global": self.hlo_flops_global,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def roofline(
    flops_per_device: float,
    bytes_per_device: float,
    coll_bytes_per_device: float,
    chips: int,
    model_flops: float,
    hw: HardwareModel = V5E,
) -> RooflineTerms:
    return RooflineTerms(
        t_compute=flops_per_device / hw.peak_flops,
        t_memory=bytes_per_device / hw.hbm_bw,
        t_collective=coll_bytes_per_device / hw.ici_bw,
        flops_per_device=flops_per_device,
        bytes_per_device=bytes_per_device,
        coll_bytes_per_device=coll_bytes_per_device,
        chips=chips,
        model_flops=model_flops,
        hlo_flops_global=flops_per_device * chips,
    )


def model_flops_for(cfg, shape) -> float:
    """6ND (train) / 2ND (inference) with N = active FLOP-bearing params."""
    n = count_params(cfg, active_only=True, exclude_embed=True)
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n * d
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
