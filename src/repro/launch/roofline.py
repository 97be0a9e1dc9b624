"""Roofline model: three terms derived from the compiled dry-run artifact.

    compute    = HLO_FLOPs_total      / (chips * peak FLOP/s, bf16)
    memory     = HLO_bytes_total      / (chips * peak HBM B/s)
    collective = collective_bytes     / (chips * peak B/s per ICI link)

The peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
a device that is not in the table is an error, never a default.

All three terms come from the loop-aware post-SPMD HLO walk in
``repro.launch.hlo_analysis`` (XLA's own ``cost_analysis()`` counts
``lax.scan`` bodies once and would under-report layer-stacked models).
Terms are per-device seconds per step.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks: bf16 FLOP/s, HBM bytes/s, bytes/s per
    ICI link."""

    flops: float
    hbm_bw: float
    ici_bw: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links x 50 GB/s).
_V5E = DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)

#: Peaks by ``device_kind`` as JAX reports it.
PEAKS: dict[str, DevicePeaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add the device's datasheet numbers to "
            "repro.launch.roofline.PEAKS"
        ) from None


@dataclasses.dataclass
class Roofline:
    device_kind: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_per_device: float
    peak_memory_per_device: float
    collective_breakdown: dict

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / peaks(self.device_kind).flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / peaks(self.device_kind).hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_per_device / peaks(self.device_kind).ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "device_kind": self.device_kind,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_per_device,
            "peak_memory_per_device_gib": self.peak_memory_per_device / 2**30,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "collective_breakdown": self.collective_breakdown,
        }


def analyze(compiled, mesh, *, device_kind: str) -> Roofline:
    """Three-term roofline from the compiled artifact, against the peaks
    of ``device_kind`` (the chip the program was compiled for).

    FLOPs / HBM bytes / collective bytes come from the loop-aware HLO walk
    (repro.launch.hlo_analysis) — XLA's own cost_analysis counts scan bodies
    once and is kept only as a cross-check in the dry-run record.
    """
    from repro.launch import hlo_analysis

    peaks(device_kind)  # unknown devices fail before any HLO work
    chips = int(np.prod(list(dict(mesh.shape).values())))
    text = compiled.as_text()
    costs = hlo_analysis.analyze_text(text)
    mem = compiled.memory_analysis()
    peak = float(
        getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )
    return Roofline(
        device_kind=device_kind,
        chips=chips,
        flops_per_device=costs.flops,
        bytes_per_device=costs.hbm_bytes,
        collective_per_device=float(sum(costs.collective_bytes.values())),
        peak_memory_per_device=peak,
        collective_breakdown=dict(costs.collective_bytes),
    )


def model_flops(n_params: int, n_active_params: int, tokens: int, kind: str) -> float:
    """6*N*D for training; 2*N*D for inference forward (per standard conventions)."""
    n = n_active_params or n_params
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens
