"""FLOPs accounting and MFU (model FLOPs utilization).

The reference publishes raw images/sec only (README.md:113-131) — no
hardware-utilization story. On TPU the number that actually says whether a
program maps well onto the MXU is MFU: achieved *model* FLOP/s over the
chip's peak. Two sources:

  1. analytic per-model estimates (the standard 6N+attention / per-image
     formulas) — the conventional MFU numerator (model FLOPs, independent
     of remat or padding);
  2. XLA's cost model for the exact compiled executable
     (`Compiled.cost_analysis()["flops"]`). Two caveats make it the
     fallback, not the primary: it analyzes the post-SPMD-partition
     module, so the count is PER DEVICE (callers must scale by mesh size
     for a global figure), and Pallas kernels are opaque custom calls it
     scores as 0 FLOPs — on the flash-attention path it misses the whole
     attention share.

All `flops_per_step` values in this module's API are GLOBAL (whole-mesh)
per-step counts; MFU is flops_per_step * steps_per_sec / (n_devices * peak).
"""
from __future__ import annotations

from typing import Optional, Tuple

# Peaks of one chip, keyed by the exact `device_kind` jax reports:
# (bf16 dense matmul FLOP/s, HBM bytes/s). The spellings are jax's own
# (jax/_src/pallas/mosaic/tpu_info.py, 0.9.0); the figures are the
# per-chip numbers of Google Cloud's TPU documentation for each version
# ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s). Only "TPU v5 lite" has been
# read off hardware by this repo (chip_smoke.py, PERF.md); a kind that is
# not listed is an error, never a default — a guessed peak makes every
# MFU computed from it wrong without saying so.
_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),      # v5e
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),          # v5p
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),     # v6e (Trillium)
    "TPU v6e": (918e12, 1640e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v3": (123e12, 900e9),
    "TPU v2": (45e12, 700e9),
}


def device_peaks(device=None) -> Optional[Tuple[float, float]]:
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one device. None off-TPU
    (CPU tests have no peak to divide by); a TPU whose `device_kind` is
    not in the table raises."""
    import jax

    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind
    if kind not in _PEAKS:
        raise ValueError(
            f"device_kind {kind!r} is not in the peaks table "
            f"(utils/flops.py); add its published bf16 FLOP/s and HBM "
            f"bytes/s with their source. Known: {sorted(_PEAKS)}")
    return _PEAKS[kind]


def device_peak_flops(device=None) -> Optional[float]:
    """Peak bf16 FLOP/s for one device; None off-TPU."""
    peaks = device_peaks(device)
    return peaks[0] if peaks else None


def compiled_flops(compiled) -> Optional[float]:
    """Total FLOPs of one execution of a jax `Compiled`, from XLA's cost
    model. Returns None when the backend doesn't report it."""
    try:
        analysis = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend-dependent surface
        return None
    # versions differ: dict, or list with one dict per computation
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    flops = (analysis or {}).get("flops")
    return float(flops) if flops and flops > 0 else None


# ---------------------------------------------------------------------------
# analytic fallbacks
# ---------------------------------------------------------------------------

# forward FLOPs per 224×224 image. The widely-quoted "GFLOPs" table values
# (1.8/3.7/4.1/7.8/11.6) are multiply-ACCUMULATES; true FLOPs are 2× that.
# Cross-checked against XLA's cost model on the compiled forward (resnet101:
# 15.07 GFLOP/img vs 15.7 analytic — within conv-padding noise).
_RESNET_FWD_FLOPS_224 = {
    "resnet18": 3.64e9,
    "resnet34": 7.36e9,
    "resnet50": 8.24e9,
    "resnet101": 15.70e9,
    "resnet152": 23.16e9,
}


def resnet_train_flops_per_image(model_name: str,
                                 image_size: int = 224,
                                 stem: str = "conv7") -> Optional[float]:
    """fwd+bwd FLOPs per image ≈ 3× forward (bwd ≈ 2× fwd); conv FLOPs
    scale with spatial area, so rescale from the 224px table. The "s2d"
    stem (models/resnet.py) replaces the 7×7/s2 conv with a 2×2 conv on
    the 4×4 space-to-depth input — fewer actual FLOPs, so the table
    value is adjusted or the reported MFU would overstate work done."""
    fwd = _RESNET_FWD_FLOPS_224.get(model_name)
    if fwd is None:
        return None
    if stem == "s2d":
        # at 224px: conv7 stem = 2·112²·64·(7·7·3) = 236.0 MF fwd;
        # s2d stem = 2·56²·64·(2·2·48) = 77.1 MF fwd
        fwd = fwd - (236.0e6 - 77.1e6)
    return 3.0 * fwd * (image_size / 224.0) ** 2


def transformer_train_flops_per_token(num_params: int, num_layers: int,
                                      embed_dim: int, seq_len: int,
                                      causal: bool = True) -> float:
    """Standard accounting (PaLM appendix B): 6N matmul FLOPs per token for
    fwd+bwd, plus attention logits/values 12·L·E·S (halved for causal)."""
    attn = 12.0 * num_layers * embed_dim * seq_len
    if causal:
        attn /= 2.0
    return 6.0 * num_params + attn


def param_count(params) -> int:
    import jax

    return sum(int(x.size) for x in jax.tree.leaves(params))


def mfu(flops_per_step: Optional[float], steps_per_sec: float,
        n_devices: int, device=None) -> Optional[float]:
    """Achieved fraction of peak, per device. `flops_per_step` is the
    GLOBAL (whole-mesh) model FLOPs of one step. None when either side of
    the ratio is unknown."""
    peak = device_peak_flops(device)
    if not flops_per_step or not peak or n_devices <= 0:
        return None
    return flops_per_step * steps_per_sec / (n_devices * peak)


def throughput_stats(flops_per_step: Optional[float], steps_per_sec: float,
                     n_devices: int, device=None) -> dict:
    """The metric triple both trainers report: global flops_per_step,
    per-device TFLOP/s, and MFU (None-safe)."""
    tfl = (flops_per_step * steps_per_sec / n_devices / 1e12
           if flops_per_step and n_devices > 0 else None)
    return {
        "flops_per_step": flops_per_step,
        "tflops_per_sec_per_device": tfl,
        "mfu": mfu(flops_per_step, steps_per_sec, n_devices, device),
    }


__all__ = ["device_peaks", "device_peak_flops", "compiled_flops",
           "resnet_train_flops_per_image",
           "transformer_train_flops_per_token", "param_count", "mfu",
           "throughput_stats"]
