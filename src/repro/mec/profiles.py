"""Early-exit accuracy/latency profiles.

Paper Table I (VGG-16 on CIFAR-10; RTX 2080TI and GTX 1080TI edge servers)
is the calibrated, paper-faithful profile. We additionally derive analytic
TPU-v5e profiles from a roofline model so the same simulator can model
TPU-backed edge servers and the assigned LLM architectures (DESIGN.md §3/§4).
"""
from __future__ import annotations

import numpy as np

from repro.obs.peaks import TPU_V5E, chip_peaks

# Paper Table I — candidate early-exits of VGG-16.
# columns: exit number (in the 17-exit enumeration), accuracy,
#          inference ms on RTX 2080TI, inference ms on GTX 1080TI.
VGG16_TABLE_I = {
    "exit_no": np.array([1, 3, 4, 7, 17]),
    "accuracy": np.array([0.800, 0.850, 0.885, 0.905, 0.935]),
    "ms_rtx2080ti": np.array([0.36, 0.46, 0.54, 0.71, 1.26]),
    "ms_gtx1080ti": np.array([0.73, 0.89, 1.06, 1.40, 2.42]),
}

# Indices (into the 17-exit enumeration) of the five candidate exits.
CANDIDATE_EXITS = (1, 3, 4, 7, 17)


def exit_profile_gpu():
    """(exit_times_s [N=2, L=5], exit_acc [L=5]) — the paper's two ESs."""
    times_ms = np.stack(
        [VGG16_TABLE_I["ms_rtx2080ti"], VGG16_TABLE_I["ms_gtx1080ti"]])
    return times_ms * 1e-3, VGG16_TABLE_I["accuracy"].copy()


# --- Analytic TPU-v5e profile -------------------------------------------------
# VGG-16 (CIFAR-10, 32x32 input) cumulative GFLOPs up to each of the five
# candidate exits (conv MACs*2 + classifier), batch 1.
_VGG16_CUM_GFLOPS = np.array([0.0049, 0.0769, 0.1147, 0.2314, 0.6280])
_VGG16_CUM_MBYTES = np.array([0.35, 1.6, 2.4, 5.1, 30.0])  # weights+acts touched


def exit_profile_tpu_v5e(derate: float = 0.15):
    """Roofline latency of each VGG-16 candidate exit on one TPU-v5e chip.

    ``derate`` models achievable fraction of peak for small conv batches.
    Latency = max(compute term, memory term) + fixed 50us dispatch overhead.
    """
    v5e = chip_peaks(TPU_V5E)
    t_comp = _VGG16_CUM_GFLOPS * 1e9 / (v5e.flops_bf16 * derate)
    t_mem = _VGG16_CUM_MBYTES * 1e6 / v5e.hbm_bw
    times = np.maximum(t_comp, t_mem) + 50e-6
    return times[None, :], VGG16_TABLE_I["accuracy"].copy()


def llm_exit_profile(n_layers: int, d_model: int, d_ff: int, vocab: int,
                     exits: tuple, *, n_chips: int = 1,
                     seq_len: int = 1, kv_len: int = 4096,
                     quality_floor: float = 0.72, quality_ceil: float = 0.95):
    """Analytic early-exit profile for a decoder-only transformer.

    The paper profiles VGG-16 exits empirically (Table I); for the assigned
    LLM architectures we derive the same two curves analytically:

    * latency(exit) from the decode-step roofline (memory-bound: weight +
      KV-cache bytes touched up to that layer),
    * quality(exit) from the empirical log-depth early-exit scaling reported
      in the multi-exit literature (deeper exits saturate — same shape as
      Fig. 3 of the paper).

    Returns (times_s [1, len(exits)], quality [len(exits)]).
    """
    exits = np.asarray(exits)
    per_layer_params = 4 * d_model * d_model + 3 * d_model * d_ff
    bytes_per_layer = 2.0 * per_layer_params            # bf16 weights
    kv_bytes_per_layer = 2 * 2.0 * kv_len * d_model     # K and V, bf16 (MHA upper bound)
    head_bytes = 2.0 * d_model * vocab
    cum_bytes = exits * (bytes_per_layer + kv_bytes_per_layer) + head_bytes
    v5e = chip_peaks(TPU_V5E)    # the edge server modelled is a v5e
    t_mem = cum_bytes / (v5e.hbm_bw * n_chips)
    cum_flops = seq_len * 2.0 * (exits * per_layer_params + d_model * vocab)
    t_comp = cum_flops / (v5e.flops_bf16 * n_chips)
    times = np.maximum(t_mem, t_comp) + 50e-6
    # saturating quality curve in depth (matches the paper's Fig 3 shape)
    frac = np.log1p(exits) / np.log1p(n_layers)
    quality = quality_floor + (quality_ceil - quality_floor) * frac
    return times[None, :], quality
