# Pallas TPU kernels for the paper's compute hot spots (the column datapath
# the custom macros implement in silicon): fused RNL-accumulate+threshold
# forward, WTA inhibition, and the fused STDP update. ops.py wraps them with
# padding + the per-backend interpret flag; padding.py owns the launch geometry
# (PadPlan) and the network-level fused-wave plan (NetworkPlan); ref.py
# holds the pure-jnp oracles. The layer-level entry points
# (layer_forward_fused / layer_stdp_fused) are the production path selected
# by ColumnConfig(impl="pallas"); tnn_wave.py is the whole-network
# single-launch wave executor selected by impl="fused" (DESIGN.md §10).
from repro.kernels import ops, padding, ref, tnn_wave
from repro.kernels.ops import (
    column_forward,
    layer_forward_fused,
    layer_stdp_fused,
    stdp_update,
    wta,
)
from repro.kernels.padding import (
    NetworkPlan,
    PadPlan,
    fused_wave_capable,
    network_plan,
)
from repro.kernels.tnn_wave import wave_forward, wave_train

__all__ = [
    "ops", "padding", "ref", "tnn_wave",
    "column_forward", "layer_forward_fused", "layer_stdp_fused",
    "stdp_update", "wta",
    "PadPlan", "NetworkPlan", "fused_wave_capable", "network_plan",
    "wave_forward", "wave_train",
]
