# The paper's primary contribution: the TNN computational model (temporal
# coding, RNL synapses, pac-adder neurons, WTA, stabilized STDP) and the
# macro-level PPA hardware model that reproduces the paper's Tables I/II.
from repro.core.temporal import WaveSpec, encode_intensity, decode_time
from repro.core.stdp import (
    STDPConfig,
    apply_net,
    default_stabilize_table,
    stdp_net_from_uniforms,
    stdp_update,
)
from repro.core.column import (
    ColumnConfig,
    body_potential,
    column_forward,
    column_forward_matmul,
    column_step,
    crossing_time,
    init_weights,
    wta_inhibit,
)
from repro.core.layer import (
    LayerConfig, init_layer, layer_forward, layer_stdp_net, layer_step,
)
from repro.core.network import (
    NetworkConfig,
    prototype_config,
    init_network,
    init_train_state,
    encode,
    encode_images,
    encode_series,
    forward_all_padded,
    input_wave_spec,
    make_online_step,
    make_online_superbatch_step,
    make_superbatch_step,
    make_train_step,
    network_forward,
    network_forward_superbatch,
    network_train_step,
    network_train_superbatch,
    network_train_wave,
    params_from_tree,
    params_to_tree,
    refresh_vote_table,
    superbatch_keys,
    build_vote_table,
    classify,
    build_centroids,
    classify_centroid,
    with_impl,
)
from repro.core import hwmodel, macros

__all__ = [
    "WaveSpec", "encode_intensity", "decode_time",
    "STDPConfig", "stdp_update", "default_stabilize_table",
    "stdp_net_from_uniforms", "apply_net",
    "ColumnConfig", "body_potential", "column_forward", "column_forward_matmul",
    "column_step", "crossing_time", "init_weights", "wta_inhibit",
    "LayerConfig", "init_layer", "layer_forward", "layer_stdp_net", "layer_step",
    "NetworkConfig", "prototype_config", "init_network", "init_train_state",
    "encode", "encode_images", "encode_series", "forward_all_padded",
    "input_wave_spec",
    "make_online_step", "make_online_superbatch_step", "make_superbatch_step",
    "make_train_step", "network_forward", "network_forward_superbatch",
    "network_train_step", "network_train_superbatch", "network_train_wave",
    "params_from_tree", "params_to_tree", "refresh_vote_table",
    "superbatch_keys",
    "build_vote_table", "classify", "build_centroids", "classify_centroid", "with_impl",
    "hwmodel", "macros",
]
