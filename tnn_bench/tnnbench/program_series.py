"""The benchmark's door into the program for a series configuration: the
network made by the program's time-series configuration
(``repro.configs.tnn_ucr``) from the file's sizes, and refused unless
every size matches the file, and the program's series encoder. The
train step, its state and the weights go through :mod:`tnnbench.program`.

A program without a series front end has no ``repro.configs.tnn_ucr``:
:func:`network` then fails at once, before any data is made.
"""
from __future__ import annotations


def network(cfg):
    """The program's network for a series configuration file."""
    import dataclasses

    from repro.configs.tnn_ucr import network_config

    prog = cfg["program"]
    (q,), (theta,) = cfg["widths"], cfg["thetas"]
    net = network_config(series_len=cfg["series_len"], clusters=q,
                         theta=theta, impl=prog["impl"])
    net = dataclasses.replace(net, packed=prog["packed"])
    stated = {
        "layers": [(cfg["sites"], cfg["series_len"], q, theta)],
        "series_len": cfg["series_len"],
        "T": 1 << cfg["time_bits"], "w_max": (1 << cfg["weight_bits"]) - 1,
        "stdp": (cfg["stdp"]["mu_capture"], cfg["stdp"]["mu_backoff"],
                 cfg["stdp"]["mu_search"], tuple(cfg["stdp"]["stabilize"]),
                 cfg["stdp"]["batch_reduce"]),
        "n_classes": cfg["n_classes"],
        "impl": prog["impl"], "packed": prog["packed"],
    }
    col = net.layers[0].column
    built = {
        "layers": [(l.n_cols, l.column.p, l.column.q, l.column.theta)
                   for l in net.layers],
        "series_len": net.series_len,
        "T": col.wave.T, "w_max": col.wave.w_max,
        "stdp": (col.stdp.mu_capture, col.stdp.mu_backoff,
                 col.stdp.mu_search, col.stdp.table_tuple(col.wave),
                 col.stdp.batch_reduce),
        "n_classes": net.n_classes, "impl": col.impl, "packed": net.packed,
    }
    for k, v in stated.items():
        if built[k] != v:
            raise ValueError(f"program builds {k}={built[k]!r}, the "
                             f"configuration states {v!r}")
    return net


def encode(series, net):
    """The program's series encoder, as ``TNNTrainer``'s stream runs it, on
    the host."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.network import encode

    return np.asarray(encode(jnp.asarray(series), net))
