"""The TNN architectures that ``launch/train.py`` and ``launch/serve.py``
take by ``--arch``, built from the launchers' flags in one place: train and
serve must build the same network or the checkpoint fingerprint refuses
the warm start."""
from __future__ import annotations

import functools

from repro.configs import tnn_mnist, tnn_ucr

TNN_ARCHS = ("tnn-mnist", "tnn-ucr-inlineskate")


def launcher_config(arch: str, sites: int, depth: int, impl: str,
                    packed: bool):
    """(network config, trainer config factory) of a TNN ``--arch``.
    ``sites`` and ``depth`` shape the prototype; the time-series column's
    shape is its dataset's."""
    if arch == "tnn-ucr-inlineskate":
        return (tnn_ucr.network_config(impl=impl, packed=packed),
                tnn_ucr.train_config)
    if arch != "tnn-mnist":
        raise ValueError(f"unknown TNN arch {arch!r}; one of {TNN_ARCHS}")
    return (tnn_mnist.launcher_network_config(sites, depth=depth, impl=impl,
                                              packed=packed),
            functools.partial(tnn_mnist.train_config, sites=sites))
