"""tnn-ucr-inlineskate — the time-series clustering TNN of Chaudhari, Nair,
Moura and Shen ("Unsupervised Clustering of Time Series Signals using
Neuromorphic Energy-Efficient Temporal Neural Networks", ICASSP 2021,
arXiv 2102.09200) at the shape of the UCR archive's InlineSkate
(Dau et al., arXiv 1810.07758): one column, one synapse line per sample of
the series (p = 1,882), one neuron per cluster (q = 7), 1-WTA, the
prototype's RNL neurons and STDP. 13,174 synapses; nothing is cut.

The front end is a 1-D z-normalised series (``NetworkConfig.series_len``),
encoded by ``core.network.encode_series``: samples at least one standard
deviation from the mean spike, the further out the earlier; the rest are
silent. The UCR files are not in the repository, so ``data/series.py``
stands in with seeded series of InlineSkate's shape.

Padded p1 (1,888) is over the fused wave's ``MAX_FUSED_P1``, so
``impl="fused"`` runs this network on the per-layer Pallas kernels
(``kernels/tnn_column.py``, ``kernels/stdp_update.py``).
"""
import dataclasses

from repro.core.column import ColumnConfig
from repro.core.layer import LayerConfig
from repro.core.network import NetworkConfig, with_impl
from repro.configs.tnn_mnist import STDP, WAVE

SERIES_LEN = 1882    # InlineSkate: samples per series
CLUSTERS = 7         # InlineSkate: classes; one neuron each
SERIES = 650         # InlineSkate: 100 train + 550 test series
# The paper states no threshold for this dataset. At 400 every series of
# the seeded stream fires, the winners spread over ticks 4-7, before and
# after learning (benchmark reference, 650 series).
THETA = 400
# The prototype's STDP with search at 1/16, the 4-bit BRV's smallest step.
# The counters sum over a wave's series, six in seven of which a neuron
# loses, each lifting its weights on every spiking line; at the prototype's
# 2/16 that lifts 73% of the weights to w_max and three neurons never win
# (benchmark reference, 300 waves), at 1/16 55% and one.
STDP_SERIES = dataclasses.replace(STDP, mu_search=1.0 / 16.0)


def network_config(series_len: int = SERIES_LEN, clusters: int = CLUSTERS,
                   theta: int = THETA, impl: str = "fused",
                   packed: bool = True) -> NetworkConfig:
    """One column of ``series_len`` synapses and ``clusters`` neurons behind
    a series front end; smaller lengths are for CPU tests."""
    column = ColumnConfig(p=series_len, q=clusters, theta=theta, wave=WAVE,
                          stdp=STDP_SERIES)
    cfg = NetworkConfig(layers=(LayerConfig(1, column),), n_classes=clusters,
                        series_len=series_len, packed=packed)
    return with_impl(cfg, impl)


def train_config(smoke: bool = False, **overrides):
    """Trainer hyper-parameters: waves of 128 series (one from each of 128
    sensor streams) over the dataset's 650 series; ``smoke=True`` shrinks
    the stream and the batch for a CPU container. Keyword overrides are
    applied last."""
    from repro.train.tnn_trainer import TNNTrainConfig

    kw = dict(epochs=1, wave_batch=128, train_size=SERIES, eval_size=256,
              ckpt_dir="/tmp/repro_tnn_ucr_ckpt")
    if smoke:
        kw.update(wave_batch=8, train_size=64, eval_size=32, log_every=2)
    kw.update(overrides)
    return TNNTrainConfig(**kw)
