"""Plain reference of the time-series clustering TNN (arXiv 2102.09200): a
series encoded to one spike per sample, feeding one column of
``series_len`` synapses and ``widths[0]`` neurons, 1-WTA, batch-summed STDP.

Written from the paper and the configuration file alone, in ``numpy`` and
``jax.numpy``; it imports nothing of the program. The layer's forward and
STDP are :mod:`tnnbench.reference`'s, imported, so the two configurations'
references share one body of math.

- Encode (the configuration's ``assumed`` mapping): a sample ``x`` of the
  z-normalised series spikes at the tick that counts the levels
  ``series_floor + k * series_step``, ``k = 0 .. T-1``, lying above
  ``|x|``; a sample below ``series_floor`` counts ``T`` and never spikes.
- Geometry: layer 1's fan-in is the series length, one site; a deeper
  layer's is the previous width, at the same site.
- Random bits: as :mod:`tnnbench.reference` (per wave the stream key splits
  into (next, wave key), the wave key per layer, each layer key per site;
  each site draws ``uniform((2, B, p, q))`` float32).

``train(..., low=True)`` is the control: the Bernoulli compares in
bfloat16, the nearest precision below the float32 the configuration
states; ``encode(..., dtype=jnp.bfloat16)`` is the encoder's fault.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from tnnbench import reference, seeds
from tnnbench.reference import advance, w_max, wave_T  # noqa: F401


def geometry(cfg) -> List[Tuple[int, int, int]]:
    """Per layer (p, q, theta); layer 1's fan-in is the series length."""
    p = cfg["series_len"]
    out = []
    for q, theta in zip(cfg["widths"], cfg["thetas"]):
        out.append((p, q, theta))
        p = q
    return out


def make_weights(cfg, seed: int):
    """The run's initial weights: per layer int8 uniform in [0, w_max], made
    on the device in one jitted call from the seed's weights stream, as
    ``common.make_weights`` makes an image configuration's."""
    import jax

    shapes = [(cfg["sites"], p, q) for p, q, _ in geometry(cfg)]
    wm = w_max(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.randint(k, s, 0, wm + 1, dtype=jnp.int8)
                for k, s in zip(keys, shapes)]

    return make(jax.random.PRNGKey(seeds.jax_seed(seed, seeds.WEIGHTS)))


def encode(series: np.ndarray, cfg, dtype=np.float32) -> np.ndarray:
    """(N, L) z-normalised series -> (N, 1, L) uint8 spike times, compared
    in ``dtype``."""
    T = wave_T(cfg)
    mag = np.abs(np.asarray(series, np.float32).astype(dtype))
    levels = [cfg["series_floor"] + k * cfg["series_step"] for k in range(T)]
    t = np.zeros(mag.shape, np.int64)
    for level in levels:
        t += mag < np.asarray(level, dtype)
    return t[:, None, :].astype(np.uint8)


def forward(x, ws: Sequence, cfg, block: int = 16) -> List[np.ndarray]:
    """Per-layer post-WTA times of every row of ``x``, ``block`` rows at a
    time; returned on the host."""
    thetas = tuple(theta for _, _, theta in geometry(cfg))
    ws = [jnp.asarray(w) for w in ws]
    outs = [[] for _ in ws]
    for off in range(0, x.shape[0], block):
        zs = reference._forward_jit(jnp.asarray(x[off:off + block]), ws,
                                    thetas, wave_T(cfg))
        for o, z in zip(outs, zs):
            o.append(np.asarray(z))
    return [np.concatenate(o) for o in outs]


def train(ws: Sequence, xs: Sequence, key, cfg, low: bool = False):
    """Run ``len(xs)`` learning waves from weights ``ws`` and stream key
    ``key``. Returns (last-layer times per wave, final weights), on the
    host."""
    s = cfg["stdp"]
    mus = (float(s["mu_capture"]), float(s["mu_backoff"]),
           float(s["mu_search"]))
    thetas = tuple(theta for _, _, theta in geometry(cfg))
    ws = [jnp.asarray(w) for w in ws]
    table = reference.stabilize_table(cfg)
    zs = []
    for x in xs:
        ws, z, key = reference._train_wave_jit(
            ws, jnp.asarray(x), key, table, mus, thetas, wave_T(cfg),
            w_max(cfg), low)
        zs.append(np.asarray(z))
    return zs, [np.asarray(w) for w in ws]
