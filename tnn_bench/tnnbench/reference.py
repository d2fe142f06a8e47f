"""Plain reference of a TNN cascade: encode, RNL forward + WTA, STDP.

Written from the paper's equations and the configuration file alone, in
straightforward ``jax.numpy`` and ``numpy``; it imports nothing of the
program and takes nothing the program made. ``cfg`` is the parsed
configuration file (``tnn_bench/configs/<name>.json``).

- Encode: DoG contrast (pixel minus its 3x3 mean, edge-padded), gain 3,
  on/off half-wave rectification, k x k sliding patches taken by slicing,
  spike time ``round((1 - v) * T)``; on and off interleaved per pixel.
- Forward: body potential ``V[t, j] = sum_i min(max(t - x_i, 0), w_ij)``
  at every wave position, first ``t`` with ``V >= theta`` (else ``T``),
  1-WTA with ties to the lowest index. Same-site cascade: layer i+1 reads
  layer i's post-WTA times at the same site.
- STDP (batched "sum" counters): capture ``x <= z`` (both fire) +1 with
  probability ``mu_capture * F[w]``, search (only ``x`` fires) +1 with
  ``mu_search``, backoff (``x > z`` both fire, or only ``z``) -1 with
  ``mu_backoff * F[w]``; Bernoulli draws are float32 uniforms, counters sum
  over the batch and apply once, saturating to ``[0, w_max]``.
- Random bits: per wave the stream key splits into (next key, wave key);
  the wave key splits per layer, each layer key per site, and each site
  draws ``uniform((2, B, p, q))`` float32 (up, down).

``train(..., low=True)`` is the control: the Bernoulli compares in
bfloat16, the nearest precision below the float32 the configuration
states.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def geometry(cfg) -> List[Tuple[int, int, int]]:
    """Per layer (p, q, theta); layer 1's fan-in is the on/off patch."""
    p = 2 * cfg["patch_k"] ** 2
    out = []
    for q, theta in zip(cfg["widths"], cfg["thetas"]):
        out.append((p, q, theta))
        p = q
    return out


def wave_T(cfg) -> int:
    return 1 << cfg["time_bits"]


def w_max(cfg) -> int:
    return (1 << cfg["weight_bits"]) - 1


def crop(images: np.ndarray, cfg) -> np.ndarray:
    """Centred crop of (N, 28, 28) frames to the field the site grid sees."""
    side = cfg["field_side"]
    H, W = images.shape[1:]
    r0, c0 = (H - side) // 2, (W - side) // 2
    return images[:, r0:r0 + side, c0:c0 + side]


# -- encode -----------------------------------------------------------------


@jax.jit
def _dog(x):
    pad = jnp.pad(x, ((0, 0), (1, 1), (1, 1)), mode="edge")
    s = jnp.zeros_like(x)
    for dr in (-1, 0, 1):
        for dc in (-1, 0, 1):
            s = s + pad[:, 1 + dr:1 + dr + x.shape[1], 1 + dc:1 + dc + x.shape[2]]
    return x - s / 9.0


def _encode(images, k, T):
    c = _dog(images) * 3.0
    N, H, W = c.shape
    oh, ow = H - k + 1, W - k + 1
    planes = []
    for v in (jnp.clip(c, 0.0, 1.0), jnp.clip(-c, 0.0, 1.0)):
        pix = [v[:, r:r + oh, s:s + ow].reshape(N, oh * ow)
               for r in range(k) for s in range(k)]
        planes.append(jnp.round((1.0 - jnp.stack(pix, -1)) * T))
    return jnp.stack(planes, -1).reshape(N, oh * ow, 2 * k * k).astype(jnp.uint8)


_encode_jit = jax.jit(_encode, static_argnums=(1, 2))


def encode(images: np.ndarray, cfg) -> np.ndarray:
    """(N, side, side) float32 in [0, 1] -> (N, sites, 2 k^2) uint8 times."""
    return np.asarray(_encode_jit(jnp.asarray(images, jnp.float32),
                                  cfg["patch_k"], wave_T(cfg)))


# -- forward ----------------------------------------------------------------


def layer_forward(x: jax.Array, w: jax.Array, theta: int, T: int) -> jax.Array:
    """x (B, C, p) times, w (C, p, q) -> post-WTA times (B, C, q) int32."""
    t = jnp.arange(T, dtype=jnp.int32)
    ramp = jnp.maximum(t[:, None] - x.astype(jnp.int32)[..., None, :], 0)
    resp = jnp.minimum(ramp[..., None], w.astype(jnp.int32)[None, :, None])
    V = resp.sum(axis=-2)                                   # (B, C, T, q)
    crossed = V >= theta
    z = jnp.where(crossed.any(axis=-2), jnp.argmax(crossed, axis=-2), T)
    first = jnp.argmin(z, axis=-1)
    won = jnp.arange(z.shape[-1]) == first[..., None]
    return jnp.where(won & (z < T), z, T).astype(jnp.int32)


def _forward(x, ws, thetas, T):
    zs = []
    for w, theta in zip(ws, thetas):
        x = layer_forward(x, w, theta, T)
        zs.append(x)
    return zs


_forward_jit = jax.jit(_forward, static_argnums=(2, 3))


def forward(x, ws: Sequence, cfg, block: int = 16) -> List[np.ndarray]:
    """Per-layer post-WTA times of every row of ``x``, ``block`` rows at a
    time so the (B, C, T, q) potentials fit; returned on the host."""
    thetas = tuple(theta for _, _, theta in geometry(cfg))
    ws = [jnp.asarray(w) for w in ws]
    outs = [[] for _ in ws]
    for off in range(0, x.shape[0], block):
        zs = _forward_jit(jnp.asarray(x[off:off + block]), ws, thetas,
                          wave_T(cfg))
        for o, z in zip(outs, zs):
            o.append(np.asarray(z))
    return [np.concatenate(o) for o in outs]


# -- STDP -------------------------------------------------------------------


def stabilize_table(cfg) -> jax.Array:
    return jnp.asarray(cfg["stdp"]["stabilize"], jnp.float32)


def _stdp_net(w, x, z, uu, ud, table, mus, T, low):
    """One layer's batch-summed counters. w (C, p, q); x (B, C, p);
    z (B, C, q); uu/ud (C, B, p, q)."""
    mu_c, mu_b, mu_s = mus
    xs = x.astype(jnp.int32).transpose(1, 0, 2)[..., :, None]  # (C, B, p, 1)
    zs = z.astype(jnp.int32).transpose(1, 0, 2)[..., None, :]  # (C, B, 1, q)
    xf, zf = xs < T, zs < T
    capture = xf & zf & (xs <= zs)
    backoff = (xf & zf & (xs > zs)) | (~xf & zf)
    search = xf & ~zf
    f = table[w.astype(jnp.int32)][:, None]                    # (C, 1, p, q)
    p_up = capture * (mu_c * f) + search * jnp.float32(mu_s)
    p_dn = backoff * (mu_b * f)
    if low:
        uu, ud = uu.astype(jnp.bfloat16), ud.astype(jnp.bfloat16)
        p_up, p_dn = p_up.astype(jnp.bfloat16), p_dn.astype(jnp.bfloat16)
    inc = (uu < p_up).astype(jnp.int32).sum(axis=1)
    dec = (ud < p_dn).astype(jnp.int32).sum(axis=1)
    return inc - dec


def _uniforms(key, C, B, p, q):
    keys = jax.random.split(key, C)
    return jax.vmap(
        lambda k: jax.random.uniform(k, (2, B, p, q), dtype=jnp.float32))(keys)


def _train_wave(ws, x, key, table, mus, thetas, T, wm, low):
    key, sub = jax.random.split(key)
    lkeys = jax.random.split(sub, len(ws))
    new_ws, z = [], None
    for w, theta, lk in zip(ws, thetas, lkeys):
        z = layer_forward(x, w, theta, T)
        C, p, q = w.shape
        u = _uniforms(lk, C, x.shape[0], p, q)
        net = _stdp_net(w, x, z, u[:, 0], u[:, 1], table, mus, T, low)
        new_ws.append(jnp.clip(w.astype(jnp.int32) + net, 0, wm).astype(jnp.int8))
        x = z
    return new_ws, z, key


_train_wave_jit = jax.jit(_train_wave, static_argnums=(4, 5, 6, 7, 8))


@jax.jit
def advance(key, n):
    """The stream key after ``n`` waves: split ``n`` times, keeping the
    next key each time."""
    return jax.lax.fori_loop(0, n, lambda i, k: jax.random.split(k)[0], key)


def train(ws: Sequence, xs: Sequence, key, cfg, low: bool = False):
    """Run ``len(xs)`` learning waves from weights ``ws`` and stream key
    ``key``. Returns (last-layer times per wave, final weights), on the
    host."""
    s = cfg["stdp"]
    mus = (float(s["mu_capture"]), float(s["mu_backoff"]),
           float(s["mu_search"]))
    thetas = tuple(theta for _, _, theta in geometry(cfg))
    ws = [jnp.asarray(w) for w in ws]
    table = stabilize_table(cfg)
    zs = []
    for x in xs:
        ws, z, key = _train_wave_jit(ws, jnp.asarray(x), key, table, mus,
                                     thetas, wave_T(cfg), w_max(cfg), low)
        zs.append(np.asarray(z))
    return zs, [np.asarray(w) for w in ws]
