"""The benchmark's one door into the program under test.

Everything the harness takes from the program goes through here: the
network built from a configuration file (and checked against it), its
train step and state, and its image encoder. The reference, the traffic
and the weights never come from here.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def import_program() -> None:
    """Put the checkout's ``src/`` on the path; fail when it is not there."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: no program source at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every executable so that a second
    run in the checkout compiles nothing."""
    import jax
    from repro.launch.runtime import enable_compile_cache as enable

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable()


def network(cfg):
    """The program's network for a configuration file, built by its cascade
    builder with the file's widths and thresholds, and refused unless every
    size matches the file."""
    import dataclasses

    from repro.configs.tnn_mnist import deep_config

    prog = cfg["program"]
    net = deep_config(cfg["sites"], widths=tuple(cfg["widths"]),
                      thetas=tuple(cfg["thetas"]), impl=prog["impl"])
    net = dataclasses.replace(net, packed=prog["packed"])
    stated = {
        "layers": [(cfg["sites"], p, q, th) for p, q, th in _layers(cfg)],
        "T": 1 << cfg["time_bits"], "w_max": (1 << cfg["weight_bits"]) - 1,
        "stdp": (cfg["stdp"]["mu_capture"], cfg["stdp"]["mu_backoff"],
                 cfg["stdp"]["mu_search"], tuple(cfg["stdp"]["stabilize"]),
                 cfg["stdp"]["batch_reduce"]),
        "field": (cfg["field_side"], cfg["field_side"]),
        "patch_k": cfg["patch_k"], "n_classes": cfg["n_classes"],
        "impl": prog["impl"], "packed": prog["packed"],
    }
    col = net.layers[0].column
    built = {
        "layers": [(l.n_cols, l.column.p, l.column.q, l.column.theta)
                   for l in net.layers],
        "T": col.wave.T, "w_max": col.wave.w_max,
        "stdp": (col.stdp.mu_capture, col.stdp.mu_backoff,
                 col.stdp.mu_search, col.stdp.table_tuple(col.wave),
                 col.stdp.batch_reduce),
        "field": tuple(net.image_hw), "patch_k": net.patch_k,
        "n_classes": net.n_classes, "impl": col.impl, "packed": net.packed,
    }
    for k, v in stated.items():
        if built[k] != v:
            raise ValueError(f"program builds {k}={built[k]!r}, the "
                             f"configuration states {v!r}")
    if any(l.column.wave != col.wave or l.column.stdp != col.stdp
           or l.column.impl != col.impl for l in net.layers):
        raise ValueError("layers of the built network differ in wave, STDP "
                         "or backend")
    return net


def _layers(cfg):
    p = 2 * cfg["patch_k"] ** 2
    for q, theta in zip(cfg["widths"], cfg["thetas"]):
        yield p, q, theta
        p = q


def train_step(net):
    """The program's jitted learning wave, ``step(state, x) -> (state, z)``
    (its state buffers donated), as ``TNNTrainer`` builds it."""
    from repro.core.network import make_train_step

    return make_train_step(net)


def train_state(params, key):
    """The state the program's train step carries: per-layer weights, the
    STDP stream key and the wave counter."""
    import jax.numpy as jnp

    return {"params": {f"layer_{i:02d}": w for i, w in enumerate(params)},
            "rng": key, "wave": jnp.asarray(0, jnp.int32)}


def weights(state):
    """The per-layer weights of a train state, on the host."""
    import jax

    ps = state["params"]
    return [jax.device_get(ps[k]) for k in sorted(ps)]


def encode(frames, net):
    """The program's encoder (DoG, on/off patches, spike times), as
    ``TNNTrainer``'s stream runs it, on the host."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.network import encode_images

    return np.asarray(encode_images(jnp.asarray(frames), net))
