"""The benchmark's door into the program's (data x model) mesh path.

Beside :mod:`tnnbench.program`, which builds the network and its unsharded
step: the program's host mesh, its sharded train step, the shardings that
step's state and wave batch live on, and the program's compile counter.
"""
from __future__ import annotations


def mesh(data: int, model: int):
    """The program's ("data", "model") mesh over the first ``data * model``
    devices, as ``launch/train.py --mesh DxM`` builds it."""
    from repro.launch.mesh import make_host_mesh_2d

    return make_host_mesh_2d(data, model)


def train_step(net, mesh):
    """The program's jitted learning wave sharded over ``mesh``:
    batch rows over "data", sites over "model", the STDP counters summed
    over "data" before the apply."""
    from repro.core.network import make_train_step

    return make_train_step(net, mesh=mesh)


def shardings(net, mesh):
    """(state, x): the ``NamedSharding`` pytree of the train state and the
    ``NamedSharding`` of a wave batch, from the program's own specs. The
    sites have to divide over the model axis: a padded step takes its
    state and batch unsharded."""
    from jax.sharding import NamedSharding

    from repro.core.network import network_mesh_spec

    spec = network_mesh_spec(net, mesh)
    if spec.site_pad:
        raise ValueError(f"{spec.n_cols} sites do not divide over "
                         f"{spec.n_model} model shards")
    state = spec.state_spec()
    params = NamedSharding(mesh, state["params"])
    return ({"params": {f"layer_{i:02d}": params
                        for i in range(len(net.layers))},
             "rng": NamedSharding(mesh, state["rng"]),
             "wave": NamedSharding(mesh, state["wave"])},
            NamedSharding(mesh, spec.x_spec()))


def compile_count() -> int:
    """Executables the process has compiled or loaded so far."""
    from repro.utils.tracing import compile_count as count

    return count()
