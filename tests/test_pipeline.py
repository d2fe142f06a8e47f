"""Pipeline parallelism (GPipe over the pod axis): equivalence to sequential
execution, forward and backward. Needs >1 device, so it runs in a
subprocess with forced host devices (the main pytest process is 1-device)."""
import textwrap

from proptest import sharded_subprocess

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.sharding.pipeline import pipeline_apply

    mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
    R, B, D = 8, 16, 32
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w": 0.3 * jax.random.normal(k1, (R, D, D)),
              "b": 0.01 * jax.random.normal(k2, (R, D))}
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def layer(pr, h):
        return jnp.tanh(h @ pr["w"] + pr["b"])

    # sequential reference
    def seq(params, x):
        def body(c, pr):
            return layer(pr, c), None
        out, _ = jax.lax.scan(body, x, params)
        return out

    ref = seq(params, x)
    out = pipeline_apply(layer, params, x, mesh, n_micro=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    print("forward OK")

    # gradient equivalence (pipelined backward through ppermute)
    def loss_seq(p):
        return jnp.sum(seq(p, x) ** 2)
    def loss_pipe(p):
        return jnp.sum(pipeline_apply(layer, p, x, mesh, n_micro=4) ** 2)
    g1 = jax.grad(loss_seq)(params)
    g2 = jax.grad(loss_pipe)(params)
    for kk in g1:
        np.testing.assert_allclose(np.asarray(g2[kk]), np.asarray(g1[kk]),
                                   rtol=5e-4, atol=5e-5)
    print("backward OK")

    # jit + different microbatch counts
    for nm in (2, 8, 16):
        o = jax.jit(lambda p, xx: pipeline_apply(layer, p, xx, mesh, n_micro=nm))(params, x)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), rtol=2e-5, atol=2e-5)
    print("jit/microbatch OK")
""")


def test_pipeline_equivalence_subprocess():
    r = sharded_subprocess(SCRIPT, devices=4, timeout=420)
    assert "forward OK" in r.stdout
    assert "backward OK" in r.stdout
    assert "jit/microbatch OK" in r.stdout
