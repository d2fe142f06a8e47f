"""The program's tracing (``repro.utils.tracing``): device scopes in the
compiled step programs, the pallas_calls' names, the trainer's and the
engine's host spans, and the compile counter. CPU only; a profile here has
no device ops, so what a TPU trace reads is checked in ``tnn_bench``."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tnn_ucr
from repro.configs.tnn_mnist import (crop_field, launcher_network_config,
                                     network_config)
from repro.core.network import (
    classify,
    encode_images,
    encode_series,
    init_train_state,
    make_online_step,
    make_superbatch_step,
    make_train_step,
    params_from_tree,
)
from repro.core.stdp import default_stabilize_table
from repro.data.mnist_like import digits
from repro.kernels import ops, padding, tnn_wave
from repro.kernels.wta import wta_pallas
from repro.serve.tnn_engine import ClassifyRequest, TNNEngine
from repro.train.tnn_trainer import TNNTrainConfig, TNNTrainer
from repro.utils import tracing

from proptest import sharded_subprocess

SITES, B = 4, 8

# Fusions outside every tnn.* scope that a step program may hold, each with
# why: the step's own bookkeeping and what lax.scan's lowering makes. On the
# chip their time is what unscoped_us_per_wave.train reads.
UNSCOPED = {
    r'op_name="jit\(step\)/add"':
        "the wave counter's advance, one scalar add",
    r'op_name="jit\(step\)/broadcast_in_dim"':
        "the buffer lax.scan stacks the per-wave outputs into",
    r"^%wrapped_broadcast[.\d]* = u32\[\d+,2\]\S* fusion\((?!.*metadata)":
        "the buffer lax.scan stacks the per-wave keys into; the CPU "
        "compiler gives it no metadata",
}


def _computations(text):
    """{name: [instruction lines]} of a compiled HLO module's text, the
    entry computation under "ENTRY"."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?(%[\w.\-]+) ", line)
        if m and line.rstrip().endswith("{"):
            name = "ENTRY" if m.group(1) else m.group(2)
            comps[name] = []
        elif name and line.startswith("  "):
            comps[name].append(line.strip().removeprefix("ROOT "))
    return comps


def _scopes_of(text):
    """(every tnn.* scope in the module's op names, the fusions and custom
    calls of its entry computation that carry none)."""
    seen, bare = set(), []
    for name, lines in _computations(text).items():
        for l in lines:
            on = re.search(r'op_name="([^"]*)"', l)
            found = re.findall(r"tnn\.\w+", on.group(1)) if on else []
            seen.update(found)
            if (name == "ENTRY" and not found
                    and re.search(r" (fusion|custom-call)\(", l)
                    and not any(re.search(p, l) for p in UNSCOPED)):
                bare.append(l[:200])
    return seen, bare


def _step_and_args(factory, impl):
    cfg = launcher_network_config(SITES, depth=2, impl=impl)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    x = jnp.full((B, SITES, cfg.layers[0].column.p), 3, jnp.uint8)
    if factory == "superbatch":
        return make_superbatch_step(cfg, donate=False), (state, x[None].repeat(2, 0))
    if factory == "online":
        ps = params_from_tree(state["params"], cfg)
        return make_online_step(cfg, donate=False), (ps, state, x)
    return make_train_step(cfg, donate=False), (state, x)


@pytest.mark.parametrize("factory", ["train", "superbatch", "online"])
@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_step_programs_run_under_their_scopes(impl, factory):
    step, args = _step_and_args(factory, impl)
    seen, bare = _scopes_of(step.lower(*args).compile().as_text())
    assert not bare, "fusions outside every tnn.* scope:\n" + "\n".join(bare)
    assert {tracing.UNIFORMS, tracing.WAVE, tracing.APPLY} <= seen
    assert tracing.PSUM not in seen          # no mesh, no all-reduce


def test_psum_scope_under_a_data_mesh():
    sharded_subprocess("""
        import re
        import jax, jax.numpy as jnp
        from repro.configs.tnn_mnist import launcher_network_config
        from repro.core.network import init_train_state, make_train_step
        from repro.launch.mesh import make_host_mesh_2d

        cfg = launcher_network_config(4, depth=2, impl="fused")
        step = make_train_step(cfg, mesh=make_host_mesh_2d(4, 1), donate=False)
        st = init_train_state(jax.random.PRNGKey(0), cfg)
        x = jnp.full((8, 4, 32), 3, jnp.uint8)
        text = step.lower(st, x).compile().as_text()
        reduces = [l for l in text.splitlines() if " all-reduce(" in l]
        assert reduces and all("tnn.psum" in l for l in reduces), reduces
        print("PSUM_SCOPED", len(reduces))
    """, marker="PSUM_SCOPED")


@pytest.mark.parametrize("which", ["encode", "encode_series", "classify"])
def test_off_path_layers_are_scoped(which):
    cfg = network_config(sites=SITES)
    if which == "encode":
        fn, args = (lambda im: encode_images(im, cfg)), (jnp.zeros((2, 7, 7)),)
    elif which == "encode_series":
        cfg = tnn_ucr.network_config(series_len=40, clusters=3, theta=40)
        fn, args = (lambda s: encode_series(s, cfg)), (jnp.zeros((2, 40)),)
    else:
        fn = lambda z, vt: classify(z, vt, 8)                          # noqa: E731
        args = (jnp.zeros((2, SITES, 10), jnp.uint8),
                jnp.ones((SITES, 10, 10)))
    seen, _ = _scopes_of(jax.jit(fn).lower(*args).compile().as_text())
    assert seen == {tracing.CLASSIFY if which == "classify"
                    else tracing.ENCODE}


def _pallas_names(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    names = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    return names


def test_every_pallas_call_has_a_stable_name():
    cfg = launcher_network_config(SITES, depth=2, impl="fused")
    plan = padding.network_plan(cfg, B)
    x = jnp.zeros((B, SITES, 32), jnp.uint8)
    ws = tuple(jnp.zeros((SITES, l.column.p, l.column.q), jnp.int8)
               for l in cfg.layers)
    us = tuple((jnp.ones((SITES, B, l.column.p, l.column.q)),) * 2
               for l in cfg.layers)
    z = jnp.zeros((B, SITES, 12), jnp.int32)
    table = default_stabilize_table(7)
    assert _pallas_names(lambda: tnn_wave.wave_train(
        x, ws, us, plan=plan)) == ["tnn_wave_train"]
    assert _pallas_names(lambda: tnn_wave.wave_forward(
        x, ws, plan=plan)) == ["tnn_wave_forward"]
    assert _pallas_names(lambda: ops.layer_forward_fused(
        x, ws[0], theta=6, T=8)) == ["tnn_column_forward"]
    assert _pallas_names(lambda: ops.layer_stdp_fused(
        ws[0], x, z, us[0][0], us[0][1], table=table)) == ["tnn_stdp_update"]
    assert _pallas_names(lambda: wta_pallas(
        z[0], block_b=SITES, interpret=True)) == ["tnn_wta"]


def test_compile_counter_counts_new_shapes_only():
    f = jax.jit(lambda a: a * 2 + 1)
    a3, a4 = jnp.asarray(np.ones(3)), jnp.asarray(np.ones(4))
    n = tracing.compile_count()
    f(a3).block_until_ready()
    assert tracing.compile_count() == n + 1
    f(a3).block_until_ready()                 # a repeat: no executable
    assert tracing.compile_count() == n + 1
    f(a4).block_until_ready()                 # a new shape compiles
    assert tracing.compile_count() == n + 2
    assert tracing.compile_counts()["jit(<lambda>)"] >= 2


def _span_events(log_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("tnn.")]


def test_trainer_waves_are_profiler_steps(tmp_path):
    cfg = network_config(sites=4, theta1=6, theta2=2, impl="direct")
    tcfg = TNNTrainConfig(wave_batch=4, train_size=12, eval_size=4,
                          ckpt_dir=str(tmp_path / "ck"), log_every=1000,
                          metrics_path=str(tmp_path / "m.jsonl"))
    with jax.profiler.trace(str(tmp_path / "prof")):
        out = TNNTrainer(cfg, tcfg).run()
    assert out["final_wave"] == 3 and "waves_per_s" not in out
    spans = _span_events(tmp_path / "prof")
    waves = [s for s in spans if s[0] == tracing.TRAIN_WAVE]
    assert len(waves) == 3
    for _, a, b in waves:
        inside = [n for n, s, e in spans if a <= s and e <= b]
        assert inside.count(tracing.STAGE) == inside.count(tracing.STEP) \
            == inside.count(tracing.BLOCK) == 1
    names = {n for n, _, _ in spans}
    assert {tracing.EVAL, tracing.CHECKPOINT} <= names
    recs = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
    assert [r["wave"] for r in recs] == [1, 2, 3]
    assert recs[0]["compiles"] >= 1 and recs[1]["compiles"] == 0
    assert all("dt_s" in r and "waves_per_s" not in r for r in recs)


def test_engine_spans_and_compiles(tmp_path):
    cfg = network_config(sites=4, theta1=6, theta2=2)
    params = [jnp.asarray(w) for w in params_from_tree(
        init_train_state(jax.random.PRNGKey(0), cfg)["params"], cfg)]
    imgs, labels = digits(8, seed=1)
    imgs = crop_field(imgs, 4)
    eng = TNNEngine(cfg, params, n_slots=4, impl="direct", online_stdp=True)
    eng.fit(imgs, labels)

    def serve():
        for uid in range(6):
            eng.submit(ClassifyRequest(uid=uid, image=imgs[uid]))
        eng.run_until_done()
        return eng.stats()

    assert serve().compiles > 0              # the first waves compile
    eng.reset()
    with jax.profiler.trace(str(tmp_path)):
        st = serve()
        eng.hot_swap()
    assert st.compiles == 0 and st.requests == 6     # warm: nothing new
    assert eng.stats_by_version()[0].compiles is None
    names = {n for n, _, _ in _span_events(tmp_path)}
    assert {tracing.ADMIT, tracing.STAGE, tracing.DISPATCH, tracing.RETIRE,
            tracing.HOT_SWAP} <= names
    assert np.isfinite(st.p50_ms)
