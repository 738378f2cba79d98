"""Launch-layer tests.

In-process: param counts + abstract param trees (no mesh needed).
Subprocess (8 virtual devices, same pattern as test_distributed_gp):
spec-building for every (arch x shape), tiny-mesh end-to-end train-step
compile, sharding-rule divisibility fallback.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.launch.param_count import active_param_count, total_param_count
from repro.launch.specs import abstract_params


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_abstract_params_build(arch):
    cfg = get_config(arch)
    tree = abstract_params(cfg, tp=16)
    assert len(jax.tree.leaves(tree)) > 3


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_count_close_to_eval_shape(arch):
    """Analytic count (used for 6ND roofline terms) within 30% of the
    real parameter tree."""
    cfg = get_config(arch)
    tree = abstract_params(cfg, tp=1)
    real = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
    analytic = total_param_count(cfg)
    assert 0.7 < analytic / real < 1.3, (arch, analytic, real)
    assert active_param_count(cfg) <= analytic


_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS, SHAPES, applicable, get_config
    from repro.launch.mesh import make_test_mesh
    from repro.launch.specs import build_cell
    from repro.sharding.rules import batch_spec, param_specs
    from repro.models.model import init_params
    from repro.training.train_step import make_train_step, train_state_init

    mesh = make_test_mesh((2, 2))

    # 1. every applicable cell builds specs + NamedShardings
    n_cells = 0
    for arch in sorted(ARCHS):
        cfg = get_config(arch)
        for shape in sorted(SHAPES):
            ok, _ = applicable(cfg, shape)
            if not ok:
                continue
            step, args, in_sh, out_sh, donate = build_cell(arch, shape, mesh)
            for s in jax.tree.leaves(in_sh):
                assert isinstance(s, NamedSharding), (arch, shape, s)
            n_cells += 1
    assert n_cells == 32, n_cells  # 40 cells - 8 long_500k full-attn skips

    # 2. tiny end-to-end train compile+run on the 2x2 mesh
    cfg = get_config("internlm2-1.8b").reduced(n_layers=2, vocab=256)
    params = init_params(jax.random.key(0), cfg, tp=2)
    state = train_state_init(params)
    pspec = param_specs(state.params, mesh)
    sspec = type(state)(params=pspec,
                        opt=type(state.opt)(step=P(), mu=pspec, nu=pspec),
                        step=P())
    ssh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                       is_leaf=lambda x: isinstance(x, P))
    bsh = NamedSharding(mesh, batch_spec(mesh, 4))
    tok = jnp.zeros((4, 64), jnp.int32)
    step = make_train_step(cfg, tp=2, lr=1e-3)
    with jax.set_mesh(mesh):
        state2, metrics = jax.jit(
            step, in_shardings=(ssh, bsh, bsh), donate_argnums=(0,)
        )(state, tok, tok)
    assert np.isfinite(float(metrics["loss"]))

    # 3. divisibility fallback
    specs = param_specs({"wq": jnp.zeros((4, 6, 10)), "odd": jnp.zeros((7,))}, mesh)
    assert specs["wq"] == P(None, "data", "model"), specs
    assert specs["odd"] == P(None)
    specs2 = param_specs({"wq": jnp.zeros((4, 5, 6))}, mesh)
    assert specs2["wq"] == P(None, None, "model"), specs2
    print("MESH_OK", n_cells)
    """
)


def test_mesh_cells_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=900,
    )
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "MESH_OK" in r.stdout
