"""Distributed-semantics tests: run a subprocess with 8 fake host devices and
check that the collective (shard_map) mixers agree with the dense reference
mixer (``conftest.reference_mix``), and that a sharded PISCO round equals the
single-device one."""
import os
import subprocess
import sys
import textwrap

import pytest

# slow: excluded from the quick lane; distributed: runs in its own CI job
pytestmark = [pytest.mark.slow, pytest.mark.distributed]

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.mixing import (
        collective_global_mixing, collective_shift_mixing,
    )
    from repro.core.pisco import PiscoConfig, init_state, make_round_fn
    from repro.core.mixing import MixingOps
    from repro.launch.steps import gossip_matrix, mesh_gossip_shifts
    from repro.utils.pytree import tree_agent_mean
    from conftest import reference_mix

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("agents",))
    n = 8
    rng = np.random.default_rng(0)
    spec_tree = {"w": P("agents", None), "b": P("agents")}
    tree = {
        "w": jnp.asarray(rng.normal(size=(n, 6)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(n,)), jnp.float32),
    }
    sharded = jax.device_put(
        tree, {k: NamedSharding(mesh, s) for k, s in spec_tree.items()}
    )

    # ---- global (J) mixing == mean ----
    g = collective_global_mixing(mesh, ("agents",), spec_tree)
    out = jax.jit(g.global_avg)(sharded)
    ref = tree_agent_mean(tree)
    err = max(float(jnp.max(jnp.abs(out[k] - ref[k]))) for k in tree)
    assert err < 1e-6, f"global mixing err {err}"

    # ---- ring gossip (ppermute) == dense circulant matmul ----
    shifts = mesh_gossip_shifts(mesh, ("agents",))
    ops = collective_shift_mixing(mesh, ("agents",), spec_tree, shifts)
    w = gossip_matrix(mesh, ("agents",), shifts)
    assert np.allclose(w.sum(0), 1) and np.allclose(w.sum(1), 1), "not doubly stochastic"
    out = jax.jit(ops.gossip)(sharded)
    ref = reference_mix(tree, w)
    err = max(float(jnp.max(jnp.abs(out[k] - ref[k]))) for k in tree)
    assert err < 1e-6, f"ring gossip err {err}"

    # ---- full PISCO round: sharded collective == dense single-device ----
    d = 6
    data_x = jnp.asarray(rng.normal(size=(n, 32, d)), jnp.float32)
    data_y = jnp.asarray(
        np.where(rng.normal(size=(n, 32)) > 0, 1.0, -1.0), jnp.float32
    )
    def loss_fn(params, batch):
        a, lab = batch
        return jnp.mean(jnp.log1p(jnp.exp(-lab * (a @ params["w"]) - params["b"])))

    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.1, eta_c=0.9, p=0.0)
    x0 = {"w": jnp.zeros((n, d)), "b": jnp.zeros((n,))}
    local = (data_x[None].repeat(2, 0)[:, :, :16], data_y[None].repeat(2, 0)[:, :, :16])
    comm = (data_x[:, 16:], data_y[:, 16:])

    state0 = init_state(loss_fn, x0, comm)
    dense_ops = MixingOps(
        gossip=lambda t: reference_mix(t, w),
        global_avg=tree_agent_mean,
    )
    fn_dense = jax.jit(make_round_fn(loss_fn, cfg, dense_ops, global_round=False))
    s_dense, m_dense = fn_dense(state0, local, comm)

    fn_coll = jax.jit(make_round_fn(loss_fn, cfg, ops, global_round=False))
    state0_sharded = jax.device_put(
        state0,
        type(state0)(
            x={k: NamedSharding(mesh, s) for k, s in spec_tree.items()},
            y={k: NamedSharding(mesh, s) for k, s in spec_tree.items()},
            g={k: NamedSharding(mesh, s) for k, s in spec_tree.items()},
            step=NamedSharding(mesh, P()),
        ),
    )
    s_coll, m_coll = fn_coll(state0_sharded, local, comm)
    for ka in ("x", "y", "g"):
        for kb in ("w", "b"):
            a = getattr(s_dense, ka)[kb]
            b = getattr(s_coll, ka)[kb]
            err = float(jnp.max(jnp.abs(a - b)))
            assert err < 1e-5, f"{ka}/{kb} err {err}"
    print("DISTRIBUTED-OK")
    """
)


def test_collective_mixers_match_dense_in_subprocess():
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(here, "..", "src"), here])
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert "DISTRIBUTED-OK" in proc.stdout


def test_dryrun_small_pair_compiles():
    """End-to-end dry-run of one cheap pair on the 512-device mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "mamba2-370m", "--shape", "decode_32k",
            "--mesh", "single", "--out", "/tmp/dryrun_test",
        ],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert "OK " in proc.stdout
