"""Distribution tests that need multiple devices — run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single real CPU device."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # jit-heavy, excluded from tier-1

_SCRIPT_CIRCULANT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import topology as T
    from repro.core.decavg import mix_pytree, mix_pytree_circulant
    from repro.core.mixing import receive_matrix

    n = 8
    mesh = jax.make_mesh((8,), ("data",))
    graph = T.circulant(n, (1, 2))
    m = jnp.asarray(receive_matrix(graph), jnp.float32)
    params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (n, 16, 4)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (n, 5)),
    }
    dense = mix_pytree(m, params)
    specs = {"w": P("data", None, None), "b": P("data", None)}
    with mesh:
        circ = jax.jit(
            jax.shard_map(
                lambda p: mix_pytree_circulant(p, offsets=(1, 2), axis_name="data"),
                mesh=mesh, in_specs=(specs,), out_specs=specs,
            )
        )(params)
    err = max(float(jnp.abs(dense[k] - circ[k]).max()) for k in params)
    assert err < 1e-5, err
    print("CIRCULANT_OK", err)
    """
)

_SCRIPT_SHARDED_TRAIN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import topology as T
    from repro.core.initialisation import InitConfig, gain_from_graph
    from repro.core.mixing import receive_matrix
    from repro.core.decavg import mix_pytree
    from repro.models.paper_models import init_mlp, mlp_forward, classifier_loss
    from repro.optim import sgd

    n = 8
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    graph = T.random_k_regular(n, 4, seed=0)
    m = jnp.asarray(receive_matrix(graph), jnp.float32)
    opt = sgd(1e-3, 0.5)
    icfg = InitConfig("he_normal", gain_from_graph(graph))
    init_one = lambda k: init_mlp(icfg, k, in_dim=64, hidden=(32, 16), n_classes=4)
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    params = jax.vmap(init_one)(keys)
    opt_state = jax.vmap(opt.init)(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, 8, 64))
    y = jax.random.randint(jax.random.PRNGKey(2), (n, 8), 0, 4)

    def loss_fn(p, xx, yy):
        return classifier_loss(mlp_forward(p, xx), yy)

    def step(params, opt_state, x, y):
        loss, grads = jax.vmap(jax.value_and_grad(loss_fn))(params, x, y)
        upd, opt_state = jax.vmap(opt.update)(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda a, u: a + u, params, upd)
        params = mix_pytree(m, params)
        opt_state = jax.vmap(opt.init)(params)
        return params, opt_state, loss.mean()

    pspec = jax.tree_util.tree_map(lambda l: P("data", *([None] * (l.ndim - 1))), params)
    shard = lambda t, s: jax.tree_util.tree_map(
        lambda l, sp: jax.device_put(l, NamedSharding(mesh, sp)), t, s,
        is_leaf=lambda z: hasattr(z, "shape"))
    with mesh:
        params = shard(params, pspec)
        compiled = jax.jit(step)
        p2, o2, loss = compiled(params, opt_state, x, y)
        p3, o3, loss2 = compiled(p2, o2, x, y)
    assert np.isfinite(float(loss)) and np.isfinite(float(loss2))
    # loss decreases across two rounds on the same batch
    assert float(loss2) < float(loss)
    print("SHARDED_TRAIN_OK", float(loss), float(loss2))
    """
)


def _run(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=420
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_circulant_schedule_equals_dense_mixing():
    """The ppermute schedule must equal the dense receive-matrix product on a
    circulant graph — the beyond-paper optimisation is semantics-preserving."""
    assert "CIRCULANT_OK" in _run(_SCRIPT_CIRCULANT)


def test_sharded_training_round_runs_and_learns():
    """A full DFL round jits and runs under a (data, model) mesh."""
    assert "SHARDED_TRAIN_OK" in _run(_SCRIPT_SHARDED_TRAIN)
