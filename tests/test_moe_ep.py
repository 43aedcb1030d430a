"""shard_map expert-parallel MoE == einsum baseline (8 host devices).

Runs in a subprocess because jax locks the device count at first init
and the rest of the suite must see ONE device.
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model, make_batch

mesh = make_mesh((2, 4), ('data', 'model'))
for arch in ('qwen3_moe_235b', 'arctic_480b'):
    base = reduced(get_config(arch))
    base = dataclasses.replace(
        base, moe=dataclasses.replace(base.moe,
                                      capacity_factor=float(base.moe.n_experts)))
    batch = make_batch(base, 4, 16)
    m0 = build_model(base, dtype=jnp.float32)
    p0 = m0.init(jax.random.PRNGKey(0))
    ref, aux0 = jax.jit(m0.forward)(p0, batch)
    m1 = build_model(base, dtype=jnp.float32, mesh=mesh)
    x_sh = jax.device_put(batch, jax.tree.map(
        lambda _: NamedSharding(mesh, P('data', None)), batch))
    with mesh:
        out, aux1 = jax.jit(m1.forward)(p0, x_sh)
    err = float(np.max(np.abs(np.asarray(ref) - np.asarray(out))))
    assert err < 1e-3, (arch, err)
    print(arch, 'OK', err)
print('ALL-OK')
"""


@pytest.mark.slow
def test_shard_map_moe_matches_einsum_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ALL-OK" in r.stdout


def test_shard_map_moe_refuses_explicit_mesh():
    """jax.make_mesh's default Explicit axes break the model's GSPMD
    code outside the shard_map; the kernel factory says so up front."""
    import jax
    from jax.sharding import AxisType

    from repro.sharding.moe_ep import make_shard_map_moe
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Explicit,) * 2)
    with pytest.raises(ValueError, match="Auto"):
        make_shard_map_moe(mesh)
