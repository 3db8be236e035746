"""Distribution layer: spec validity + 8-device end-to-end equivalence.

Runs in a subprocess with ``--xla_force_host_platform_device_count=8``
(the test session itself must keep 1 device for everything else).
"""
import json
import os
import subprocess
import sys

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_E2E = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import get_arch
from repro.launch.train import FLRunConfig, make_train_step
from repro.sharding.rules import param_specs, named, input_specs_sharding

from repro.core.compat import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
arch = get_arch("smollm-360m", reduced=True)
params = arch.init(jax.random.PRNGKey(0))
fl = FLRunConfig(num_virtual_clients=2, local_steps=2, local_lr=0.05)
step = make_train_step(arch, fl)

rng = np.random.RandomState(0)
tokens = jnp.asarray(rng.randint(0, 64, size=(8, 32)).astype(np.int32))
batch = {"tokens": tokens, "labels": tokens}

# single-device reference
p1, m1 = jax.jit(step)(params, batch, jnp.int32(0))

# sharded run
pspec = param_specs(jax.tree_util.tree_map(
    lambda w: jax.ShapeDtypeStruct(w.shape, w.dtype), params), mesh)
pshard = named(mesh, pspec)
bshard = named(mesh, input_specs_sharding(batch, mesh, 8))
with jax.set_mesh(mesh):
    p8, m8 = jax.jit(step, in_shardings=(pshard, bshard, None),
                     out_shardings=(pshard, None))(params, batch, jnp.int32(0))

err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
          for a, b in zip(jax.tree_util.tree_leaves(p1),
                          jax.tree_util.tree_leaves(p8)))
print("RESULT", json.dumps({"err": err, "loss1": float(m1["loss"]),
                            "loss8": float(m8["loss"])}))
"""


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The pjit'd FedScalar round computes the same update as 1 device."""
    code = "import json\n" + _E2E
    out = subprocess.run([sys.executable, "-c", code, _SRC],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT "):])
    assert res["err"] < 2e-2, res          # bf16-free reduced cfg → tight-ish
    assert abs(res["loss1"] - res["loss8"]) < 1e-3, res


def test_param_specs_divisibility():
    """Every assigned spec dim divides the leaf dim on the 16×16 mesh."""
    import jax
    from repro.configs.registry import ARCH_IDS, get_arch
    from repro.sharding.rules import param_specs

    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)

    sizes = {"data": 16, "model": 16}
    for name in ARCH_IDS:
        arch = get_arch(name)
        shapes = arch.param_shapes()
        specs = param_specs(shapes, FakeMesh(), arch.cfg.num_experts)
        for (path, leaf), (_, spec) in zip(
                jax.tree_util.tree_leaves_with_path(shapes),
                jax.tree_util.tree_leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(x, tuple))):
            for dim, ax in zip(leaf.shape, spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axes:
                    n *= sizes[a]
                assert dim % n == 0, (name, path, leaf.shape, spec)
