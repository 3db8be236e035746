"""Compile-only checks of the main-path kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so each
kernel is lowered and compiled here for a *described* v5e chip at real
widths — smollm-360m's ``(49152, 960)`` bf16 embedding and one
``(960, 2560)`` MLP leaf, cohort 256.  Mosaic refuses what the Pallas
interpreter accepts (unsupported casts, unaligned blocks, scalar stores
to VMEM, primitives with no TPU lowering), so these tests catch a kernel
that would fail on the chip without spending chip time.  Nothing runs:
results are checked by the differential suites and on the chip.

The topology is described inside a module fixture, never at import: the
TPU library admits one process at a time, and every pytest worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.core.prng import Distribution
from repro.core.projection import ProjectionMode
from repro.kernels import ops
from repro.kernels.reconstruct_apply import fused_reconstruct_apply
from repro.kernels.tune import PALLAS_BLOCKS

COHORT = 256
EMBED = (49152, 960)     # smollm-360m token embedding
MLP = (960, 2560)        # one smollm-360m w_up leaf
TUNE = (512, 2048)       # the leaf the kernel benchmark tunes on


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _tree(sharding, *shapes):
    return {f"w{i}": jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for i, s in enumerate(shapes)}


def _mode(k):
    return ProjectionMode.BLOCK if k > 1 else ProjectionMode.FULL


def _fused(dist, k):
    return functools.partial(ops.server_update_fused, distribution=dist,
                             mode=_mode(k), use_pallas=True, interpret=False)


def _reconstruct(k):
    return functools.partial(ops.server_update_kernel,
                             distribution=Distribution.RADEMACHER,
                             mode=_mode(k), interpret=False)


# (case, leaves, k, round-close fn or None for the client-side kernels).
# Masked BLOCK cases use the MLP leaf: the embedding is past the float32
# block-mask domain (2**24 elements) that ops.leaf_block_bounds enforces.
CASES = [
    ("fused-rademacher-k1", (EMBED, MLP), 1, _fused(Distribution.RADEMACHER, 1)),
    ("fused-gaussian-k1", (EMBED, MLP), 1, _fused(Distribution.GAUSSIAN, 1)),
    ("fused-rademacher-k4-masked", (MLP,), 4, _fused(Distribution.RADEMACHER, 4)),
    ("reconstruct-unmasked", (EMBED, MLP), 1, _reconstruct(1)),
    ("reconstruct-masked", (MLP,), 4, _reconstruct(4)),
    ("projection-k1", (EMBED, MLP), 1, None),
    ("projection-k4", (MLP,), 4, None),
    ("qsgd", (EMBED, MLP), 1, None),
]


@pytest.mark.parametrize("case,shapes,k,close", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, case, shapes, k, close):
    tree = _tree(one_chip, *shapes)
    if close is not None:
        rs = jax.ShapeDtypeStruct((COHORT, k), jnp.float32, sharding=one_chip)
        seeds = jax.ShapeDtypeStruct((COHORT,), jnp.uint32, sharding=one_chip)
        lowered = jax.jit(close).lower(tree, rs, seeds)
    elif case.startswith("projection"):
        lowered = jax.jit(functools.partial(
            ops.project_tree_kernel, seed=jnp.uint32(7), num_blocks=k,
            mode=_mode(k), interpret=False)).lower(tree)
    else:
        lowered = jax.jit(functools.partial(
            ops.qsgd_roundtrip_kernel, seed=jnp.uint32(3),
            interpret=False)).lower(tree)
    text = lowered.compile().as_text()
    # One Mosaic kernel per leaf, and no host callback (the interpreter).
    assert text.count("tpu_custom_call") == len(shapes), case
    assert "callback" not in text, case


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", PALLAS_BLOCKS,
                         ids=[f"{br}x{bc}" for br, bc in PALLAS_BLOCKS])
def test_every_tuner_tile_compiles_for_v5e(one_chip, block, dtype):
    """Every tile the TPU autotuner may pick fits Mosaic's VMEM budget,
    its (16, br, bc) float32 product scratch included; gaussian has the
    largest kernel body of the families."""
    x = jax.ShapeDtypeStruct(TUNE, dtype, sharding=one_chip)
    rs = jax.ShapeDtypeStruct((COHORT, 1), jnp.float32, sharding=one_chip)
    seeds = jax.ShapeDtypeStruct((COHORT,), jnp.uint32, sharding=one_chip)
    close = functools.partial(fused_reconstruct_apply, leaf_tag=0, scale=1.0,
                              distribution="gaussian", block=block,
                              use_pallas=True, interpret=False)
    text = jax.jit(close).lower(x, seeds, rs).compile().as_text()
    assert text.count("tpu_custom_call") == 1, block
    assert "callback" not in text, block
