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
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.prng import Distribution
from repro.core.projection import ProjectionMode
from repro.kernels import ops
from repro.kernels.reconstruct_apply import fused_plan, fused_reconstruct_apply
from repro.kernels.tune import PALLAS_BLOCKS

COHORT = 256
EMBED = (49152, 960)     # smollm-360m token embedding
MLP = (960, 2560)        # one smollm-360m w_up leaf
TUNE = (512, 2048)       # the leaf the kernel benchmark tunes on
# smollm-360m's stacked wk and w_down leaves as the close sees them: the
# fused kernel tiles both rows-along-lanes, with 320 and 960 columns.
WK = (30720, 320)
W_DOWN = (81920, 960)


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
    ("fused-lanes-rademacher-k1", (WK, W_DOWN), 1,
     _fused(Distribution.RADEMACHER, 1)),
    ("fused-lanes-gaussian-k1", (WK, W_DOWN), 1, _fused(Distribution.GAUSSIAN, 1)),
    ("fused-lanes-rademacher-k4-masked", (WK,), 4,
     _fused(Distribution.RADEMACHER, 4)),
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
    if case.startswith("fused-lanes"):
        # Rows along lanes: no pad columns, so no pad in the program.
        assert all(fused_plan(*s).lanes_rows for s in shapes), case
        assert " pad(" not in text, case


def _tile_compiles(one_chip, shape, block, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    rs = jax.ShapeDtypeStruct((COHORT, 1), jnp.float32, sharding=one_chip)
    seeds = jax.ShapeDtypeStruct((COHORT,), jnp.uint32, sharding=one_chip)
    close = functools.partial(fused_reconstruct_apply, leaf_tag=0, scale=1.0,
                              distribution="gaussian", block=block,
                              use_pallas=True, interpret=False)
    text = jax.jit(close).lower(x, seeds, rs).compile().as_text()
    assert text.count("tpu_custom_call") == 1, block
    assert "callback" not in text, block


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", PALLAS_BLOCKS,
                         ids=[f"{br}x{bc}" for br, bc in PALLAS_BLOCKS])
def test_every_tuner_tile_compiles_for_v5e(one_chip, block, dtype):
    """Every tile the TPU autotuner may pick fits Mosaic's VMEM budget,
    its float32 product scratch of 16 tiles included; gaussian has the
    largest kernel body of the families.  The tuned leaf closes
    rows-along-lanes."""
    _tile_compiles(one_chip, TUNE, block, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", PALLAS_BLOCKS,
                         ids=[f"{br}x{bc}" for br, bc in PALLAS_BLOCKS])
@pytest.mark.parametrize("shape", [W_DOWN, MLP], ids=["w_down", "mlp"])
def test_every_tuner_tile_compiles_in_both_orientations(one_chip, shape,
                                                        block, dtype):
    """The same tiles on a 960-column leaf rows-along-lanes (240- or
    480-column tiles) and on a leaf that closes as it lies (960 rows,
    padded to the tile)."""
    assert fused_plan(*shape).lanes_rows == (shape == W_DOWN)
    _tile_compiles(one_chip, shape, block, dtype)


def test_close_kernel_keeps_its_name_under_any_jit_or_scope(one_chip):
    """The benchmark finds the fused close in a device trace by the
    custom call's HLO name (``close_kernel`` of the close cell's
    traffic); the name stays under a jit of another name and inside a
    named scope."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmarks", "chip", "traffic", "close.c256.json")
    with open(path) as f:
        kernel = re.compile(json.load(f)["close_kernel"])
    tree = _tree(one_chip, MLP, (320, 960))
    rs = jax.ShapeDtypeStruct((COHORT, 1), jnp.float32, sharding=one_chip)
    seeds = jax.ShapeDtypeStruct((COHORT,), jnp.uint32, sharding=one_chip)
    close = _fused(Distribution.RADEMACHER, 1)

    def close_step(params, rs, seeds):
        with jax.named_scope("fed.close"):
            return close(params, rs, seeds)

    text = jax.jit(close_step).lower(tree, rs, seeds).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == len(tree)
    assert all(kernel.search(c) for c in calls), calls
