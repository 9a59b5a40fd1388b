"""Compile the main-path kernels for a described TPU v5e, at real sizes.

Nothing runs: the TPU compiler, which is installed even where no chip
is attached, compiles each kernel for `v5e:2x2` and raises what the chip
would raise (Mosaic layout errors, scoped-VMEM overflows).  Interpret
mode cannot see these.  The topology is described inside a fixture, so
only the test worker that runs this file loads the TPU library.

Shapes are the real ones: the wavefront grids of the paper sweep's
layout buckets (64 kb arrays lay out as tall (2142, 19) or wide
(122, 1090) routing grids), with and without the router's target
cells, the routing program of the largest 64 kb bucket (which takes the
kernel with its targets), and the rank kernels at the service's
population and at 4096.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.eda import batched_flow
from repro.kernels.acim_matmul.kernel import acim_matmul_kernel
from repro.kernels.maze_route.kernel import (goal_route_kernel,
                                             goal_wavefront_kernel,
                                             wavefront_kernel)
from repro.kernels.pareto_dom.kernel import (dominance_matrix_kernel,
                                             nds_rank_kernel)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text    # the Pallas kernel is in the program
    return text


@pytest.mark.parametrize("shape", [(8, 344, 128), (8, 2144, 128),
                                   (1, 128, 1152), (32, 1032, 1024)])
def test_wavefront_kernel_compiles(one_chip, shape):
    grid = _spec(one_chip, shape, jnp.int8)
    _compile(wavefront_kernel.lower(grid, grid))


GOAL_SHAPES = [(8, 344, 128), (8, 2144, 128), (1, 128, 1152),
               (32, 1032, 1024)]


@pytest.mark.parametrize("shape", GOAL_SHAPES)
def test_goal_wavefront_kernel_compiles(one_chip, shape):
    # two target cells per grid, by scalar prefetch
    grid = _spec(one_chip, shape, jnp.int8)
    goals = _spec(one_chip, (shape[0], 2, 2), jnp.int32)
    _compile(goal_wavefront_kernel.lower(grid, grid, goals))


@pytest.mark.parametrize("shape", GOAL_SHAPES)
def test_goal_route_kernel_compiles(one_chip, shape):
    # the router's form: the goal wavefront, then the targets' backtraces
    # walked in the kernel through the field it holds in VMEM
    grid = _spec(one_chip, shape, jnp.int8)
    goals = _spec(one_chip, (shape[0], 2, 2), jnp.int32)
    _compile(goal_route_kernel.lower(grid, grid, goals))


def test_route_program_compiles_with_kernel(one_chip, monkeypatch):
    # the largest 64 kb bucket of the paper sweep: 8 specs on (2142, 19)
    # grids, 2 * 16 column nets + 64 row-driver nets
    b, gh, gw, n = 8, 2142, 19, 96
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    nets = batched_flow.NetBatch(
        _spec(one_chip, (b, n, 2), jnp.int32),
        _spec(one_chip, (b, n, 2, 2), jnp.int32),
        _spec(one_chip, (b, n, 2), jnp.bool_),
        _spec(one_chip, (b, n), jnp.bool_))
    occ0 = _spec(one_chip, (b, gh, gw), jnp.int32)
    text = _compile(batched_flow._route_program.lower(occ0, nets, capacity=4,
                                                      use_kernel=None))
    # the backtrace runs inside the kernel: the only XLA loop left is
    # the scan over net slots, not the walk's nested while loops
    assert len(re.findall(r"= .*\bwhile\(", text)) == 1


@pytest.mark.parametrize("p", [256, 4096])
def test_dominance_matrix_kernel_compiles(one_chip, p):
    _compile(dominance_matrix_kernel.lower(_spec(one_chip, (4, p),
                                                 jnp.float32)))


@pytest.mark.parametrize("p", [256, 4096])
def test_nds_rank_kernel_compiles(one_chip, p):
    _compile(nds_rank_kernel.lower(_spec(one_chip, (p, 4), jnp.float32)))


@pytest.mark.parametrize("n", [64, 256])
def test_acim_matmul_kernel_compiles(one_chip, n):
    x = _spec(one_chip, (256, 1024), jnp.float32)
    w = _spec(one_chip, (1024, 256), jnp.float32)
    _compile(acim_matmul_kernel.lower(x, w, n=n, b_adc=5))
