"""Ahead-of-time compiles of the partition kernels for a TPU v5e.

Interpret mode runs the Pallas kernels on the CPU but cannot show what
Mosaic, the TPU kernel compiler, refuses: unsupported shape casts, slices
off the (8, 128) tiling, blocks past the scoped VMEM. These tests lower
each kernel of the main path at the widths the engines use (tile width
up to 2048) for a described ``v5e:2x2`` topology, with no chip attached,
and check that the compiled program really holds the kernel
(``tpu_custom_call``).

The topology is described inside a fixture only: one process at a time
may load the TPU library, so nothing here may touch it while the module
is imported.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.hype_score.ops import _hype_score_select, _hype_scores
from repro.kernels.kway_refine.ops import _kway_gains


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, *shapes, **static):
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    compiled = fn.lower(*shapes, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _shapes_on(tree, sharding):
    """The shapes of a call's arguments, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            jnp.shape(a), jax.dtypes.canonicalize_dtype(a.dtype),
            sharding=sharding), tree)


class _Captured(BaseException):
    """Ends an engine run at its first device call (after the compile).

    A BaseException, so the engines' retry and fallback handlers, which
    catch ``Exception``, let it through."""


@pytest.mark.parametrize("L", [32, 2048])
def test_hype_scores_compiles(one_chip, L):
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile_for_chip(
        functools.partial(_hype_scores, tile_b=256, interpret=False),
        S((1024, L), jnp.int32), S((16,), jnp.int32))


# (phases G, rows R, pool slots P, fringe slots s, select_k): the
# superstep / device-loop call (fringe-free, t admissions per phase) and
# the stream call (one phase per partition, the micro-batch as its rows)
@pytest.mark.parametrize("G,R,P,s,select_k", [(32, 16, 64, 1, 16),
                                              (32, 64, 1, 16, 1)])
@pytest.mark.parametrize("with_remaining", [False, True])
@pytest.mark.parametrize("L", [32, 2048])
def test_hype_score_select_compiles(one_chip, L, with_remaining, G, R, P,
                                    s, select_k):
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile_for_chip(
        functools.partial(_hype_score_select, select_k=select_k,
                          tile_g=8, interpret=False,
                          with_remaining=with_remaining),
        S((G, R, L), jnp.int32), S((G, s), jnp.int32),
        S((G, R), jnp.float32), S((G, P), jnp.float32))


@pytest.mark.parametrize("k", [32, 128])
def test_kway_gains_compiles(one_chip, k):
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile_for_chip(
        functools.partial(_kway_gains, k=k, tile_b=256, interpret=False),
        S((1024, 2048), jnp.int32), S((1024,), jnp.int32))


# The engines' jitted programs build their shapes (and the sharded
# engine its mesh) from the live backend, so each test runs the engine on
# the CPU up to its first device call and compiles that call's argument
# shapes for the chip instead.

@pytest.fixture(scope="module")
def small_graph():
    from repro.data.synthetic import powerlaw_hypergraph
    return powerlaw_hypergraph(600, 400, seed=11, max_edge=30,
                               max_degree=20)


def test_superstep_program_compiles(one_chip, small_graph, monkeypatch):
    from repro.engines import superstep

    def capture(*args, tile_l, select_k, interpret):
        _compile_for_chip(superstep._pipeline_program(),
                          *_shapes_on(args, one_chip), tile_l=tile_l,
                          select_k=select_k, interpret=False)
        raise _Captured

    monkeypatch.setattr(superstep, "pipeline_superstep_device", capture)
    with pytest.raises(_Captured):
        superstep.hype_superstep_partition(
            small_graph, 16, superstep.SuperstepParams(seed=0))


def test_device_loop_program_compiles(one_chip, small_graph, monkeypatch):
    from repro.core import device_loop
    from repro.engines.device import DeviceParams, hype_device_partition

    def capture(cfg):
        def run(*args):
            _compile_for_chip(
                device_loop._device_loop_program.__wrapped__(
                    cfg._replace(interpret=False)),
                *_shapes_on(args, one_chip))
            raise _Captured
        return run

    monkeypatch.setattr(device_loop, "device_loop_program", capture)
    with pytest.raises(_Captured):
        hype_device_partition(small_graph, 16, DeviceParams(seed=0))


def test_sharded_program_compiles(topo, small_graph, monkeypatch):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.engines import sharded

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices to run the engine up to its call")
    mesh = Mesh(np.asarray(topo.devices), ("shard",))

    def capture(*args, num_devices, group_l, tile_l, select_k, interpret):
        # the program takes its mesh from _sharded_mesh: hand it the
        # described chips for this one build
        monkeypatch.setattr(sharded, "_sharded_mesh", lambda n: mesh)
        program = sharded._sharded_program.__wrapped__(
            num_devices, group_l, tile_l, select_k, False)
        _compile_for_chip(program, *_shapes_on(
            args, NamedSharding(mesh, PartitionSpec())))
        raise _Captured

    monkeypatch.setattr(sharded, "sharded_superstep_device", capture)
    with pytest.raises(_Captured):
        sharded.hype_sharded_partition(
            small_graph, 16, sharded.ShardedParams(seed=0, devices=4))
