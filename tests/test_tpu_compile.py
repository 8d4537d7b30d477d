"""Compile the device programs for a TPU v5e without one.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached (``jax.experimental.topologies``).  These
tests lower the programs of the chip path for one v5e chip, and the sharded
loop for a 2x2 mesh, so a program the chip's compiler would refuse fails
here at no chip time.  Nothing runs: results and times need the chip
(``python chip_smoke.py``).

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these compiles
(entries written for a described chip cannot be read back without one).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import fused, sharded
from repro.kernels import split_score


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_x64 = jax.config.jax_enable_x64
    was_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", True)   # as the fused engine runs
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_x64", was_x64)
    jax.config.update("jax_enable_compilation_cache", was_cache)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return tuple(jax.ShapeDtypeStruct(shape, dt, sharding=sharding(shape))
                 for shape, dt in specs)


def _loop_args(n, p, S):
    """Argument specs of ``fused._build_loop``'s loop for S rows."""
    f, i, b = jnp.float64, jnp.int64, jnp.bool_
    return [((S, n + 1), f), ((S, p), f), ((), f), ((), f), ((S, n + 1), f),
            ((S, p), i), ((S,), b), ((S,), f), ((S,), f), ((S,), b),
            ((S, n, 5), f), ((S,), i), ((S,), i), ((S,), f), ((S,), i),
            ((S,), f), ((S, p), i)]


def test_split_score_2way_compiles_at_n160(one_chip):
    A, K = 64, 159
    f32, i32 = jnp.float32, jnp.int32
    args = _shapes(lambda s: one_chip,
                   ((A, 1), f32), ((A, K), f32), ((A, 1), f32),
                   ((A, 1), f32), ((A, K), f32), ((A, 1), f32), ((), f32),
                   ((A, 1), f32), ((A, 1), f32), ((), f32), ((A,), i32))
    compiled = split_score._score2_call.lower(*args, False, 8, 128).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_split_score_3way_compiles_at_n160(one_chip):
    A, K = 16, 159 * 158 // 2
    f32, i32 = jnp.float32, jnp.int32
    args = _shapes(lambda s: one_chip,
                   ((A, 3, K), f32), ((A, 3, K), f32), ((A, 3, K), f32),
                   ((A, 6, 3), f32), ((A, 1, 1), f32), ((), f32),
                   ((A,), i32))
    compiled = split_score._score3_call.lower(*args, False, 8, 128).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_device_kernels_score_in_float32(one_chip):
    """Out of interpret mode the wrappers hand the kernels float32 and
    int32 ``need`` bounds, which is what Mosaic accepts under x64."""
    assert split_score._score_dtype(False, jnp.float64) == jnp.float32
    assert split_score._score_dtype(True, jnp.float64) == jnp.float64


def test_fused_loop_compiles_every_bucket(one_chip):
    # the chip runs the certified loop (TPU float64 is not IEEE); n=6 has
    # three k=1 span buckets, so the lax.switch has several branches
    n, p, k, S = 6, 4, 1, 8
    assert len(fused.bucket_sizes(n, k)) == 3
    _init, loop = fused._build_loop(n, p, k, min(n - 1, p - 1), S,
                                    fused.TPU_BAND)
    args = _shapes(lambda s: one_chip, *_loop_args(n, p, S))
    compiled = jax.jit(loop).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_fused_3way_loop_compiles(one_chip):
    n, p, k, S = 5, 4, 2, 8
    assert len(fused.bucket_sizes(n, k)) == 2
    _init, loop = fused._build_loop(n, p, k, min(n - 1, p - 1), S,
                                    fused.TPU_BAND)
    args = _shapes(lambda s: one_chip, *_loop_args(n, p, S))
    jax.jit(loop).lower(*args).compile()


def test_sharded_loop_compiles_on_2x2_mesh(topo):
    n, p, k, S_local = 5, 4, 2, 8
    mesh = Mesh(np.array(topo.devices), ("i",))
    _init, loop = fused._build_loop(n, p, k, min(n - 1, p - 1), S_local,
                                    fused.TPU_BAND)
    wrapped = sharded._shard_wrap(loop, fused.TPU_BAND, mesh=mesh)
    args = _shapes(lambda s: NamedSharding(mesh, P() if not s else P("i")),
                   *_loop_args(n, p, S_local * len(topo.devices)))
    compiled = jax.jit(wrapped).lower(*args).compile()
    out = compiled.output_shardings   # the certified loop's one record
    assert len(out.device_set) == len(topo.devices) == 4
