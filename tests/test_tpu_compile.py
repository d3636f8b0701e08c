"""Compile-only checks of the main-path kernels for the TPU v5e target.

Each test lowers a kernel or jitted program at the width the controllers
run it and compiles it for a *described* v5e chip (no chip attached):
the TPU compiler refuses what interpret mode accepts — a tile not aligned
to the vreg layout, more VMEM than a kernel may use, a program too large
for HBM.  A compile that passes is not a run: ``chip_smoke.py`` runs the
same kernels on the chip and checks them against ``kernels/ref.py``.

The topology is described inside a module-scoped fixture, never while a
module imports: only one process at a time may load the TPU library, and
every pytest-xdist worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import EC2_CATALOG_ADJUSTED, SpaceEncoding, make_ec2_space
from repro.core.annealing import _fleet_nd_jit, _fleet_shard_jit
from repro.kernels.sizing_latency import sizing_latency
from repro.kernels.surrogate_distance import fused_interp, pairwise_sqdist

#: The store's full capacity bucket (``DeviceMeasurementStore.cap`` at the
#: default capacity of 8192 measurements).
M_CAP = 8192
#: A refit's query block: one 1024-state window bucket.
Q = 1024
#: Tenants of the largest trace-fleet replay, and its chain steps.
FLEET_T, FLEET_STEPS = 1024, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _scale_feature_dim() -> int:
    """Feature width of the 1,179,648-state surrogate-scale space."""
    from benchmarks.surrogate_scale import scale_problem
    space, _ = scale_problem()
    return SpaceEncoding.from_space(space).feature_dim


def _trace_fleet_space():
    from benchmarks.trace_fleet import CORES
    return make_ec2_space(EC2_CATALOG_ADJUSTED, core_counts=CORES)


@pytest.mark.parametrize("kind", ["idw", "rbf"])
def test_fused_interp_compiles_at_store_capacity(one_chip, kind):
    F = _scale_feature_dim()
    fn = jax.jit(lambda xq, xm, y, w: fused_interp(
        xq, xm, y, w, kind=kind, interpret=False))
    compiled = fn.lower(_f32((Q, F), one_chip), _f32((M_CAP, F), one_chip),
                        _f32((M_CAP,), one_chip),
                        _f32((M_CAP,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pairwise_sqdist_compiles(one_chip):
    F = _scale_feature_dim()
    fn = jax.jit(lambda xq, xm: pairwise_sqdist(xq, xm, interpret=False))
    compiled = fn.lower(_f32((Q, F), one_chip),
                        _f32((Q, F), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sizing_latency_compiles_at_small_spec_width(one_chip):
    from benchmarks.container_sizing import small_spec
    spec = small_spec()
    B, K = spec.space.size(), spec.dag.n_tiers
    assert (B, K) == (65_536, 8)
    fn = jax.jit(lambda lam, mu, repl, w, adj: sizing_latency(
        lam, mu, repl, w, adj, c_max=spec.c_max, interpret=False))
    compiled = fn.lower(
        _f32((B, K), one_chip), _f32((B, K), one_chip),
        _f32((B, K), one_chip), _f32((B, K), one_chip),
        jax.ShapeDtypeStruct((K, K), jnp.bool_, sharding=one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fleet_args(space, sharding, key_sharding):
    size = space.size()
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), FLEET_T))
    return (jax.ShapeDtypeStruct(keys.shape, keys.dtype,
                                 sharding=key_sharding),
            _f32((FLEET_T, size), sharding),
            _f32((FLEET_T, FLEET_STEPS), sharding),
            jax.ShapeDtypeStruct((FLEET_T, len(space.shape)), jnp.int32,
                                 sharding=sharding),
            _f32((FLEET_T, size), sharding))


def test_fleet_chains_compile_at_1024_tenants(one_chip):
    space = _trace_fleet_space()
    assert space.size() == 64
    keys, tables, taus, inits, extra = _fleet_args(space, one_chip, one_chip)
    enc = space.encoded()
    compiled = _fleet_nd_jit.lower(
        keys, tables, None, taus, inits, extra, shape=enc.shape,
        categorical=enc.categorical, dynamic=False, noise_std=0.0,
        per_chain=True).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_fleet_chains_compile_over_four_chips(topo):
    """The ``--four-chips`` path of chip_smoke.py: the chain axis split
    over a 4-device ``"tenants"`` mesh, each output sharded the same way
    and no collective between the chains."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("tenants",))
    rows = NamedSharding(mesh, PartitionSpec("tenants"))
    space = _trace_fleet_space()
    enc = space.encoded()
    _, tables, taus, inits, extra = _fleet_args(space, rows, rows)
    kd = jax.ShapeDtypeStruct((FLEET_T, 2), jnp.uint32, sharding=rows)
    fn = _fleet_shard_jit(mesh, enc.shape, enc.categorical, 0.0, False, True)
    compiled = fn.lower(kd, tables, taus, inits, extra).compile()
    for s in compiled.output_shardings:
        assert s.is_equivalent_to(rows, 2) or s.is_equivalent_to(rows, 3)
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text
