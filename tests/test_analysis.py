"""launch analysis layers: jaxpr cost counter, trip-aware HLO walker,
collective byte accounting, cpu-upcast parser."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.launch.hlo_analysis import (collective_stats, group_size,
                                       parse_collective_line)
from repro.launch.hlo_graph import (collective_stats_trip_aware,
                                    while_census)
from repro.launch.jaxpr_cost import cost_of

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _mesh():
    return jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))


def test_jaxpr_cost_exact_matmul():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    c = cost_of(lambda a, b: a @ b, a, b)
    assert c.dot_flops == 2 * 64 * 128 * 32


def test_jaxpr_cost_scan_multiplies():
    W = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)

    def f(ws, x):
        return jax.lax.scan(lambda h, w: (h @ w, None), x, ws)[0]
    c = cost_of(f, W, x)
    assert c.dot_flops == 10 * 2 * 4 * 64 * 64


def test_jaxpr_cost_counts_remat():
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(x):
        g = jax.checkpoint(lambda y: jnp.sum((y @ y) ** 2))
        return jax.grad(g)(x)
    base = cost_of(lambda x: jax.grad(
        lambda y: jnp.sum((y @ y) ** 2))(x), x)
    rem = cost_of(f, x)
    assert rem.dot_flops >= base.dot_flops    # recompute visible


def test_trip_aware_collectives():
    mesh = _mesh()
    W = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)

    def f(ws, x):
        def body(h, w):
            y = h @ w
            y = jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P(None, "model")))
            h2 = y @ w.T
            h2 = jax.lax.with_sharding_constraint(
                h2, NamedSharding(mesh, P()))
            return h2, None
        return jax.lax.scan(body, x, ws)[0]

    comp = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P(None, None, "model")),
        NamedSharding(mesh, P()))).lower(W, x).compile()
    hlo = comp.as_text()
    flat = collective_stats(hlo)
    aware = collective_stats_trip_aware(hlo)
    assert flat.count_by_kind.get("all-reduce") == 1
    assert aware.count_by_kind.get("all-reduce") == 10
    assert aware.bytes_by_kind["all-reduce"] == \
        10 * flat.bytes_by_kind["all-reduce"]
    trips = dict(while_census(hlo))
    assert 10 in trips.values()


def test_group_size_parsing():
    assert group_size("replica_groups=[16,32]<=[512]") == 32
    assert group_size("replica_groups={{0,4},{1,5}}") == 2
    assert group_size("no groups here") == 1


def test_parse_collective_conversions():
    line = ("%all-gather.1 = bf16[32,128]{1,0} all-gather(%x), "
            "replica_groups=[2,16]<=[32], dimensions={0}")
    base, nbytes = parse_collective_line(line)
    assert base == "all-gather"
    assert nbytes == 32 * 128 * 2 // 16       # result / group size
    line2 = ("%reduce-scatter.3 = f32[8,16]{1,0} reduce-scatter(%y), "
             "replica_groups=[1,4]<=[4], dimensions={0}")
    base2, nbytes2 = parse_collective_line(line2)
    assert base2 == "reduce-scatter"
    assert nbytes2 == 8 * 16 * 4 * 4          # result * group size
