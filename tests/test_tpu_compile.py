"""The hot-path Pallas kernels compile for a TPU v5e chip.

Interpret mode never applies the chip compiler's rules (block tiling,
vector layouts, scoped VMEM), so these tests compile each kernel at the
widths of the paper's setting (d=192, R=96, R_d=1100, PQ m=32, 2 numeric
fields, query batches of 64) for a described v5e chip, which needs no
chip attached, and check that the compiled program holds the Pallas
custom call.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hop_fused as hop_mod
from repro.kernels import or_scatter as or_mod
from repro.kernels import prune_scan as prune_mod

B, M, K, F, QL, NR = 64, 32, 256, 2, 8, 4
DIM, R, R_DENSE, L_BUILD, CAP = 192, 96, 1100, 128, 2048
N = 1_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("w", [1, 4])
def test_hop_fused_compiles(one_chip, no_persistent_cache, w):
    c = w * (R + R_DENSE)
    text = _compile_text(
        lambda *a: hop_mod.hop_fused(*a, interpret=False), one_chip,
        ((B, c, M), jnp.uint8), ((B, c), jnp.int32), ((B, c, F), jnp.int32),
        ((B, c), jnp.bool_), ((B, M, K), jnp.float32), ((B, 4), jnp.int32),
        ((B, QL), jnp.int32), ((B, NR), jnp.int32), ((B, NR), jnp.int32),
        ((B, NR), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nw,c", [
    ((1 << 20) // 32, 1),             # visited set: entry seeds
    ((1 << 20) // 32, R),             # visited set: W=1 admissions
    ((1 << 20) // 32, 4 * R),         # visited set: W=4 admissions
    ((N + 1 + 31) // 32, CAP),        # merged rare-list table
])
def test_or_scatter_compiles(one_chip, no_persistent_cache, nw, c):
    text = _compile_text(
        lambda w, s: or_mod.or_scatter(w, s, interpret=False), one_chip,
        ((B, nw), jnp.int32), ((B, c), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b,c", [
    (1024, 128),
    (1024, L_BUILD + R),              # build batch: pool ∪ old out-edges
    (64, R + 8),                      # reverse-edge overflow re-prune
])
def test_prune_scan_compiles(one_chip, no_persistent_cache, b, c):
    text = _compile_text(
        lambda d, e: prune_mod.prune_scan(d, e, 1.44, R, interpret=False),
        one_chip, ((b, c), jnp.float32), ((b, c, c), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def mesh(topo):
    from jax.sharding import Mesh
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))


def test_sharded_build_prune_compiles(mesh, no_persistent_cache,
                                      monkeypatch):
    """The sharded build re-prunes overflow rows on mesh-replicated
    arrays; the chip's compiler cannot partition a Pallas kernel itself,
    so the prune must compile under its shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    plan = distributed.ShardPlan(mesh=mesh, shard_axes=("model",))
    prune = distributed._make_replicated_prune(plan, R, 1.2)
    rep = NamedSharding(mesh, P())
    text = prune.lower(
        jax.ShapeDtypeStruct((N, DIM), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((64,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((64, R + 8), jnp.int32, sharding=rep),
    ).compile().as_text()
    assert "tpu_custom_call" in text
