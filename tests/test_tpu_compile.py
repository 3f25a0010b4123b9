"""Compile rehearsal for one TPU v5e chip, without the chip.

The TPU compiler is installed with jaxlib; it compiles for a chip that is
*described* (``topologies.get_topology_desc``) rather than attached.
Every Pallas kernel on the model path at real widths, and the COX staged
programs on each backend, are compiled here for one v5e chip, and the
COX sharded programs for the four chips of a 2x2 v5e host: what the
chip's compiler refuses fails here, at no chip time.  A compile that
passes says nothing about results or speed.

The topology is described inside a module-scoped fixture, never while
the module is imported: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from benchmarks.kernels_suite import all_kernels
from repro.core.runtime import build_traceable
from repro.kernels import flash_attention as fa
from repro.kernels import norms, softmax, ssd_scan
from repro.launch.mesh import make_mesh

# granite-moe-1b-a400m attention widths; mamba2-130m SSD widths
GRANITE = dict(S=2048, H=16, Hkv=8, D=64, d=1024)
MAMBA2 = dict(S=2048, H=24, P=64, N=128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_rmsnorm_compiles(one_chip):
    x = _sds(one_chip, (4096, GRANITE["d"]), jnp.bfloat16)
    w = _sds(one_chip, (GRANITE["d"],), jnp.float32)
    assert "tpu_custom_call" in _compile_text(norms.rmsnorm, x, w)


def test_softmax_compiles(one_chip):
    x = _sds(one_chip, (4096, GRANITE["S"]), jnp.float32)
    assert "tpu_custom_call" in _compile_text(softmax.softmax, x)


def test_flash_attention_compiles(one_chip):
    S, H, Hkv, D = (GRANITE[k] for k in ("S", "H", "Hkv", "D"))
    q = _sds(one_chip, (S, H, D), jnp.bfloat16)
    kv = _sds(one_chip, (S, Hkv, D), jnp.bfloat16)
    assert "tpu_custom_call" in _compile_text(fa.flash_attention, q, kv, kv)


@pytest.mark.parametrize("batch", [0, 8])
def test_flash_decode_compiles(one_chip, batch):
    # batch=8: vmapped over decode slots, as the serve step calls it
    S, H, Hkv, D = (GRANITE[k] for k in ("S", "H", "Hkv", "D"))
    lead = (batch,) if batch else ()
    q = _sds(one_chip, lead + (H, D), jnp.bfloat16)
    kv = _sds(one_chip, lead + (S, Hkv, D), jnp.bfloat16)
    n = _sds(one_chip, lead, jnp.int32)
    fn = jax.vmap(fa.flash_decode) if batch else fa.flash_decode
    assert "tpu_custom_call" in _compile_text(fn, q, kv, kv, n)


def test_ssd_scan_compiles(one_chip):
    S, H, P, N = (MAMBA2[k] for k in ("S", "H", "P", "N"))
    x = _sds(one_chip, (S, H, P), jnp.float32)
    a = _sds(one_chip, (S, H), jnp.float32)
    bc = _sds(one_chip, (S, N), jnp.float32)
    assert "tpu_custom_call" in _compile_text(ssd_scan.ssd_scan, x, a, bc, bc)


_COX = {k.name: k for k in all_kernels()}


@pytest.mark.parametrize("backend", ["scan", "vmap"])
@pytest.mark.parametrize("name", ["vectorAdd", "histogram64",
                                  "warpPrefixStats", "gridReduce"])
def test_cox_staged_program_compiles(one_chip, name, backend):
    sk = _COX[name]
    req = sk.kernel.make_request(grid=sk.grid, block=sk.block,
                                 args=sk.make_args(), backend=backend)
    _, fn = build_traceable(req.ck, req.rl, simd=req.simd, chunk=req.chunk)

    def shapes(tree):
        return {k: _sds(one_chip, v.shape, v.dtype) for k, v in tree.items()}

    text = _compile_text(fn, shapes(req.globals_), shapes(req.scalars))
    assert "ENTRY" in text


@pytest.mark.parametrize("name", ["vectorAdd", "histogram64", "gridReduce"])
def test_cox_sharded_program_compiles_on_four_chips(topo, name):
    # one program over the 2x2 mesh: the cross-device merge is a psum
    sk = _COX[name]
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    req = sk.kernel.make_request(grid=sk.grid, block=sk.block,
                                 args=sk.make_args(), mesh=mesh,
                                 backend="sharded")
    _, fn = build_traceable(req.ck, req.rl, simd=req.simd, mesh=mesh,
                            chunk=req.chunk)
    rep = NamedSharding(mesh, P())

    def shapes(tree):
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep)
                for k, v in tree.items()}

    text = _compile_text(fn, shapes(req.globals_), shapes(req.scalars))
    assert "all-reduce" in text
