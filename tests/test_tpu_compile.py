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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from benchmarks.kernels_suite import all_kernels
from repro.core.runtime import build_traceable
from repro.configs import registry
from repro.kernels import flash_attention as fa
from repro.kernels import moe_decode, norms, softmax, ssd_decode, ssd_scan
from repro.launch.mesh import make_mesh
from repro.launch.serve import prefill_program
from repro.models import lm
from repro.parallel import steps

# granite-moe-1b-a400m attention widths; mamba2-130m SSD widths;
# granite-4.0-h-small's Mamba2 (a run of 5 layers' state stack)
GRANITE = dict(S=2048, H=16, Hkv=8, D=64, d=1024)
MAMBA2 = dict(S=2048, H=24, P=64, N=128)
HYBRID_SSM = dict(L=5, B=1, H=128, P=64, N=128)


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


# (layers, experts, d, f, top-k, tokens): granite-moe-1b-a400m at batch 1
# and 3; deepseek-moe-16b, whose expert slabs are sliced along f to fit
# VMEM (two layers: its whole stack would not fit one chip's HBM)
@pytest.mark.parametrize("L,E,d,f,k,tokens", [
    (24, 32, 1024, 512, 8, 1), (24, 32, 1024, 512, 8, 3),
    (2, 64, 2048, 1408, 6, 1)])
def test_moe_decode_experts_compiles(one_chip, L, E, d, f, k, tokens):
    n = tokens * k
    args = (_sds(one_chip, (1,), jnp.int32), _sds(one_chip, (n,), jnp.int32),
            _sds(one_chip, (tokens, d), jnp.bfloat16),
            _sds(one_chip, (n,), jnp.float32),
            _sds(one_chip, (L, E, d, f), jnp.bfloat16),
            _sds(one_chip, (L, E, d, f), jnp.bfloat16),
            _sds(one_chip, (L, E, f, d), jnp.bfloat16))
    text = _compile_text(moe_decode.moe_decode_experts, *args)
    assert "moe_decode_experts" in text


def test_serve_step_reads_only_the_chosen_experts(topo):
    """The granite-moe-1b-a400m serve step at batch 1, as the server
    builds it on one chip: each of the 24 layers' MoE is the kernel, fed
    the whole (24, 32, ...) expert stacks, and nothing in the step holds
    one layer's 32 experts (a slice of the stack the kernel cannot fuse
    would copy them before every call)."""
    cfg = registry.get("granite-moe-1b-a400m")
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    step, bundle = steps.make_serve_step(cfg, mesh, backend="pallas")
    rules = bundle["rules"]

    def shapes(specs, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            specs, shardings, is_leaf=lambda s: hasattr(s, "abstract"))

    cache = lm.cache_specs(cfg, 1, 512)
    tok = jax.ShapeDtypeStruct((1,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    text = _compile_text(step, shapes(bundle["specs"], bundle["param_sh"]),
                         shapes(cache, rules.tree_shardings(cache)), tok, tok)
    calls = [line for line in text.splitlines()
             if "moe_decode_experts" in line and "custom-call(" in line]
    assert calls
    for line in calls:
        assert "bf16[24,32,1024,512]" in line and "bf16[24,32,512,1024]" in line
    assert not re.search(r"bf16\[(1,)?32,(1024,512|512,1024)\]", text)


def test_ssd_scan_compiles(one_chip):
    S, H, P, N = (MAMBA2[k] for k in ("S", "H", "P", "N"))
    x = _sds(one_chip, (S, H, P), jnp.float32)
    a = _sds(one_chip, (S, H), jnp.float32)
    bc = _sds(one_chip, (S, N), jnp.float32)
    assert "tpu_custom_call" in _compile_text(ssd_scan.ssd_scan, x, a, bc, bc)


def test_ssd_decode_compiles(one_chip):
    L, B, H, P, N = (HYBRID_SSM[k] for k in ("L", "B", "H", "P", "N"))
    args = (_sds(one_chip, (L, B, H, P, N), jnp.float32),
            _sds(one_chip, (1,), jnp.int32), _sds(one_chip, (B,), jnp.bool_),
            _sds(one_chip, (B, H, P), jnp.bfloat16),
            _sds(one_chip, (B, H), jnp.float32),
            _sds(one_chip, (H,), jnp.float32),
            _sds(one_chip, (B, N), jnp.bfloat16),
            _sds(one_chip, (B, N), jnp.bfloat16),
            _sds(one_chip, (H,), jnp.float32))
    # the stack is updated in place: donated, as the serve step's scan
    # carry is
    text = jax.jit(ssd_decode.ssd_decode, donate_argnums=(0,)).lower(
        *args).compile().as_text()
    assert "ssd_decode" in text


def test_hybrid_serve_step_takes_the_kernels_on_whole_stacks(topo):
    """granite-4.0-h-small's serve step (10 layers: Mamba2 runs of 5 and
    4, one attention layer) as the server builds it on one chip: each
    Mamba2 run's scan calls ``ssd_decode`` on its whole f32 state stack
    (5 + 4 = 9 layers), kept in HBM, and each run's MoE calls
    ``moe_decode_experts`` on the run's whole stacks of the 18 held
    experts (5 + 1 + 4 = 10 layers)."""
    cfg = registry.get("granite-4.0-h-small")
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    step, bundle = steps.make_serve_step(cfg, mesh, backend="pallas")
    rules = bundle["rules"]

    def shapes(specs, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            specs, shardings, is_leaf=lambda s: hasattr(s, "abstract"))

    cache = lm.cache_specs(cfg, 1, 512)
    tok = jax.ShapeDtypeStruct((1,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    text = _compile_text(step, shapes(bundle["specs"], bundle["param_sh"]),
                         shapes(cache, rules.tree_shardings(cache)), tok, tok)
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    ssd = [line for line in calls if re.match(r"\s*%ssd_decode", line)]
    stacks = sorted(int(m) for line in ssd
                    for m in re.findall(r"f32\[(\d+),1,128,64,128\]\{[^}]*\}\)", line))
    assert stacks == [4, 5]
    moe = [line for line in calls if re.match(r"\s*%moe_decode_experts", line)]
    held = sorted(int(m) for line in moe
                  for m in re.findall(r"bf16\[(\d+),18,768,4096\]", line))
    assert held == [1, 4, 5]


def _serve_programs(topo, arch, ctx, prompt):
    """The serve step and a ``prompt``-token prefill of ``arch`` at batch
    1 and context ``ctx``, as the server builds them on one chip (the
    cache donated), compiled: their HLO texts."""
    cfg = registry.get(arch)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    step, bundle = steps.make_serve_step(cfg, mesh, backend="pallas")
    rules = bundle["rules"]

    def shapes(specs, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            specs, shardings, is_leaf=lambda s: hasattr(s, "abstract"))

    params = shapes(bundle["specs"], bundle["param_sh"])
    cache = lm.cache_specs(cfg, 1, ctx)
    cache = shapes(cache, rules.tree_shardings(cache))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, P()))

    def text(fn, *args):
        return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()

    return (text(step, params, cache, ints(1), ints(1)),
            text(prefill_program(step), params, cache, ints(prompt, 1),
                 ints(1), ints(1)))


# (arch, context, a stack's shape, as the kernel sees it (the TPU keeps
# Dh = 64 with the positions minor), one layer of it, one sequence's
# heads-major).  granite-4.0-h-small at 2048: at 4096 its wk and wv,
# (1, d_model = 4096, 8, 128), have one layer's cache shape
CACHE_STACKS = [
    ("granite-moe-1b-a400m", 4096, "24,1,4096,8,64", "24,1,8,64,4096",
     ["1,1,4096,8,64", "1,4096,8,64", "8,4096,64", "1,1,8,64,4096",
      "1,8,64,4096", "8,64,4096"]),
    ("granite-4.0-h-small", 2048, "1,1,2048,8,128", "1,1,2048,8,128",
     ["1,2048,8,128", "8,2048,128", "1,1,8,128,2048", "1,8,128,2048"]),
]


@pytest.mark.parametrize("arch,ctx,stack,seen,cuts", CACHE_STACKS,
                         ids=[a for a, *_ in CACHE_STACKS])
def test_decode_attention_works_on_the_whole_cache_stacks(topo, arch, ctx,
                                                          stack, seen, cuts):
    """The serve step and a prefill program: every attention layer's
    ``decode_attn_cache`` call is handed the run's whole K and V stacks in
    the order the chip stores them, and no copy, slice, update, transpose
    or fusion makes a whole stack, one layer's cache or one sequence's
    heads-major cache."""
    made = re.compile(r"= bf16\[({})\]\S* ({})\(".format(
        "|".join([stack, seen] + cuts),
        "copy|copy-start|copy-done|dynamic-slice|dynamic-update-slice|"
        "transpose|fusion"))
    for text in _serve_programs(topo, arch, ctx, 16):
        calls = [line for line in text.splitlines()
                 if re.match(r"\s*%decode_attn_cache(\.\d+)? = ", line)]
        assert calls
        for line in calls:
            assert line.count(f"bf16[{seen}]") >= 4   # 2 operands, 2 results
        assert not [line for line in text.splitlines() if made.search(line)]


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


def test_mellum2_decode_works_on_rings_and_full_stacks_in_place(topo):
    """Mellum2-12B-A2.5B at its served context, 16,384: the serve step and
    a prefill program call ``decode_attn_ring`` on each sliding run's
    whole (3, 1, 1024, 4, 128) ring stacks (7 runs, 21 layers) and
    ``decode_attn_cache`` on each full run's whole (1, 1, 16384, 4, 128)
    stacks (7 layers), and no copy, slice, update, transpose or fusion
    makes a ring or full stack, one layer of either, or one sequence's
    heads-major cache."""
    ring, full = "3,1,1024,4,128", "1,1,16384,4,128"
    cuts = ["1,1024,4,128", "4,1024,128", "1,16384,4,128", "4,16384,128"]
    made = re.compile(r"= bf16\[({})\]\S* ({})\(".format(
        "|".join([ring, full] + cuts),
        "copy|copy-start|copy-done|dynamic-slice|dynamic-update-slice|"
        "transpose|fusion"))
    for text in _serve_programs(topo, "mellum2-12b-a2.5b", 16384, 16):
        lines = text.splitlines()
        for name, stack, count in (("decode_attn_ring", ring, 7),
                                   ("decode_attn_cache", full, 7)):
            calls = [line for line in lines
                     if re.match(rf"\s*%{name}(\.\d+)? = ", line)]
            assert len(calls) == count
            for line in calls:
                assert line.count(f"bf16[{stack}]") >= 4
        assert not [line for line in lines if made.search(line)]
