"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp ref."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import norms, ref, softmax as sm, ssd_scan, warp_reduce

RNG = np.random.default_rng(42)


def rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale).astype(dtype)


@pytest.mark.parametrize("rows,cols", [(8, 128), (16, 256), (3, 128),
                                       (8, 4096), (1, 512)])
@pytest.mark.parametrize("op", ["sum", "max", "absmax"])
def test_row_reduce(rows, cols, op):
    x = rand((rows, cols))
    got = warp_reduce.row_reduce(x, op, interpret=True)
    want = ref.row_reduce(x, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 128), (4, 16, 256), (2, 8, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_softmax(shape, dtype):
    x = rand(shape, dtype, scale=3.0)
    got = sm.softmax(x, interpret=True)
    want = ref.softmax(x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-3)


@pytest.mark.parametrize("shape", [(8, 128), (16, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = rand(shape, dtype)
    w = rand((shape[-1],), dtype, 0.5)
    got = norms.rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2)


def test_layernorm():
    x = rand((8, 256))
    w = rand((256,), scale=0.5)
    b = rand((256,), scale=0.1)
    got = norms.layernorm(x, w, b, interpret=True)
    want = ref.layernorm(x, w, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,H,Hkv,D", [(256, 4, 4, 64), (256, 8, 2, 64),
                                       (128, 4, 1, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(S, H, Hkv, D, causal):
    q = rand((S, H, D), scale=0.5)
    k = rand((S, Hkv, D), scale=0.5)
    v = rand((S, Hkv, D), scale=0.5)
    got = fa.flash_attention(q, k, v, causal=causal, bq=64, bk=64,
                             interpret=True)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_windowed():
    S, H, D = 256, 2, 64
    q, k, v = rand((S, H, D)), rand((S, H, D)), rand((S, H, D))
    got = fa.flash_attention(q, k, v, causal=True, window=64, bq=64, bk=64,
                             interpret=True)
    want = ref.attention(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,H,Hkv,D,kvlen", [(512, 8, 2, 64, 300),
                                             (256, 4, 1, 64, 256),
                                             (512, 4, 4, 128, 17)])
def test_flash_decode(S, H, Hkv, D, kvlen):
    q = rand((H, D), scale=0.5)
    k = rand((S, Hkv, D), scale=0.5)
    v = rand((S, Hkv, D), scale=0.5)
    got = fa.flash_decode(q, k, v, kvlen, bk=128, interpret=True)
    want = ref.decode_attention(q, k, v, kvlen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,H,P,N,chunk", [(256, 2, 64, 32, 64),
                                           (128, 4, 32, 16, 128),
                                           (512, 1, 128, 64, 128)])
def test_ssd_scan(S, H, P, N, chunk):
    x = rand((S, H, P), scale=0.5)
    a = -jnp.abs(rand((S, H), scale=0.3)) - 0.05   # log-decay ≤ 0
    b = rand((S, N), scale=0.3)
    c = rand((S, N), scale=0.3)
    got = ssd_scan.ssd_scan(x, a, b, c, chunk=chunk, interpret=True)
    want = ref.ssd_scan(x, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def _grad_case(name):
    from repro.kernels import ops
    if name == "rmsnorm":
        w = rand((128,))
        return (lambda x, b: ops.rmsnorm(x, w, backend=b)), rand((8, 128))
    if name == "attention":
        k = rand((128, 2, 64), scale=0.5)
        return (lambda q, b: ops.attention(q, k, k, backend=b),
                rand((128, 4, 64), scale=0.5))
    a = -jnp.abs(rand((128, 2), scale=0.3)) - 0.05
    bc = rand((128, 16), scale=0.3)
    return (lambda x, b: ops.ssd_scan(x, a, bc, bc, chunk=64, backend=b),
            rand((128, 2, 32), scale=0.5))


@pytest.mark.parametrize("name", ["rmsnorm", "attention", "ssd_scan"])
def test_kernel_ops_differentiate_like_the_reference(name):
    # Pallas calls have no reverse-mode rule: ops gives the kernel path
    # the reference's gradient, so training runs on the kernels too
    import jax
    fn, x = _grad_case(name)

    def grad(backend):
        return jax.grad(lambda v: (fn(v, backend) ** 2).sum())(x)

    np.testing.assert_allclose(np.asarray(grad("interpret")),
                               np.asarray(grad("xla")),
                               rtol=1e-3, atol=1e-3)
