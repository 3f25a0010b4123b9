"""Kernel dispatch layer: jit'd wrappers selecting Pallas vs XLA (ref).

Policy:
* ``backend="pallas"``  — compiled Pallas TPU kernels (real hardware);
* ``backend="interpret"`` — Pallas interpret mode (CPU validation; the
  kernel *body* runs, slowly, through XLA);
* ``backend="xla"``     — the pure-jnp reference math (used by the model
  stack for CPU dry-runs: identical numerics, compact HLO);
* ``backend="auto"``    — pallas on TPU, xla elsewhere.
"""
from __future__ import annotations

import functools

import jax

from . import flash_attention as _fa
from . import norms as _norms
from . import ref as _ref
from . import softmax as _sm
from . import ssd_scan as _ssd
from . import warp_reduce as _wr


def resolve(backend: str = "auto") -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def _dispatch(backend: str, kernel, reference, *args):
    """``reference(*args)`` on the xla path; otherwise the Pallas
    ``kernel`` forward with ``reference``'s gradient.  A Pallas call has
    no reverse-mode rule, so training differentiates through the
    reference math of the same function instead."""
    b = resolve(backend)
    if b == "xla":
        return reference(*args)
    run = functools.partial(kernel, interpret=(b == "interpret"))

    @jax.custom_vjp
    def op(*a):
        return run(*a)

    op.defvjp(lambda *a: (run(*a), a),
              lambda a, g: jax.vjp(reference, *a)[1](g))
    return op(*args)


def softmax(x, backend: str = "auto"):
    return _dispatch(backend, _sm.softmax, _ref.softmax, x)


def rmsnorm(x, w, eps: float = 1e-6, backend: str = "auto"):
    return _dispatch(backend, functools.partial(_norms.rmsnorm, eps=eps),
                     functools.partial(_ref.rmsnorm, eps=eps), x, w)


def layernorm(x, w, bias, eps: float = 1e-6, backend: str = "auto"):
    return _dispatch(backend, functools.partial(_norms.layernorm, eps=eps),
                     functools.partial(_ref.layernorm, eps=eps), x, w, bias)


def row_reduce(x, op: str = "sum", backend: str = "auto"):
    return _dispatch(backend, functools.partial(_wr.row_reduce, op=op),
                     functools.partial(_ref.row_reduce, op=op), x)


def attention(q, k, v, causal: bool = True, window: int = 0,
              backend: str = "auto"):
    kw = dict(causal=causal, window=window)
    return _dispatch(backend, functools.partial(_fa.flash_attention, **kw),
                     functools.partial(_ref.attention, **kw), q, k, v)


def decode_attention(q, k_cache, v_cache, kv_len, backend: str = "auto"):
    return _dispatch(backend, _fa.flash_decode, _ref.decode_attention,
                     q, k_cache, v_cache, kv_len)


def ssd_scan(x, a, bmat, cmat, chunk: int = 128, backend: str = "auto"):
    # xla: the chunked dual form — same math, production XLA path
    return _dispatch(backend, functools.partial(_ssd.ssd_scan, chunk=chunk),
                     functools.partial(_ref.ssd_scan_chunked, chunk=chunk),
                     x, a, bmat, cmat)


def topk_gate(logits, k: int):
    return _ref.topk_gate(logits, k)
