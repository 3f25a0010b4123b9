"""Fig. 14 worker: blocks sharded over 1/2/4/8 host devices.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
parent harness before jax initializes).
"""
import statistics
import time

import jax
import numpy as np

from repro.core import cox
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(3)


@cox.kernel
def saxpy_heavy(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
                b: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        acc = 0.0
        for t in range(64):  # compute-heavy body (Hetero-mark style)
            acc = acc + a[i] * 1.0001 + b[i] * 0.9999
        out[i] = acc


def main():
    ndev = len(jax.devices())
    n = 64 * 256
    a = RNG.normal(size=n).astype(np.float32)
    b = RNG.normal(size=n).astype(np.float32)
    out0 = np.zeros(n, np.float32)
    base_us = None
    for d in (1, 2, 4, 8):
        if d > ndev:
            break
        mesh = make_mesh((d,), ("data",))

        def run():
            return saxpy_heavy.launch(grid=64, block=256,
                                      args=(out0, a, b, n), mesh=mesh)

        run()
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            res = run()
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), res)
            ts.append(time.perf_counter() - t0)
        us = statistics.median(ts) * 1e6
        if base_us is None:
            base_us = us
        print(f"scalability.devices_{d},{us:.1f},"
              f"speedup={base_us / us:.2f}x", flush=True)
    print("scalability.NOTE,0.0,host has a single physical core - the 8 "
          "XLA host devices time-share it so wall-clock speedup is not "
          "observable here; block distribution + psum merge correctness "
          "is covered by tests/test_multidevice.py (paper Fig.14 ran on "
          "8 real cores)", flush=True)


if __name__ == "__main__":
    main()
