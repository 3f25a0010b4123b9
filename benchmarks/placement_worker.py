"""Multi-device stream placement worker: N streams over 1/2/4/8 devices.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the device
count must be set before jax initializes; ``tests/test_placement.py``
starts it so).  Four streams issue independent compute-heavy launches;
a private :class:`~repro.core.streams.Dispatcher` with a ``devices=``
pool of size k round-robins the streams over k XLA devices.  Every
pool's outputs are asserted bitwise-equal to the 1-device pool's, and
every device of the pool must have taken dispatches — placement must
never change results.  Prints one ``placement.devices_<k>`` line per
pool, then ``placement-ok``.

``placeFma`` is also the kernel ``chip_smoke.py`` places over four
chips.
"""
import jax
import numpy as np

from repro.core import cox
from repro.core.streams import Dispatcher
from repro.launch.mesh import device_pool

POOL_SIZES = (1, 2, 4, 8)
N_STREAMS = 4
DEPTH = 2  # launches in flight per stream before the sync


@cox.kernel
def placeFma(c, out: cox.Array(cox.f32), a: cox.Array(cox.f32),
             b: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        acc = a[i]
        for t in range(128):  # compute-bound: device work dominates host
            acc = acc * 0.9995 + b[i]
        out[i] = acc


def main():
    ndev = len(jax.devices())
    assert ndev >= max(POOL_SIZES), f"needs {max(POOL_SIZES)} devices"
    grid, block = 32, 256
    n = grid * block
    rng = np.random.default_rng(7)
    # independent per-stream inputs so the streams share no data edges
    per_stream = [(np.zeros(n, np.float32),
                   rng.normal(size=n).astype(np.float32),
                   rng.normal(size=n).astype(np.float32), n)
                  for _ in range(N_STREAMS)]

    ref_outs = None
    for k in POOL_SIZES:
        disp = Dispatcher(devices=device_pool(k))
        streams = [cox.Stream(f"place-s{i}", dispatcher=disp)
                   for i in range(N_STREAMS)]
        hs = [st.launch(placeFma, grid=grid, block=block, args=a)
              for _ in range(DEPTH) for st, a in zip(streams, per_stream)]
        outs = [np.asarray(h.result()["out"]) for h in hs]
        if ref_outs is None:
            ref_outs = outs
        for got, want in zip(outs, ref_outs):
            np.testing.assert_array_equal(
                got, want, err_msg=f"pool={k}: placed != 1-device")
        used = [d for d, c in disp.device_health().items()
                if c.get("dispatches", 0) > 0]
        assert len(used) == min(k, N_STREAMS), (k, used)
        print(f"placement.devices_{k}: {N_STREAMS} streams x {DEPTH}, "
              f"{len(used)} devices used, bitwise == 1-device", flush=True)
    print("placement-ok", flush=True)


if __name__ == "__main__":
    main()
