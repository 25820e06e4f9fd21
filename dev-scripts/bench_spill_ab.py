"""A/B the spill-to-scatter hybrid kernel vs spill_cap=0 at the ads shape.

Run on the real TPU, through the chip tool, as the only process on the
chip. Protocol: in-jit fori_loop differencing.
"""

import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.ops.tiled_sparse import (
        TileParams,
        TiledGLMObjective,
        build_tiled_batch,
    )

    rng = np.random.default_rng(0)
    n, k, d = 1 << 18, 64, 1 << 20
    indices = rng.integers(0, d, size=(n, k), dtype=np.int64)
    values = rng.normal(size=(n, k)).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)

    obj = TiledGLMObjective(LOGISTIC, d)

    @jax.jit
    def loop(m, w0, tb):
        def body(i, carry):
            w, acc = carry
            v, g = obj.value_and_gradient(w, tb, 0.1)
            return (w - 1e-9 * g, acc + v)

        return lax.fori_loop(0, m, body, (w0, jnp.float32(0.0)))

    w0 = jnp.zeros((d,), jnp.float32)
    iters = 11

    def timed(tb, m):
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = loop(m, w0, tb)
            _ = float(out[1])
            best = min(best, time.perf_counter() - t0)
        return best

    def measure(tb):
        _ = timed(tb, 1)  # compile + warm
        return (timed(tb, iters) - timed(tb, 1)) / (iters - 1)

    results = {}
    for name, cap, chunk in (
        ("spill4096", None, 4096),
        ("spill4224", None, 4224),
        ("spill4352", None, 4352),
    ):
        t0 = time.time()
        tb = build_tiled_batch(
            rows, indices.reshape(-1), values.reshape(-1), labels,
            np.zeros(n, np.float32), np.ones(n, np.float32), d,
            params=TileParams(spill_cap=cap, chunk=chunk),
        )
        build_s = time.time() - t0
        zs, gs = tb.z_sched.num_steps, tb.g_sched.num_steps
        sp_z = int(np.count_nonzero(np.asarray(tb.z_sched.spill_vals)))
        sp_g = int(np.count_nonzero(np.asarray(tb.g_sched.spill_vals)))
        dt = measure(tb)
        results[name] = dt
        print(
            f"{name}: {dt*1e3:.2f} ms/eval  {n/dt/1e6:.2f}M ex/s  "
            f"steps z/g {zs}/{gs}  spills z/g {sp_z}/{sp_g}  "
            f"build {build_s:.1f}s",
            flush=True,
        )
        del tb

    base = 23.12e-3  # nospill measured earlier this session
    for k, v in results.items():
        print(f"{k}: {base/v:.3f}x vs nospill", flush=True)


if __name__ == "__main__":
    main()
