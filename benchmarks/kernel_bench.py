"""Kernel micro-benchmarks (CPU host): wall-time of the jnp deployment path
vs the float path, plus the derived TPU-roofline expectation for the Pallas
kernel (interpret mode has no meaningful wall time — the derived column is
the §Roofline-model time on v5e).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.w1a8 import (deploy_w1a8_linear, init_w1a8_linear,
                             w1a8_linear_float_ref, w1a8_linear_infer)
from repro.launch.peaks import V5E, chip_peaks

V5E_FLOPS = chip_peaks(V5E)["peak_flops_bf16"]
V5E_BW = chip_peaks(V5E)["hbm_bw"]


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6        # µs


def run() -> list:
    rows = []
    for (m, k, n) in [(256, 4096, 4096), (64, 1152, 128)]:
        key = jax.random.PRNGKey(0)
        p = init_w1a8_linear(key, k, n)
        x = jax.random.uniform(key, (m, k), jnp.float32, 0.0, 2.0)
        d = deploy_w1a8_linear(p)
        a = jnp.clip(jnp.round(x / d["mul_prev"]), 0, 255).astype(jnp.uint8)

        f_ref = jax.jit(lambda p_, x_: w1a8_linear_float_ref(p_, x_))
        f_pkd = jax.jit(lambda d_, a_: w1a8_linear_infer(d_, a_))
        us_ref = _time(f_ref, p, x)
        us_pkd = _time(f_pkd, d, a)
        flops = 2 * m * k * n
        wbytes_bf16 = k * n * 2
        wbytes_packed = k * n / 8
        t_tpu_bf16 = max(flops / V5E_FLOPS, wbytes_bf16 / V5E_BW) * 1e6
        t_tpu_pkd = max(flops / V5E_FLOPS, wbytes_packed / V5E_BW) * 1e6
        rows.append((f"kernel.w1a8_matmul.{m}x{k}x{n}.cpu_ref_us",
                     round(us_ref, 1), "float eval path (CPU wall)"))
        rows.append((f"kernel.w1a8_matmul.{m}x{k}x{n}.cpu_packed_us",
                     round(us_pkd, 1), "1-bit deployed path (CPU wall)"))
        rows.append((f"kernel.w1a8_matmul.{m}x{k}x{n}.v5e_model_us",
                     round(t_tpu_pkd, 2),
                     f"roofline model; bf16-weight equivalent "
                     f"{t_tpu_bf16:.2f}us → {t_tpu_bf16/t_tpu_pkd:.1f}x"))
    return rows
