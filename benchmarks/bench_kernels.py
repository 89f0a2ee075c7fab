"""Per-kernel report: correctness vs the jnp oracle (interpret mode) and
analytic TPU roofline estimates for the production shapes.

CPU wall-clock of interpret-mode Pallas is NOT a TPU time; what we report per
kernel is (a) max|err| vs ref across representative shapes, (b) FLOPs/bytes
and the v5e roofline bound, i.e. the time the kernel cannot beat."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_result
from repro.kernels.composite.ops import composite
from repro.kernels.fused_mlp.ops import fused_mlp
from repro.kernels.hash_encoding.ops import hash_encode
from repro.utils import hw


def _roofline_us(flops, bytes_):
    chip = hw.peaks(hw.V5E)        # the chip these kernels are written for
    return max(flops / chip.bf16_flops, bytes_ / chip.hbm_bytes_per_s) * 1e6


def run(quick: bool = False) -> dict:
    rows = []
    key = jax.random.PRNGKey(0)

    # hash_encoding: production DVNR config L=5 F=4 T=2^16, N=65536 coords
    L, T, F, N = 5, 1 << 16, 4, 65_536 if not quick else 4096
    tables = jax.random.uniform(key, (L, T, F), jnp.float32, -1e-4, 1e-4)
    coords = jax.random.uniform(key, (N, 3))
    res = tuple(8 * 2 ** i for i in range(L))
    ref = hash_encode(coords, tables, res, "ref")
    pal = hash_encode(coords, tables, res, "pallas")
    err = float(jnp.abs(ref - pal).max())
    flops = N * L * (14 * F + 36)
    bytes_ = N * L * (8 * F * 4 + 12) + tables.size * 0  # gather traffic
    rows.append(dict(kernel="hash_encoding", shape=f"L{L} T{T} F{F} N{N}",
                     max_err=err, flops=flops,
                     roofline_us=_roofline_us(flops, bytes_)))

    # fused_mlp: W=16 H=2 on the same N
    dims = [L * F, 16, 16, 1]
    ws = [jax.random.normal(jax.random.fold_in(key, i), (a, b)) * 0.1
          for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]
    x = jax.random.normal(key, (N, dims[0]))
    ref = fused_mlp(x, ws, "ref")
    pal = fused_mlp(x, ws, "pallas")
    err = float(jnp.abs(ref - pal).max())
    flops = 2 * N * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    bytes_ = N * (dims[0] + 1) * 4
    rows.append(dict(kernel="fused_mlp", shape=f"N{N} {dims}", max_err=err,
                     flops=flops, roofline_us=_roofline_us(flops, bytes_)))

    # composite: R rays x S samples
    R, S = (4096, 64) if not quick else (512, 32)
    rgba = jax.random.uniform(key, (R, S, 4))
    ref = composite(rgba, "ref")
    pal = composite(rgba, "pallas")
    err = float(jnp.abs(ref - pal).max())
    flops = R * S * 11
    bytes_ = R * S * 16 + R * 16
    rows.append(dict(kernel="composite", shape=f"R{R} S{S}", max_err=err,
                     flops=flops, roofline_us=_roofline_us(flops, bytes_)))

    for r in rows:
        print(f"[{r['kernel']}] {r['shape']}: max_err={r['max_err']:.2e} "
              f"roofline={r['roofline_us']:.1f}us")
        assert r["max_err"] < 2e-2, r

    # grid-exact static traffic model (repro.analysis.traffic) alongside the
    # hand-derived roofline terms above: per-kernel HBM bytes, FLOPs and
    # arithmetic intensity from the actual BlockSpec schedules — the numbers
    # the trace-driven tuner (ROADMAP) calibrates against measured time
    from repro.analysis.traffic import estimate_traffic_jaxpr

    jx = jax.make_jaxpr(
        lambda c, t, x, w0, w1, w2, rg: (
            fused_mlp(hash_encode(c, t, res, "pallas"), [w0, w1, w2],
                      "pallas"),
            composite(rg, "pallas")))(coords, tables, x, *ws, rgba)
    static = [dict(kernel=kt.kernel, grid=list(kt.grid),
                   hbm_bytes=int(kt.hbm_bytes),
                   ideal_bytes=int(kt.ideal_bytes), flops=int(kt.flops),
                   streaming_factor=round(kt.streaming_factor, 3),
                   intensity=round(kt.intensity, 2))
              for kt in estimate_traffic_jaxpr(jx)]
    for s in static:
        print(f"[static] {s['kernel']} grid={s['grid']}: "
              f"{s['streaming_factor']}x ideal, {s['intensity']} FLOP/B")
    out = {"rows": rows, "static_traffic": static}
    save_result("kernels", out)
    return out


if __name__ == "__main__":
    run()
