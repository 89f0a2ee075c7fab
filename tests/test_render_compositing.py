"""Sort-last compositing correctness: the scalable binary-swap path must equal
the exact depth-sort reference, and the fully shard_map'd production render
step must equal the host-loop renderer. Run on fake devices in a subprocess
(jax pins the device count at first init)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.render import composite_depth_sort, over


def test_over_operator_associativity_on_opaque():
    """Compositing a fully-opaque front layer hides everything behind it."""
    front = jnp.asarray([[1.0, 0.0, 0.0, 1.0]])
    back = jnp.asarray([[0.0, 1.0, 0.0, 0.7]])
    out = over(front, back)
    np.testing.assert_allclose(np.asarray(out), [[1.0, 0.0, 0.0, 1.0]],
                               atol=1e-6)


def test_depth_sort_reference_orders_by_depth():
    key = jax.random.PRNGKey(0)
    P, R = 4, 16
    imgs = jax.random.uniform(key, (P, R, 4)) * 0.5
    depths = jnp.stack([jnp.full((R,), float(p)) for p in (3, 1, 0, 2)])
    out = composite_depth_sort(imgs, depths)
    # manual front-to-back with known order 2,1,3,0
    ref = jnp.zeros((R, 4))
    for p in (2, 1, 3, 0):
        ref = over(ref, imgs[p])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("cached", [False, True], ids=["direct", "cached"])
def test_ray_chunks_with_a_remainder_match_one_pass(monkeypatch, cached):
    """A frame rendered in many ray chunks (the last one partial) equals the
    same frame rendered in one pass, on the INR and the brick-pool paths."""
    from repro.configs.dvnr import SMOKE
    from repro.core import render
    from repro.core.inr import init_inr

    P, W, H, S = 4, 12, 10, 8
    grid, edge = (8, 8, 8), 4
    key = jax.random.PRNGKey(3)
    params = jax.vmap(lambda k: init_inr(SMOKE, k))(jax.random.split(key, P))
    los = jnp.asarray([(0.5 * (p % 2), 0.5 * (p // 2), 0.0)
                       for p in range(P)], jnp.float32)
    exts = jnp.tile(jnp.asarray([[0.5, 0.5, 1.0]], jnp.float32), (P, 1))
    vrs = jnp.tile(jnp.asarray([[0.0, 1.0]], jnp.float32), (P, 1))
    nb = grid[0] // edge
    pool = jax.random.uniform(key, (P * nb ** 3,) + (edge + 1,) * 3)
    slots = jnp.arange(P * nb ** 3, dtype=jnp.int32).reshape(P, nb, nb, nb)
    cam = render.Camera(eye=(1.8, 1.4, 1.6))

    def frame():
        if cached:
            return render._render_distributed_sampled(
                pool, slots, grid, edge, (los, exts, vrs), cam, W, H,
                (0.0, 1.0), n_samples=S)
        return render._render_distributed(
            SMOKE, params, None, cam, W, H, (0.0, 1.0), n_samples=S,
            metas=(los, exts, vrs))

    one_pass = np.asarray(frame())
    # 7 rays per chunk: 120 rays -> 17 full chunks and one of a single ray
    monkeypatch.setattr(render, "_CHUNK_SAMPLES", P * S * 7)
    chunked = np.asarray(frame())
    assert one_pass[..., 3].max() > 0
    np.testing.assert_allclose(chunked, one_pass, rtol=0, atol=1e-6)


_SWAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import build_mesh
    from repro.core.render import (Camera, binary_swap, composite_depth_sort,
                                   make_rays, ray_aabb)

    mesh = build_mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    P, W, H = 8, 8, 8
    R = W * H
    # binary swap's precondition: partition p is the box whose corner is p's
    # bit pattern on a 2x2x2 grid (plane-separated swap partners). Depths are
    # the TRUE per-ray box entry distances — a scalar per-partition depth is
    # not geometrically realizable and breaks any sort-last compositor.
    origins, dirs = make_rays(Camera(eye=(1.9, 1.6, 1.4)), W, H)
    imgs, depths = [], []
    key = jax.random.PRNGKey(0)
    for p in range(P):
        lo = 0.5 * jnp.asarray([(p >> 2) & 1, (p >> 1) & 1, p & 1],
                               jnp.float32)
        t0, t1 = ray_aabb(origins, dirs, lo, lo + 0.5)
        hit = t1 > t0
        img = jax.random.uniform(jax.random.fold_in(key, p), (R, 4)) * 0.6
        imgs.append(jnp.where(hit[:, None], img, 0.0))
        depths.append(jnp.where(hit, t0, jnp.inf))
    imgs = jnp.stack(imgs)
    depths = jnp.stack(depths)
    ref = composite_depth_sort(imgs, depths)
    with mesh:
        out = binary_swap(mesh, ("data", "model"), imgs, depths)
    # every device row carries the same fully composited frame
    for p in range(P):
        np.testing.assert_allclose(np.asarray(out[p]), np.asarray(ref),
                                   atol=1e-5)
    print("BINARY_SWAP_OK")
""")


def test_binary_swap_equals_depth_sort_on_8_devices():
    r = subprocess.run([sys.executable, "-c", _SWAP_SCRIPT],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "BINARY_SWAP_OK" in r.stdout


_RENDER_STEP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import build_mesh
    from repro.configs.dvnr import SMOKE
    from repro.core.inr import init_inr
    from repro.core.render import (Camera, _render_distributed, default_tf,
                                   make_distributed_render_step, make_rays)

    mesh = build_mesh(np.asarray(jax.devices()).reshape(2, 2), ("data", "model"))
    cfg = SMOKE
    P = 4
    params = jax.vmap(lambda k: init_inr(cfg, k))(
        jax.random.split(jax.random.PRNGKey(0), P))
    metas = []
    los, exts, vrs = [], [], []
    for p in range(P):
        lo = (0.5 * (p % 2), 0.5 * (p // 2), 0.0)
        metas.append({"origin": lo, "extent": (0.5, 0.5, 1.0),
                      "vmin": 0.0, "vmax": 1.0})
        los.append(lo); exts.append((0.5, 0.5, 1.0)); vrs.append((0.0, 1.0))
    cam = Camera(eye=(1.8, 1.4, 1.6))
    W = H = 16   # 256 rays, divisible by 4 devices
    ref = _render_distributed(cfg, params, metas, cam, W, H, (0.0, 1.0),
                              n_samples=8)
    step = make_distributed_render_step(cfg, mesh, n_samples=8)
    origins, dirs = make_rays(cam, W, H)
    with mesh:
        out = jax.jit(step)(params, jnp.asarray(los, jnp.float32),
                            jnp.asarray(exts, jnp.float32),
                            jnp.asarray(vrs, jnp.float32),
                            origins, dirs, default_tf(),
                            jnp.asarray([0.0, 1.0], jnp.float32))
    img = np.asarray(out[0]).reshape(H, W, 4)
    np.testing.assert_allclose(img, np.asarray(ref), atol=1e-4)
    print("RENDER_STEP_OK")
""")


def test_distributed_render_step_equals_host_loop():
    r = subprocess.run([sys.executable, "-c", _RENDER_STEP_SCRIPT],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RENDER_STEP_OK" in r.stdout
