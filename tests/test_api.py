"""The unified DVNR facade: backend registry resolution, DVNRModel lifecycle
(save/load/compress round-trips), codec registry, and the deprecation shims
for the pre-facade free functions."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, backends
from repro.configs.dvnr import SMOKE
from repro.data.volume import make_partition


# --------------------------------------------------------------------------- #
# Backend registry
# --------------------------------------------------------------------------- #
def test_get_backend_known_names():
    for name in ("ref", "fused", "pallas", "pallas_tpu"):
        b = backends.get_backend(name)
        assert b.name == name
    # the LM stack's historical name for the jnp path is an alias of ref
    assert backends.get_backend("xla").name == "ref"


def test_get_backend_unknown_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        backends.get_backend("cuda_graphs")


def test_auto_resolution_picks_ref_on_cpu(repro_backend):
    b = backends.resolve("auto")
    if repro_backend != "ref":
        assert b.name == repro_backend      # pinned by the CI backend matrix
    else:
        assert b.name == "ref"
        # on a TPU too: the compiler refuses pallas_tpu's kernels, so auto
        # must rank the XLA path first (tests/test_tpu_compile.py)
        assert backends.resolve_auto("tpu").name == "ref"
    # pallas_tpu is registered but not available off-TPU
    assert backends.get_backend("pallas_tpu").available("cpu") is False
    assert "pallas_tpu" not in backends.available_backends("cpu")


def test_backend_capability_metadata():
    assert backends.get_backend("ref").supports("flash_attention")
    assert backends.get_backend("fused").supports("hash_encoding")
    assert not backends.get_backend("fused").supports("composite")
    # the whole-step op is advertised by every built-in backend
    for name in ("ref", "fused", "pallas", "pallas_tpu"):
        assert backends.get_backend(name).supports("fused_train_step")


def test_register_custom_backend():
    b = backends.Backend(name="_test_backend", kind="jnp", priority=-1)
    backends.register_backend(b)
    assert backends.resolve("_test_backend") is b
    # a Backend instance passes through resolve unchanged
    assert backends.resolve(b) is b


def test_kernels_accept_backend_objects():
    from repro.kernels.hash_encoding.ops import hash_encode

    cfg = SMOKE
    params = api.DVNRModel.init(cfg, jax.random.PRNGKey(0)).params
    coords = jax.random.uniform(jax.random.PRNGKey(1), (32, 3))
    by_name = hash_encode(coords, params["tables"], cfg.level_resolutions(), "ref")
    by_obj = hash_encode(coords, params["tables"], cfg.level_resolutions(),
                         backends.get_backend("ref"))
    np.testing.assert_array_equal(np.asarray(by_name), np.asarray(by_obj))


# --------------------------------------------------------------------------- #
# DVNRModel lifecycle
# --------------------------------------------------------------------------- #
def _tiny_model():
    return api.DVNRModel.init(SMOKE, jax.random.PRNGKey(0))


def test_model_save_load_roundtrip(tmp_path):
    m = _tiny_model()
    path = tmp_path / "model.msgpack"
    m.save(path)
    m2 = api.DVNRModel.load(path)
    assert m2.cfg == m.cfg
    grid = m.decode_grid((6, 6, 6), backend="ref")
    grid2 = m2.decode_grid((6, 6, 6), backend="ref")
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(grid2))


def test_model_compress_roundtrip_within_tolerance(tmp_path):
    m = _tiny_model()
    path = tmp_path / "model.msgpack"
    m.save(path)
    loaded = api.DVNRModel.load(path)
    blobs, info = api.compress(loaded)
    assert info["bytes"] > 0 and len(blobs) == 1
    rec = api.decompress(SMOKE, blobs)
    ref = np.asarray(m.decode_grid((8, 8, 8), backend="ref"))
    dec = np.asarray(rec.decode_grid((8, 8, 8), backend="ref"))
    # zfp_enc/zfp_mlp bound the WEIGHT error; the decoded-field error is the
    # propagated effect and stays well within a loose envelope at SMOKE scale
    assert np.abs(ref - dec).max() < 0.25


def test_model_is_a_pytree():
    m = _tiny_model()
    doubled = jax.tree.map(lambda t: t * 2, m)
    assert isinstance(doubled, api.DVNRModel)
    assert doubled.cfg == m.cfg
    np.testing.assert_allclose(np.asarray(doubled.params["tables"]),
                               2 * np.asarray(m.params["tables"]))
    # jit flows through the registered pytree
    out = jax.jit(lambda mm: mm.params["mlp"][0].sum())(m)
    assert np.isfinite(float(out))


def test_train_render_isosurface_through_facade():
    parts = [make_partition("cloverleaf", p, (1, 1, 2), (8, 8, 8), t=0.2)
             for p in range(2)]
    model, info = api.train(parts, SMOKE, steps=8, key=jax.random.PRNGKey(0))
    assert model.stacked and model.n_partitions == 2
    assert info["steps"] == 8 and info["train_time_s"] > 0
    assert model.grange[1] >= model.grange[0]
    img = api.render(model, api.RenderRequest(width=16, height=16, n_samples=8),
                     backend="ref")
    assert img.shape == (16, 16, 4)
    assert np.isfinite(np.asarray(img)).all()
    pts = api.isosurface(model, 0.5, resolution=8, backend="ref")
    assert pts.ndim == 2 and pts.shape[1] == 3
    one = model.partition(1)
    assert not one.stacked
    v = one.apply(jnp.asarray([[0.5, 0.5, 0.5]]), backend="ref")
    assert v.shape == (1, SMOKE.out_dim)


# --------------------------------------------------------------------------- #
# Codec registry
# --------------------------------------------------------------------------- #
def test_codec_registry_names_and_unknown():
    from repro.compress import available_codecs, get_codec

    for name in ("interp", "blockt", "quantizer", "zstd"):
        assert name in available_codecs()
        assert get_codec(name).name == name
    assert get_codec("quant").name == "quantizer"   # alias
    with pytest.raises(ValueError, match="unknown codec"):
        get_codec("sz9")


def test_codec_uniform_interface_bounds_error():
    from repro.compress import get_codec

    x = np.random.default_rng(0).standard_normal((257,)).astype(np.float32)
    for name in ("blockt", "quantizer"):
        c = get_codec(name)
        y = c.decode(c.encode(x, 0.01))
        assert np.abs(np.asarray(y).ravel()[:257] - x).max() <= 0.01 + 1e-7
    z = get_codec("zstd")
    np.testing.assert_array_equal(z.decode(z.encode(x)), x)


# --------------------------------------------------------------------------- #
# Deprecation shims
# --------------------------------------------------------------------------- #
def test_inr_apply_shim_warns_and_matches_model_apply():
    from repro.core.inr import inr_apply

    m = _tiny_model()
    xyz = jax.random.uniform(jax.random.PRNGKey(2), (16, 3))
    with pytest.warns(DeprecationWarning, match="inr_apply"):
        old = inr_apply(m.cfg, m.params, xyz, impl="ref")
    new = m.apply(xyz, backend="ref")
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_decode_grid_shim_warns_and_matches_model_decode():
    from repro.core.inr import decode_grid

    m = _tiny_model()
    with pytest.warns(DeprecationWarning, match="decode_grid"):
        old = decode_grid(m.cfg, m.params, (5, 5, 5), impl="ref")
    new = m.decode_grid((5, 5, 5), backend="ref")
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_new_api_paths_do_not_warn():
    m = _tiny_model()
    xyz = jnp.zeros((4, 3))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        m.apply(xyz, backend="ref")
        m.decode_grid((4, 4, 4), backend="ref")
