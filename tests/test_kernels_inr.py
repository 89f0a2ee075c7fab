"""Pallas kernel validation (interpret=True) vs pure-jnp oracles: hash encoding
and fused MLP, swept over shapes/dtypes, including gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.kernels.fused_mlp import ref as mlp_ref
from repro.kernels.fused_mlp.ops import fused_mlp
from repro.kernels.hash_encoding import ops as he_ops
from repro.kernels.hash_encoding import ref as he_ref
from repro.kernels.hash_encoding.ops import hash_encode


def _mk_tables(key, L, T, F, dtype):
    return (0.1 * jax.random.normal(key, (L, T, F))).astype(dtype)


@pytest.mark.parametrize("N", [17, 256, 1500])
@pytest.mark.parametrize("L,T,F", [(2, 128, 2), (4, 2048, 4), (3, 64, 8)])
def test_hash_encode_matches_ref(N, L, T, F):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    coords = jax.random.uniform(k1, (N, 3))
    tables = _mk_tables(k2, L, T, F, jnp.float32)
    res = tuple(int(4 * 2**l) for l in range(L))
    out_k = hash_encode(coords, tables, res, "pallas")
    out_r = he_ref.hash_encode_ref(coords, tables, res)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-6)


def test_hash_encode_dense_vs_hashed_paths():
    """Small resolutions are dense-injective, large ones hashed; both must work."""
    key = jax.random.PRNGKey(3)
    coords = jax.random.uniform(key, (333, 3))
    tables = _mk_tables(key, 2, 512, 4, jnp.float32)
    res = (4, 64)     # (4+1)^3=125 <= 512 dense; (64+1)^3 >> 512 hashed
    out_k = hash_encode(coords, tables, res, "pallas")
    out_r = he_ref.hash_encode_ref(coords, tables, res)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=1e-6)


def test_hash_encode_grad_matches_ref():
    key = jax.random.PRNGKey(1)
    coords = jax.random.uniform(key, (200, 3))
    tables = _mk_tables(key, 3, 256, 4, jnp.float32)
    res = (4, 8, 16)

    def loss_custom(t):
        return jnp.sum(jnp.sin(hash_encode(coords, t, res, "ref")))

    def loss_ref(t):
        return jnp.sum(jnp.sin(he_ref.hash_encode_ref(coords, t, res)))

    g_c = jax.grad(loss_custom)(tables)
    g_r = jax.grad(loss_ref)(tables)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_r), atol=1e-5)


def _table_grad_np(coords, g, res, T):
    """float64 ``np.add.at`` accumulation of the corners' weighted cotangents:
    (grad (L,T,F), sum of |terms| per row (L,T,F), rows touched (L,T))."""
    N = coords.shape[0]
    L = len(res)
    F = g.shape[1] // L
    g = g.astype(np.float64).reshape(N, L, F)
    grad = np.zeros((L, T, F))
    mag = np.zeros((L, T, F))
    touched = np.zeros((L, T), bool)
    primes = [1, 2_654_435_761, 805_459_861]
    for l, r in enumerate(res):
        pos = coords * np.float32(r)                     # float32, as the op
        lo = np.clip(np.floor(pos), 0, max(r - 1, 0)).astype(np.int64)
        w = pos.astype(np.float64) - lo
        for off in np.ndindex(2, 2, 2):
            c = lo + np.asarray(off)
            if (r + 1) ** 3 <= T:
                idx = c[:, 0] + (r + 1) * (c[:, 1] + (r + 1) * c[:, 2])
            else:
                h = [(c[:, k] * primes[k]) % 2**32 for k in range(3)]
                idx = (h[0] ^ h[1] ^ h[2]) % T
            wc = np.prod(np.where(np.asarray(off) == 1, w, 1 - w), axis=1)
            np.add.at(grad[l], idx, wc[:, None] * g[:, l])
            np.add.at(mag[l], idx, np.abs(wc[:, None] * g[:, l]))
            touched[l, idx] = True
    return grad, mag, touched


# (N, T, resolutions, F, table dtype, backend, ranks). A level with at most
# N rows in use (a dense level's (R+1)^3, else T) searches each row's run;
# one with more writes each run's total at its row.
_TABLE_GRAD_CASES = {
    "dense": (500, 128, (2, 4), 4, jnp.float32, "ref", None),
    "hashed": (500, 64, (8, 16), 4, jnp.float32, "ref", None),
    "mixed": (500, 128, (2, 4, 16, 32), 4, jnp.float32, "ref", None),
    "duplicated": (4096, 64, (2, 3, 8, 32), 4, jnp.float32, "ref", None),
    "untouched": (300, 512, (4, 6, 64), 4, jnp.float32, "ref", None),
    "ranks": (300, 128, (2, 4, 16, 32), 4, jnp.float32, "ref", 3),
    "bf16": (500, 128, (2, 4, 16, 32), 4, jnp.bfloat16, "ref", None),
    "fused": (500, 128, (2, 4, 16, 32), 4, jnp.float32, "fused", None),
    "fused_ranks_bf16": (300, 128, (2, 4, 16, 32), 4, jnp.bfloat16, "fused",
                         3),
    "large_table": (40, 2048, (2, 4, 8, 16, 64), 8, jnp.float32, "ref",
                    None),
    "table_at_corners": (64, 512, (2, 8, 16), 8, jnp.float32, "ref", None),
    "rows_at_batch": (125, 512, (4, 16), 8, jnp.float32, "ref", None),
    "rows_past_batch": (124, 512, (4, 16), 8, jnp.float32, "ref", None),
    "large_table_ranks": (40, 1024, (2, 8, 32), 8, jnp.float32, "fused", 3),
    # three tiles of the writer; the dense level's and the hashed level's
    # touched rows run across the tiles' edges
    "tile_straddle": (300, 3 * he_ops._TILE, (2, 6, 16), 8, jnp.float32,
                      "ref", None),
}


@pytest.mark.parametrize("case", sorted(_TABLE_GRAD_CASES))
def test_table_grad_matches_float64_accumulation(case):
    """The tables' gradient (sort, segmented sum, row readout) against a
    float64 scatter-add: dense, hashed and mixed levels, 8N >> T, T = 8N,
    T >> 8N at F = 8, a level's rows in use at and past the batch (searched,
    then written), written rows across the writer's tile edges, rows no
    sample touches (exactly 0), rank-vmapped calls, bf16 tables, the ref and
    fused backends."""
    N, T, res, F, dtype, impl, ranks = _TABLE_GRAD_CASES[case]
    P = ranks or 1
    L = len(res)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    # a sub-box of the domain, so that dense rows go untouched
    coords = 0.6 * jax.random.uniform(k1, (P, N, 3))
    tables = _mk_tables(k2, L, T, F, dtype)
    tables = jnp.broadcast_to(tables, (P,) + tables.shape)
    g = jax.random.normal(k3, (P, N, L * F)).astype(dtype)

    @jax.jit
    def table_grad(c, t, gg):
        _, vjp = jax.vjp(lambda t_: hash_encode(c, t_, res, impl), t)
        return vjp(gg)[0]

    traced = len(tracing.table_readouts())
    if ranks:
        got = jax.jit(jax.vmap(table_grad))(coords, tables, g)
    else:
        got = table_grad(coords[0], tables[0], g[0])[None]
    readouts = tracing.table_readouts()[traced:]
    assert got.dtype == dtype and got.shape == (P, L, T, F)
    got = np.asarray(got.astype(jnp.float32), np.float64)
    rel = 2.0 ** -8 if dtype == jnp.bfloat16 else 0.0
    for p in range(P):
        want, mag, touched = _table_grad_np(
            np.asarray(coords[p]), np.asarray(g[p].astype(jnp.float32)),
            res, T)
        assert np.all(got[p][~touched] == 0.0)
        err = np.abs(got[p] - want)
        assert np.all(err <= rel * np.abs(want) + 1e-5 * mag + 1e-30), \
            (case, p, float(err.max()))
    # the readout, per level: a search where the rows in use are at most N,
    # else the tile writer; nothing is scattered
    rows = [min((r + 1) ** 3, T) for r in res]
    written = sum(r > N for r in rows)
    assert readouts == [(L - written, written)], (case, readouts)
    jaxpr = str(jax.make_jaxpr(table_grad)(coords[0], tables[0], g[0]))
    assert "scatter" not in jaxpr, case
    if case.startswith("large_table"):
        assert 8 * N < T and (~touched).any()
        dense = [(r + 1) ** 3 <= T for r in res]
        assert any(dense) and not all(dense)
    if case == "untouched":
        assert (~touched).any()
    if case == "tile_straddle":
        B = he_ops._TILE
        assert written == 2 and all(
            touched[l, B - 1] and touched[l, B] for l in (1, 2))
        assert touched[2, 2 * B - 1] and touched[2, 2 * B]
    if case == "duplicated":
        assert 8 * N >= 64 * T


def _tile_writer_case(rng, T, M, F, rows_of_level):
    """keys (L, M): each level's rows, ascending, then T in the places past
    them; totals (L, F, M) with full 24-bit mantissas, also in those places
    (there they must be read by no row)."""
    keys = np.full((len(rows_of_level), M), T, np.int32)
    for l, rows in enumerate(rows_of_level):
        keys[l, :len(rows)] = np.sort(rows)
    mant = rng.integers(2 ** 22, 2 ** 23, (len(rows_of_level), F, M)) | 1
    totals = (np.float32(2.0 ** -23) * (2 ** 23 + mant).astype(np.float32)
              * rng.choice(np.float32([-1, 1]), mant.shape)
              * np.float32(2.0) ** rng.integers(-30, 5, mant.shape))
    return keys, totals.astype(np.float32)


def _assigned(keys, totals, T):
    """NumPy assignment of each key's totals at its row."""
    L, F, _ = totals.shape
    want = np.zeros((L, T, F), np.float32)
    for l in range(L):
        ends = keys[l] < T
        want[l, keys[l, ends]] = totals[l][:, ends].T
    return want


def test_tile_writer_matches_numpy_assignment():
    """The run totals' tile writer (a one-hot product a tile of rows) puts
    every total at its row bit for bit and 0 in every other row: a tile
    with all of its rows, empty tiles, a partial and a last tile, windows
    that start inside a block of entries, spare places holding totals that
    no row may read; the totals' mantissas use all 24 bits, so a product
    that rounds them to bf16 (a TPU's default precision) fails; ranks
    vmapped."""
    B = he_ops._TILE
    T, M, F = 8 * B, 4 * B, 8
    rng = np.random.default_rng(16)
    full = np.arange(2 * B, 3 * B)                         # all of tile 2
    sparse = rng.choice(T, 3 * B, replace=False)
    levels = [np.concatenate([[B + 3, B + 77], full, [T - 2, T - 1]]),
              sparse, np.zeros(0, np.int64),
              rng.choice(np.arange(B // 2, 6 * B), M, replace=False)]
    keys, totals = _tile_writer_case(rng, T, M, F, levels)
    assert np.all(totals != totals.astype(jnp.bfloat16).astype(np.float32))
    first = np.searchsorted(keys[1], np.arange(0, T, B))
    assert np.any(first % B)                               # mid-block windows
    write = jax.jit(jax.vmap(he_ops._write_tiles, (0, 0, None)),
                    static_argnums=2)                      # levels
    got = np.asarray(write(jnp.asarray(keys), jnp.asarray(totals), T))
    want = _assigned(keys, totals, T)
    assert got.shape == want.shape and np.array_equal(got, want)
    assert not got[0, :B].any() and not got[2].any()       # empty tiles
    assert np.all(got[0, 2 * B:3 * B] != 0)                # a full tile
    assert np.all(got[3, :B // 2] == 0) and not got[3, 6 * B:].any()
    # a partial last tile: its rows past T are cut off
    short = T - B // 2
    trimmed = np.where(keys < short, keys, short).astype(np.int32)
    got = np.asarray(write(jnp.asarray(trimmed), jnp.asarray(totals), short))
    assert np.array_equal(got, _assigned(trimmed, totals, short))
    # ranks vmapped, each its own keys
    keys2, totals2 = _tile_writer_case(
        rng, T, M, F, [rng.choice(T, n, replace=False) for n in (M, 5, B)])
    both = np.stack([keys[:3], keys2]), np.stack([totals[:3], totals2])
    got = np.asarray(jax.jit(jax.vmap(
        lambda k, t: write(k, t, T)))(*map(jnp.asarray, both)))
    for p in range(2):
        assert np.array_equal(got[p], _assigned(both[0][p], both[1][p], T))


def test_hash_encode_boundary_coords():
    """Coords exactly at 0 and 1 must not index out of bounds."""
    coords = jnp.array([[0.0, 0.0, 0.0], [1.0, 1.0, 1.0], [0.0, 1.0, 0.5]])
    tables = _mk_tables(jax.random.PRNGKey(0), 2, 128, 2, jnp.float32)
    out = hash_encode(coords, tables, (4, 16), "pallas")
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,D_in,W,H,D_out", [
    (100, 8, 16, 2, 1), (513, 32, 64, 3, 3), (64, 16, 16, 1, 1),
])
def test_fused_mlp_matches_ref(dtype, N, D_in, W, H, D_out):
    ks = jax.random.split(jax.random.PRNGKey(0), H + 1)
    ws = [jax.random.normal(ks[0], (D_in, W)).astype(dtype) * 0.3]
    for i in range(H - 1):
        ws.append(jax.random.normal(ks[i + 1], (W, W)).astype(dtype) * 0.3)
    ws.append(jax.random.normal(ks[H], (W, D_out)).astype(dtype) * 0.3)
    x = jax.random.normal(jax.random.PRNGKey(9), (N, D_in)).astype(dtype)
    out_k = fused_mlp(x, ws, "pallas")
    out_r = mlp_ref.fused_mlp_ref(x, ws)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=tol, rtol=tol)


def test_fused_mlp_grads_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    ws = [0.3 * jax.random.normal(ks[0], (8, 32)),
          0.3 * jax.random.normal(ks[1], (32, 32)),
          0.3 * jax.random.normal(ks[2], (32, 2))]
    x = jax.random.normal(ks[3], (300, 8))

    def loss_k(xx, ww):
        return jnp.sum(jnp.square(fused_mlp(xx, ww, "pallas")))

    def loss_r(xx, ww):
        return jnp.sum(jnp.square(mlp_ref.fused_mlp_ref(xx, ww)))

    gx_k, gw_k = jax.grad(loss_k, argnums=(0, 1))(x, ws)
    gx_r, gw_r = jax.grad(loss_r, argnums=(0, 1))(x, ws)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_r), atol=1e-4)
    for a, b in zip(gw_k, gw_r):
        # accumulation order across batch tiles differs from one big matmul
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3, rtol=1e-4)
