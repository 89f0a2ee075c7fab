"""jit'd wrapper for hash encoding: backend dispatch + custom VJP.

Forward: Pallas kernel (TPU) or pure-jnp oracle (CPU / default).
Backward (every backend): :func:`table_grad` sorts each level's 8N corner
indices once, carrying the weighted cotangents, sums each run of equal
indices with a segmented scan in f32 and reads the run totals out into the
table's rows: by a search a row where the rows are no more than the
corners, else by tiles of rows that pick their runs' totals out of the
sorted run ends with a one-hot product. Nothing is scattered: a TPU
scatter is serial per value, ~5–40 ns each (PERF.md, section 6).

Dispatch goes through :mod:`repro.backends`; ``impl`` accepts a backend name
(``"ref"``, ``"fused"``, ``"pallas"``, ``"pallas_tpu"``, ``"auto"``) or a
resolved :class:`~repro.backends.Backend`.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro import backends, tracing
from repro.kernels.hash_encoding import ref as _ref
from repro.kernels.hash_encoding.kernel import hash_encode_pallas


def hash_encode(coords, tables, resolutions: Sequence[int],
                impl: backends.BackendLike = "ref", *, compute_dtype=None):
    """coords (N,3) in [0,1]; tables (L,T,F) -> (N, L*F). Differentiable in tables.

    Output features carry the table dtype — every path (ref / fused / pallas)
    accepts bf16 tables without upcasting. ``compute_dtype`` (a dtype or name)
    casts the tables before encoding (a differentiable cast, so the cotangent
    arrives in the caller's param dtype); coords stay float32 — grid
    *positions* need the mantissa.
    """
    backend = backends.resolve(impl)
    with jax.named_scope(tracing.ENCODE):
        if compute_dtype is not None:
            tables = tables.astype(backend.require_dtype(compute_dtype))
        return _hash_encode(coords, tables, resolutions, backend)


def vmem_footprint(coords, tables, resolutions: Sequence[int],
                   impl: backends.BackendLike = "pallas"):
    """Static VMEM bill of the forward encode: one
    :class:`repro.analysis.vmem.KernelFootprint` per ``pallas_call`` the op
    would emit for these operand shapes (empty on jnp backends). ``coords`` /
    ``tables`` may be ``jax.ShapeDtypeStruct``s — nothing executes."""
    from repro.analysis.vmem import footprint_of

    backend = backends.resolve(impl)
    return footprint_of(lambda c, t: _fwd_impl(c, t, resolutions, backend),
                        coords, tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _hash_encode(coords, tables, resolutions, backend: backends.Backend):
    return _fwd_impl(coords, tables, resolutions, backend)


def _use_fused(backend):
    return backend.is_fused and backend.supports("hash_encoding")


def _fwd_impl(coords, tables, resolutions, backend):
    if backend.is_pallas:
        return hash_encode_pallas(coords, tables,
                                  jnp.asarray(resolutions, jnp.int32),
                                  interpret=backend.interpret)
    if _use_fused(backend):
        return _ref.hash_encode_fused(coords, tables, resolutions)
    return _ref.hash_encode_ref(coords, tables, resolutions)


def _fwd(coords, tables, resolutions, backend):
    if _use_fused(backend):
        # store the (small) corner indices/weights as residuals: the backward
        # reuses them instead of recomputing the whole index chain
        # (EXPERIMENTS.md §Perf DVNR iteration C2)
        idx, ww = _ref.fused_corners(coords, resolutions, tables.shape[1])
        out = _ref._combine_fused(idx, ww, tables)
        return out, (coords, tables.shape, idx, ww)
    return _fwd_impl(coords, tables, resolutions, backend), \
        (coords, tables.shape, None, None)


def _bwd(resolutions, backend, res, g):
    coords, (_, T, _), idx, ww = res
    if idx is None:
        idx, ww = _ref.fused_corners(coords, resolutions, T)
    rows = tuple(min((int(r) + 1) ** 3, T) for r in resolutions)
    return jnp.zeros_like(coords), table_grad(idx, ww, g, T, rows)


_COLUMNS = 128    # the sorted corners' segmented scan runs in 128 columns
_STRIDE = 256     # keys per step of the row search's coarse pass
_TILE = 128       # rows a tile of the run totals' writer


def table_grad(idx, ww, g, table_size: int, rows):
    """Gradient of the ``(L, T, F)`` tables: each corner's weight times the
    level's cotangent, summed per table row.

    ``idx``, ``ww`` (L, N, 8) are the corners' indices and weights
    (:func:`~repro.kernels.hash_encoding.ref.fused_corners`); ``g`` (N, L*F)
    the encode's cotangent; ``rows`` each level's rows in use (a dense
    level's (R+1)^3, else T). Per level, the 8N indices are sorted once with
    the F weighted-cotangent columns as payload (levels and any vmapped
    ranks are batch dimensions of the one sort); a segmented scan sums each
    run of equal indices, so no run's total passes through another's
    partial sums. The readout (scope ``table_rows``) is chosen per level
    from the shapes: a level with at most N rows in use, an eighth of its
    corners, searches each row's run end (:func:`_read_rows`, ~16 gathers a
    row); a level with more sorts its run ends to the front and writes the
    rows by tiles, each picking its runs' totals out of the sorted ends
    with a one-hot product (:func:`_write_runs`, whose cost follows the
    tiles' windows of ends, not a search a row). How many levels took each
    is recorded as the function is traced
    (:func:`repro.tracing.table_readouts`).
    A row that no corner touches reads exactly 0. Every per-element array
    is (L, 8N), lane-dense; sums in f32, returned in ``g``'s dtype.
    """
    L, N, C = idx.shape
    F = g.shape[-1] // L
    M = C * N
    pad = ((0, 0), (0, -M % _STRIDE))          # past every row: sorts last
    keys = jnp.pad(idx.transpose(0, 2, 1).reshape(L, M), pad,
                   constant_values=table_size)                    # corner-major
    w = ww.astype(jnp.float32).transpose(0, 2, 1)                 # (L,8,N)
    gl = g.reshape(N, L, F).astype(jnp.float32).transpose(2, 1, 0)  # (F,L,N)
    cols = tuple(jnp.pad((w * gl[f][:, None, :]).reshape(L, M), pad)
                 for f in range(F))
    keys, *cols = lax.sort((keys,) + cols, dimension=1, num_keys=1)
    cols = _run_sums(keys, cols)
    search = [l for l in range(L) if rows[l] <= N]
    write = [l for l in range(L) if rows[l] > N]
    tracing.record_table_readout(len(search), len(write))

    def levels(x, ls):
        return x if len(ls) == L else jnp.concatenate([x[l:l + 1] for l in ls])

    with jax.named_scope(tracing.TABLE_ROWS):
        parts = []
        if search:
            R = max(rows[l] for l in search)
            dt = _read_rows(levels(keys, search),
                            [levels(c, search) for c in cols], R)
            if R < table_size:
                dt = jnp.pad(dt, ((0, 0), (0, table_size - R), (0, 0)))
            parts.append((search, dt))
        if write:
            parts.append((write, _write_runs(
                levels(keys, write), [levels(c, write) for c in cols],
                table_size)))
        if len(parts) == 1:
            dt = parts[0][1]
        else:
            at = {l: part[i] for ls, part in parts for i, l in enumerate(ls)}
            dt = jnp.stack([at[l] for l in range(L)])              # (L,T,F)
    return dt.astype(g.dtype)


def _read_rows(keys, cols, n_rows: int):
    """(L, n_rows, F): each of the first ``n_rows`` rows reads the total of
    its run from the sorted, run-summed ``cols`` after a search for the
    run's end (a gather a row and column): the cost follows the rows."""
    rows = jnp.arange(n_rows, dtype=keys.dtype)
    count = _count_at_most(keys, rows)                             # (L,R)
    last = jnp.maximum(count - 1, 0)
    hit = count > jnp.pad(count[:, :-1], ((0, 0), (1, 0)))        # a run at t
    return jnp.stack([jnp.where(hit, jnp.take_along_axis(c, last, 1), 0.0)
                      for c in cols], -1)


def _write_runs(keys, cols, table_size: int):
    """(L, T, F): each run's total, at the run's end in the sorted, run-summed
    ``cols``, at its row, by :func:`_write_tiles`. The runs' ends are sorted
    to the front first (a second sort of the keys with the totals): a
    level's run ends are then unique, ascending rows, each read once. The
    levels are written one after another, so that only one level's windows
    are held at a time."""
    nxt = jnp.pad(keys[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    end = (keys != nxt) & (keys < table_size)                      # run ends
    keys, *cols = lax.sort((jnp.where(end, keys, table_size),) + tuple(cols),
                           dimension=1, num_keys=1)
    return lax.map(lambda level: _write_tiles(*level, table_size),
                   (keys, jnp.stack(cols, 1)))


def _write_tiles(keys, totals, table_size: int):
    """(T, F) rows of one level from ``keys`` (M,), its rows unique and
    ascending, then ``table_size`` in the places past its last, and their
    ``totals`` (F, M): each key's totals at its row, 0 in every other.

    The rows are written by tiles of ``_TILE``. A tile's keys are at most
    ``_TILE`` from its first (the count of keys below its first row, by
    :func:`_count_at_most`), so they lie in the two aligned blocks of
    ``_TILE`` entries from the one that holds it. Each tile reads those two
    blocks (whole blocks: a gather of aligned slices) and picks its rows out
    of them by a one-hot product, so nothing is scattered and the cost
    follows the tiles; a key outside the tile, or ``table_size``, matches no
    row of it. The product is exact: each total is split into three bf16
    parts that lie along the contraction, so each row accumulates its
    total's parts, times an exact 1, in f32. The rows come out F-major by
    tile, as the tables are laid out on a TPU."""
    F, M = totals.shape
    B = _TILE
    n = -(-table_size // B)
    nb = M // B + 2                        # blocks of entries, the last spare
    first = jnp.arange(n, dtype=keys.dtype) * B
    block = _count_at_most(keys[None], first - 1)[0] // B          # (n,)
    keys = jnp.pad(keys, (0, nb * B - M),
                   constant_values=table_size).reshape(nb, B)
    totals = jnp.pad(totals, ((0, 0), (0, nb * B - M)))
    parts = _bf16_parts(totals.reshape(F, nb, B).transpose(1, 0, 2))

    def windows(x):
        return jax.vmap(lambda b: lax.dynamic_slice_in_dim(x, b, 2))(block)

    k = windows(keys)                                              # (n,2,B)
    v = windows(parts)                                             # (n,2,3,F,B)
    row = first[:, None] + jnp.arange(B, dtype=keys.dtype)         # (n,B)
    # the keys broadcast over the parts before the compare, so the compiler
    # makes the one-hot inside the product instead of writing it out
    k = jnp.broadcast_to(k[:, :, None, :, None], (n, 2, 3, B, 1))
    onehot = (k == row[:, None, None, None, :]).astype(jnp.bfloat16)
    rows = jnp.einsum("tjpfe,tjper->tfr", v, onehot,
                      preferred_element_type=jnp.float32)          # (n,F,B)
    return rows.transpose(1, 0, 2).reshape(F, n * B)[:, :table_size].T


def _bf16_parts(x):
    """f32 ``x`` (..., F, B) -> bf16 (..., 3, F, B): three parts, high first,
    whose sum in f32 is ``x`` exactly, in any order. Each part is what is
    left with the low 16 bits of the word cut off, so no conversion
    rounds."""
    def cut(v):
        bits = lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return lax.bitcast_convert_type(bits, jnp.float32)

    hi = cut(x)
    mid = cut(x - hi)
    return jnp.stack([hi, mid, x - hi - mid], -3).astype(jnp.bfloat16)


def _run_sums(keys, cols):
    """Inclusive segmented scan of the sorted (L, M) ``cols``: each element
    gets its run's sum up to itself. The M elements are read as 128
    columns of M/128 consecutive ones laid along the major axis, so the
    scan shifts whole rows, never lanes; each column's last partial sum is
    then scanned across the columns and carried into the next column's
    leading run."""
    L, M = keys.shape
    R = M // _COLUMNS

    def columns(x):
        return x.reshape(L, _COLUMNS, R).transpose(0, 2, 1)       # (L,R,128)

    k = columns(keys)
    cols = _scan_runs(k, [columns(c) for c in cols], axis=1)
    tails = _scan_runs(k[:, -1], [c[:, -1] for c in cols], axis=1)  # (L,128)
    before = ((0, 0), (1, 0))
    carry = k == jnp.pad(k[:, -1, :-1], before, constant_values=-1)[:, None]
    cols = [c + jnp.where(carry, jnp.pad(t[:, :-1], before)[:, None], 0.0)
            for c, t in zip(cols, tails)]
    return [c.transpose(0, 2, 1).reshape(L, M) for c in cols]


def _scan_runs(keys, cols, axis):
    """Inclusive segmented scan along ``axis`` (Hillis-Steele): step d adds
    the element d back wherever it holds the same key, i.e. the same run."""
    n = keys.shape[axis]
    widths = [(0, 0)] * keys.ndim
    d = 1
    while d < n:
        same = (lax.slice_in_dim(keys, d, n, axis=axis)
                == lax.slice_in_dim(keys, 0, n - d, axis=axis))
        widths[axis] = (d, 0)
        cols = [c + jnp.pad(jnp.where(same, lax.slice_in_dim(c, 0, n - d,
                                                             axis=axis), 0.0),
                            widths) for c in cols]
        d *= 2
    return cols


def _count_at_most(keys, rows):
    """keys (L, M) sorted along M, M a multiple of ``_STRIDE``; rows (T,) ->
    (L, T): how many keys of each level are <= each row. Every
    ``_STRIDE``-th key is compared with every row, and a binary search
    finishes inside the one stride left: on a TPU each gather step costs
    more than the comparisons it saves."""
    M = keys.shape[1]
    coarse = keys[:, _STRIDE - 1::_STRIDE]                         # (L,M/S)
    count = _STRIDE * jnp.sum(coarse[:, None, :] <= rows[:, None], axis=-1,
                              dtype=jnp.int32)
    step = _STRIDE // 2
    while step:
        cand = count + step
        probe = jnp.take_along_axis(keys, jnp.minimum(cand, M) - 1, 1)
        count = jnp.where((cand <= M) & (probe <= rows), cand, count)
        step //= 2
    return count


_hash_encode.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------- #
# Grid-access contract (repro.analysis grid_write_safety / hbm_traffic)
# --------------------------------------------------------------------------- #
from repro.analysis.grid import register_discipline  # noqa: E402

register_discipline(
    "_encode_kernel",
    # the (BLOCK_N, 3) coords block is re-streamed once per hash level (the
    # level axis is the outer grid dim); table and output blocks single-pass.
    # Worst-case actual/ideal traffic is 1 + 12(L-1)/(12 + 4F*L) < 2.5 for
    # any level count at F >= 2 (the output array grows with L too).
    input_refetch=("in[0]",),
    traffic_factor=2.5,
    note="coords re-fetched per level; table/output blocks move once")
