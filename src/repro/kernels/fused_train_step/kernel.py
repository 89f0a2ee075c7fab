"""Pallas kernel: one DVNR train step — (optionally) batch sampling + fwd +
hand-derived bwd + gated AdamW — as a SINGLE ``pallas_call`` (the
tiny-cuda-nn "fully fused" training regime, translated to TPU blocking).

Grid = (P partitions, N/BLOCK_N batch tiles), partition-major. Per partition:
  - the hash tables, MLP weights, Adam moments (and f32 masters under the
    mixed-precision policy) are pinned in VMEM for all batch tiles — one HBM
    round trip per partition per step instead of one per op;
  - with the SAMPLING stage fused (``fused_train_step_sampling_pallas``) the
    ghost-padded local volume is pinned alongside and each tile derives its
    own coordinates from the counter-based RNG of
    :mod:`repro.core.sampling` (global sample ids as Threefry counters, so
    tiling does not change the draws) and gathers its trilinear targets
    in-VMEM — no coordinates, targets or RNG keys ever materialize in HBM;
  - each (BLOCK_N, 3) coordinate tile runs encode -> MLP -> L1 cotangent ->
    MLP backward -> 8-corner scatter-add entirely in VMEM/VREGs, accumulating
    f32 gradients into scratch across tiles (the TPU grid is sequential, so
    ``+=`` accumulation is safe — the MXU-friendly replacement for CUDA's
    atomics);
  - the LAST tile of each partition applies the bias-corrected, gated AdamW
    update in-kernel and writes the new params / moments / masters, so no
    gradient or intermediate activation ever materializes in HBM.

Mixed precision follows the stack's ``Precision`` policy: forward/backward
matmuls run in the compute dtype (bf16 under ``"bf16"``), the sampling stage
is always f32 (coordinates/targets are f32 on every path), gradient
accumulation and the optimizer update are f32, and the new working params are
re-derived from the f32 master by casting — the exact sequence of
:meth:`repro.optim.adamw.AdamW.step`.

The schedule scalars (lr, bias corrections, convergence gate) arrive via
scalar prefetch as a (P, 4) table — they depend on the traced step counter,
which the scan-fused chunk advances on device; the sampling variant prefetches
the (P, 2) uint32 per-(step, partition) seed words next to them.

VMEM budget: params + m + v (+ master) + f32 grad scratch ~= 5 f32 copies of
the per-partition model, plus the sampling stage's volume traffic; the III-B
adaptive rule keeps per-partition T at 2^11..2^13 under strong scaling
(<= ~2 MB at F=4), well inside the ~16 MB VMEM envelope. The sampling stage
has two layouts: the PINNED kernel holds the whole ghost-padded volume in
VMEM (smoke/in situ sizes), and the brick-TILED kernel
(:func:`fused_train_step_sampling_tiled_pallas`) keeps the volume in HBM and
streams (bx, by, bz) bricks through a double-buffered VMEM block — banking
each brick's trilinear corner values into scratch before the batch tiles run
— which is what fits production 256^3 partitions. Dispatch between them is
``ops.resolve_sampling_brick`` (the ``DVNRConfig.sampling_brick`` knob).
Giant-table offline configs (T=2^16+) still need a table-sharded grid axis —
a TPU-hardware follow-up. Validated in interpret mode on CPU (the CI backend
matrix runs it on every push).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampling import counter_coords

BLOCK_N = 512
_P0, _P1, _P2 = 1, 2_654_435_761, 805_459_861
_STATE_KEYS = ("tab", "win", "whid", "wout")


def _encode_fwd(res_ref, coords, tables, cdt):
    """Forward hash encoding for all L levels of one partition; returns the
    (BN, L*F) feature block plus the (idx, ww) corner residuals the backward
    scatter reuses (same residual trick as the ``fused`` backend)."""
    L, T, F = tables.shape
    feats, residuals = [], []
    for l in range(L):
        res = res_ref[l]
        rf = res.astype(coords.dtype)
        pos = coords * rf
        lo = jnp.clip(jnp.floor(pos), 0,
                      jnp.maximum(rf - 1, 0)).astype(jnp.int32)
        w = pos - lo.astype(coords.dtype)
        n_dense = (res + 1) * (res + 1) * (res + 1)
        rp1 = (res + 1).astype(jnp.uint32)
        acc = jnp.zeros((coords.shape[0], F), cdt)
        idxs, wws = [], []
        for dx in (0, 1):
            for dy in (0, 1):
                for dz in (0, 1):
                    cx = (lo[:, 0] + dx).astype(jnp.uint32)
                    cy = (lo[:, 1] + dy).astype(jnp.uint32)
                    cz = (lo[:, 2] + dz).astype(jnp.uint32)
                    dense = cx + rp1 * (cy + rp1 * cz)
                    hashed = (cx * jnp.uint32(_P0)) ^ (cy * jnp.uint32(_P1)) \
                        ^ (cz * jnp.uint32(_P2))
                    idx = (jnp.where(n_dense <= T, dense, hashed)
                           % jnp.uint32(T)).astype(jnp.int32)
                    ww = (jnp.where(dx, w[:, 0], 1 - w[:, 0])
                          * jnp.where(dy, w[:, 1], 1 - w[:, 1])
                          * jnp.where(dz, w[:, 2], 1 - w[:, 2]))
                    acc = acc + ww[:, None].astype(cdt) * jnp.take(
                        tables[l].astype(cdt), idx, axis=0)
                    idxs.append(idx)
                    wws.append(ww)
        feats.append(acc)
        residuals.append((idxs, wws))
    return jnp.concatenate(feats, axis=-1), residuals


def _gather_trilinear(vol, coords, ghost: int):
    """In-kernel mirror of :func:`repro.data.volume.sample_trilinear`.

    ``vol``: (nx, ny, nz[, C]) ghost-padded partition resident in VMEM;
    ``coords``: (N, 3) f32 in [0,1]^3 over the owned region. Same cell-center
    mapping, index/weight clamping and corner order (dz fastest) as the host
    sampler, expressed as ``jnp.take`` on the flattened volume + an unrolled
    8-corner weighted sum so it is Pallas-legal."""
    nx, ny, nz = vol.shape[0], vol.shape[1], vol.shape[2]
    chan = vol.ndim == 4
    flat = vol.reshape((nx * ny * nz,) + vol.shape[3:])
    los, ws = [], []
    for ax, n in enumerate((nx, ny, nz)):
        owned = jnp.float32(n - 2 * ghost)
        pos = coords[:, ax] * owned - 0.5 + jnp.float32(ghost)
        lo = jnp.clip(jnp.floor(pos), 0.0, jnp.float32(n - 2))
        los.append(lo.astype(jnp.int32))
        ws.append(jnp.clip(pos - lo, 0.0, 1.0))
    acc = None
    for dx in (0, 1):
        for dy in (0, 1):
            for dz in (0, 1):
                lin = ((los[0] + dx) * ny + (los[1] + dy)) * nz + (los[2] + dz)
                vals = jnp.take(flat, lin, axis=0)        # (N[, C])
                ww = (ws[0] if dx else 1.0 - ws[0]) \
                    * (ws[1] if dy else 1.0 - ws[1]) \
                    * (ws[2] if dz else 1.0 - ws[2])
                term = ww[:, None] * vals if chan else ww * vals
                acc = term if acc is None else acc + term
    return acc


def _train_step_core(res_ref, sc_ref, coords, target, refs,
                     g_tab, g_win, g_whid, g_wout, loss_acc,
                     *, p, i, n_tiles, n_hidden, n_valid, b1, b2, eps, wd,
                     cdt, has_master):
    """The shared per-tile body: forward, L1 cotangent, backward scatter and
    (on the last tile) the gated AdamW update. ``coords``/``target`` are the
    tile's (BN, 3)/(BN, D_out) f32 arrays — read from HBM-fed refs by the
    plain kernel, derived in-VMEM by the sampling kernels. ``p``/``i``/
    ``n_tiles`` are the partition id and batch-tile position: the grid axes
    for the pinned kernels, ``s - n_bricks`` on the second axis for the
    brick-tiled sampling kernel (whose grid interleaves brick-gather steps
    before the batch tiles; program_id must be read OUTSIDE ``pl.when``
    branches, hence the parameters). ``refs``: flat input/output state refs,
    unpacked below (param/m/v[/mw] groups)."""
    (tab_ref, win_ref, whid_ref, wout_ref,
     m_tab_ref, m_win_ref, m_whid_ref, m_wout_ref,
     v_tab_ref, v_win_ref, v_whid_ref, v_wout_ref) = refs[:12]
    refs = refs[12:]
    if has_master:
        mw_tab_ref, mw_win_ref, mw_whid_ref, mw_wout_ref = refs[:4]
        refs = refs[4:]
    (o_tab_ref, o_win_ref, o_whid_ref, o_wout_ref,
     om_tab_ref, om_win_ref, om_whid_ref, om_wout_ref,
     ov_tab_ref, ov_win_ref, ov_whid_ref, ov_wout_ref) = refs[:12]
    refs = refs[12:]
    if has_master:
        omw_tab_ref, omw_win_ref, omw_whid_ref, omw_wout_ref = refs[:4]
        refs = refs[4:]
    (loss_ref,) = refs

    @pl.when(i == 0)
    def _reset():
        g_tab[...] = jnp.zeros_like(g_tab)
        g_win[...] = jnp.zeros_like(g_win)
        g_whid[...] = jnp.zeros_like(g_whid)
        g_wout[...] = jnp.zeros_like(g_wout)
        loss_acc[...] = jnp.zeros_like(loss_acc)

    tables = tab_ref[0]                               # (L, T, F) param dtype
    w_in = win_ref[0].astype(cdt)
    w_hid = whid_ref[0].astype(cdt)
    w_out = wout_ref[0].astype(cdt)
    L, F = tables.shape[0], tables.shape[2]

    # ---------------- forward (activations stay in VMEM/VREGs) ------------ #
    x, residuals = _encode_fwd(res_ref, coords, tables, cdt)
    acts = [jnp.maximum(x @ w_in, 0.0)]
    for k in range(n_hidden - 1):                     # static unroll
        acts.append(jnp.maximum(acts[-1] @ w_hid[k], 0.0))
    pred = acts[-1] @ w_out                           # (BN, D_out)

    # ------------- L1 loss + cotangent, masked past n_valid --------------- #
    row = i * coords.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, (coords.shape[0], 1), 0)
    mask = (row < n_valid).astype(jnp.float32)
    diff = pred.astype(jnp.float32) - target
    loss_acc[0, 0] += jnp.sum(jnp.abs(diff) * mask)
    g = (jnp.sign(diff) * mask / (n_valid * target.shape[1])).astype(cdt)

    # ---------------- MLP backward (f32 grad accumulation) ----------------- #
    g_wout[...] += (acts[-1].T @ g).astype(jnp.float32)
    d = g @ w_out.T
    for k in range(n_hidden - 2, -1, -1):
        d = d * (acts[k + 1] > 0)
        g_whid[k] += (acts[k].T @ d).astype(jnp.float32)
        d = d @ w_hid[k].T
    d = d * (acts[0] > 0)
    g_win[...] += (x.T @ d).astype(jnp.float32)
    d = d @ w_in.T                                    # (BN, L*F) feat cotangent

    # -------- hash-encode backward: 8-corner combining scatter ------------- #
    gt = g_tab[...]
    for l in range(L):
        gl = d[:, l * F:(l + 1) * F].astype(jnp.float32)
        idxs, wws = residuals[l]
        for idx, ww in zip(idxs, wws):
            gt = gt.at[l, idx].add(ww.astype(jnp.float32)[:, None] * gl)
    g_tab[...] = gt

    # ------------- gated AdamW on the last tile of this partition ---------- #
    @pl.when(i == n_tiles - 1)
    def _adamw():
        lr, bc1, bc2, gate = (sc_ref[p, 0], sc_ref[p, 1],
                              sc_ref[p, 2], sc_ref[p, 3])

        def upd(g32, m, v, master):
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            delta = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps)
            if wd:
                delta = delta + wd * master.astype(jnp.float32)
            u = (-lr * delta).astype(master.dtype)
            return master + (gate * u).astype(master.dtype), m32, v32

        groups = [
            (g_tab[...], m_tab_ref, v_tab_ref, tab_ref,
             o_tab_ref, om_tab_ref, ov_tab_ref),
            (g_win[...], m_win_ref, v_win_ref, win_ref,
             o_win_ref, om_win_ref, ov_win_ref),
            (g_whid[...], m_whid_ref, v_whid_ref, whid_ref,
             o_whid_ref, om_whid_ref, ov_whid_ref),
            (g_wout[...], m_wout_ref, v_wout_ref, wout_ref,
             o_wout_ref, om_wout_ref, ov_wout_ref),
        ]
        masters = ([mw_tab_ref, mw_win_ref, mw_whid_ref, mw_wout_ref]
                   if has_master else [grp[3] for grp in groups])
        m_outs = ([omw_tab_ref, omw_win_ref, omw_whid_ref, omw_wout_ref]
                  if has_master else [None] * 4)
        for (g32, m_ref, v_ref, p_ref, o_ref, om_ref, ov_ref), mw_ref, omw_ref \
                in zip(groups, masters, m_outs):
            new_master, m32, v32 = upd(g32, m_ref[0], v_ref[0], mw_ref[0])
            om_ref[0], ov_ref[0] = m32, v32
            if has_master:
                omw_ref[0] = new_master
                o_ref[0] = new_master.astype(p_ref.dtype)
            else:
                o_ref[0] = new_master
        loss_ref[0, 0] = loss_acc[0, 0] / (n_valid * target.shape[1])


# --------------------------------------------------------------------------- #
# shared pallas_call layout
# --------------------------------------------------------------------------- #
def _full_spec(shape):
    """One partition's full block, indexed by the partition grid axis."""
    return pl.BlockSpec((1,) + tuple(shape),
                        lambda p, i, *_: (p,) + (0,) * len(shape))


def _state_layout(params, moments_m, moments_v, masters, P):
    """Specs/out-shapes/operands/scratch for the param+m+v[+mw] state groups
    (shared by both kernel variants)."""
    has_master = masters is not None
    shapes = {k: params[k].shape[1:] for k in _STATE_KEYS}
    group_specs = [_full_spec(shapes[k]) for k in _STATE_KEYS]
    state_specs = group_specs * (3 + has_master)
    out_specs = group_specs * (3 + has_master) \
        + [pl.BlockSpec((1, 1), lambda p, i, *_: (p, 0))]
    param_shapes = [jax.ShapeDtypeStruct((P,) + shapes[k], params[k].dtype)
                    for k in _STATE_KEYS]
    f32_shapes = [jax.ShapeDtypeStruct((P,) + shapes[k], jnp.float32)
                  for k in _STATE_KEYS]
    out_shape = param_shapes + f32_shapes * (2 + has_master) \
        + [jax.ShapeDtypeStruct((P, 1), jnp.float32)]
    operands = [params[k] for k in _STATE_KEYS] \
        + [moments_m[k] for k in _STATE_KEYS] \
        + [moments_v[k] for k in _STATE_KEYS] \
        + ([masters[k] for k in _STATE_KEYS] if has_master else [])
    scratch = [pltpu.VMEM(shapes[k], jnp.float32) for k in _STATE_KEYS] \
        + [pltpu.VMEM((1, 1), jnp.float32)]
    return shapes, state_specs, out_specs, out_shape, operands, scratch


def _unpack_outs(outs, has_master):
    unpack = lambda flat: dict(zip(_STATE_KEYS, flat))
    new_params = unpack(outs[0:4])
    new_m = unpack(outs[4:8])
    new_v = unpack(outs[8:12])
    new_masters = unpack(outs[12:16]) if has_master else None
    loss = outs[-1][:, 0]
    return new_params, new_m, new_v, new_masters, loss


@functools.partial(
    jax.jit, static_argnames=("n_hidden", "compute_dtype", "beta1", "beta2",
                              "eps", "weight_decay", "interpret"))
def fused_train_step_pallas(coords, target, params, moments_m, moments_v,
                            masters, scalars, resolutions, *, n_hidden: int,
                            compute_dtype, beta1: float, beta2: float,
                            eps: float, weight_decay: float,
                            interpret: bool):
    """One fused train step for P stacked partitions (host-sampled batch).

    coords (P, N, 3) f32; target (P, N, D_out) f32; ``params`` / ``moments_m``
    / ``moments_v`` / ``masters`` are dicts with keys ``tab`` (P, L, T, F),
    ``win`` (P, D_in, W), ``whid`` (P, max(H-1,1), W, W), ``wout``
    (P, W, D_out) (``masters=None`` when the params are their own master);
    scalars (P, 4) f32 rows of [lr, 1-b1^t, 1-b2^t, gate]; resolutions (L,)
    int32. Returns ``(new_params, new_m, new_v, new_masters, loss)`` in the
    same stacked layout, loss (P,) f32.
    """
    has_master = masters is not None
    P, N = coords.shape[0], coords.shape[1]
    n_pad = (-N) % BLOCK_N
    coords_p = jnp.pad(coords, ((0, 0), (0, n_pad), (0, 0)))
    target_p = jnp.pad(target, ((0, 0), (0, n_pad), (0, 0)))
    n_tiles = (N + n_pad) // BLOCK_N
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None \
        else params["tab"].dtype
    _, state_specs, out_specs, out_shape, operands, scratch = \
        _state_layout(params, moments_m, moments_v, masters, P)

    def tile(*shape):
        return pl.BlockSpec((1, BLOCK_N) + shape,
                            lambda p, i, *_: (p, i) + (0,) * len(shape))

    def _step_kernel(res_ref, sc_ref, coords_ref, target_ref, *refs):
        _train_step_core(res_ref, sc_ref, coords_ref[0], target_ref[0],
                         refs[:-5], *refs[-5:],
                         p=pl.program_id(0), i=pl.program_id(1),
                         n_tiles=pl.num_programs(1),
                         n_hidden=n_hidden, n_valid=N, b1=beta1, b2=beta2,
                         eps=eps, wd=weight_decay, cdt=cdt,
                         has_master=has_master)

    outs = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(P, n_tiles),
            in_specs=[tile(3), tile(target.shape[2])] + state_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(resolutions.astype(jnp.int32), scalars.astype(jnp.float32),
      coords_p, target_p, *operands)
    return _unpack_outs(outs, has_master)


@functools.partial(
    jax.jit, static_argnames=("n_batch", "n_uniform", "sigma", "ghost",
                              "n_hidden", "compute_dtype", "beta1", "beta2",
                              "eps", "weight_decay", "interpret"))
def fused_train_step_sampling_pallas(volumes, seeds, params, moments_m,
                                     moments_v, masters, scalars, resolutions,
                                     *, n_batch: int, n_uniform: int,
                                     sigma: float, ghost: int, n_hidden: int,
                                     compute_dtype, beta1: float, beta2: float,
                                     eps: float, weight_decay: float,
                                     interpret: bool):
    """One fused train step for P stacked partitions, sampling INCLUDED.

    Instead of the host-sampled ``coords``/``target`` pair this variant takes
    the stacked ghost-padded volumes (P, nx+2g, ny+2g, nz+2g[, C]) and the
    per-(step, partition) counter seeds (P, 2) uint32 (from
    :func:`repro.core.sampling.step_seeds`); every batch tile derives its own
    coordinates with :func:`repro.core.sampling.counter_coords` (rows are
    global sample ids, so the draws are tile-count-invariant and bit-identical
    to the host sampler) and gathers the trilinear targets from the VMEM-
    pinned volume. State layout and returns match
    :func:`fused_train_step_pallas`.
    """
    has_master = masters is not None
    P = volumes.shape[0]
    n_tiles = (n_batch + (-n_batch) % BLOCK_N) // BLOCK_N
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None \
        else params["tab"].dtype
    _, state_specs, out_specs, out_shape, operands, scratch = \
        _state_layout(params, moments_m, moments_v, masters, P)

    def _sampling_kernel(res_ref, sc_ref, seed_ref, vol_ref, *refs):
        p = pl.program_id(0)
        i = pl.program_id(1)
        rows = i * BLOCK_N + jax.lax.broadcasted_iota(
            jnp.int32, (BLOCK_N, 1), 0)
        coords = counter_coords(seed_ref[p, 0], seed_ref[p, 1], rows,
                                n_uniform, sigma)
        target = _gather_trilinear(vol_ref[0], coords, ghost)
        if target.ndim == 1:
            target = target[:, None]
        _train_step_core(res_ref, sc_ref, coords, target, refs[:-5],
                         *refs[-5:],
                         p=p, i=i, n_tiles=pl.num_programs(1),
                         n_hidden=n_hidden, n_valid=n_batch, b1=beta1,
                         b2=beta2, eps=eps, wd=weight_decay, cdt=cdt,
                         has_master=has_master)

    outs = pl.pallas_call(
        _sampling_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(P, n_tiles),
            in_specs=[_full_spec(volumes.shape[1:])] + state_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(resolutions.astype(jnp.int32), scalars.astype(jnp.float32),
      seeds.astype(jnp.uint32), volumes, *operands)
    return _unpack_outs(outs, has_master)


def brick_counts(volume_shape, brick) -> tuple:
    """Per-axis brick counts of a ghost-padded (nx, ny, nz[, C]) partition
    under a (bx, by, bz) brick — ``ceil(n / b)`` per axis. The flat brick id
    enumerates x-major, z fastest: ``b = (bx_i * nby + by_i) * nbz + bz_i``
    (the same decomposition the tiled kernel's BlockSpec index map uses)."""
    return tuple(-(-int(n) // int(b))
                 for n, b in zip(volume_shape[:3], brick))


@functools.partial(
    jax.jit, static_argnames=("brick", "n_batch", "n_uniform", "sigma",
                              "ghost", "n_hidden", "compute_dtype", "beta1",
                              "beta2", "eps", "weight_decay", "interpret"))
def fused_train_step_sampling_tiled_pallas(volumes, seeds, params, moments_m,
                                           moments_v, masters, scalars,
                                           resolutions, *, brick,
                                           n_batch: int, n_uniform: int,
                                           sigma: float, ghost: int,
                                           n_hidden: int, compute_dtype,
                                           beta1: float, beta2: float,
                                           eps: float, weight_decay: float,
                                           interpret: bool):
    """The sampling-included fused step with the volume TILED through VMEM.

    Same contract (state layout, seeds, returns, bit-exact draws/targets) as
    :func:`fused_train_step_sampling_pallas`, but the ghost-padded volume
    stays in HBM and streams through VMEM one ``brick`` = (bx, by, bz) block
    at a time — Pallas double-buffers the moving block, so the DMA of brick
    ``s+1`` overlaps the gather over brick ``s``. The second grid axis is
    phase-structured: ``n_bricks`` gather steps, then ``n_tiles`` batch
    tiles, per partition.

    - step ``s == 0`` additionally draws ALL ``n_batch`` coordinates with one
      :func:`repro.core.sampling.counter_coords` call (rows are the same
      global sample ids the pinned kernel uses per tile, so the draws are
      bit-identical) into a (3, N) VMEM scratch;
    - each gather step banks the raw values of the 8 trilinear corners whose
      voxels land in the resident brick into an (8*C, N) scratch — owner
      bricks partition the corner voxels, so every (corner, sample) slot is
      written exactly once per partition sweep. This is the sort-free TPU
      analogue of bucketing the draws by brick: instead of reordering
      samples, each brick claims its corner fetches via owner masks
      (select-on-mask, never multiply — out-of-range boundary bricks are
      padded with uninitialized values);
    - each batch tile re-derives the trilinear weights from the coordinate
      scratch (the exact `_gather_trilinear` expressions over the full
      static volume dims) and sums the banked corner values in the same
      canonical (dx, dy, dz) order, so the assembled targets are bit-exact
      vs the pinned kernel, then runs the unchanged fwd+bwd+AdamW core.

    VMEM: state groups + one double-buffered brick + the two sampling
    scratches — bounded by the brick size, not the partition size, which is
    what lets production 256^3 partitions fit the ~16 MiB envelope.
    """
    has_master = masters is not None
    if volumes.ndim == 4:                   # scalar field: add channel axis
        volumes = volumes[..., None]
    P = volumes.shape[0]
    nx, ny, nz, C = volumes.shape[1:]
    brick = tuple(min(int(b), int(n)) for b, n in zip(brick, (nx, ny, nz)))
    bx, by, bz = brick
    nbx, nby, nbz = brick_counts((nx, ny, nz), brick)
    n_bricks = nbx * nby * nbz
    n_batch_p = n_batch + (-n_batch) % BLOCK_N
    n_tiles = n_batch_p // BLOCK_N
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None \
        else params["tab"].dtype
    _, state_specs, out_specs, out_shape, operands, scratch = \
        _state_layout(params, moments_m, moments_v, masters, P)
    scratch = scratch + [pltpu.VMEM((3, n_batch_p), jnp.float32),
                         pltpu.VMEM((8 * C, n_batch_p), jnp.float32)]

    def vol_index(p, s, *_):
        b = jnp.minimum(s, n_bricks - 1)    # batch tiles re-park on the last
        return (p, b // (nby * nbz), (b // nbz) % nby, b % nbz, 0)

    vol_spec = pl.BlockSpec((1, bx, by, bz, C), vol_index)

    def corner_axes(coords_ax, ax_dim):
        """Per-axis lo index + in-cell weight — the `_gather_trilinear`
        expressions, evaluated from the coordinate scratch."""
        owned = jnp.float32(ax_dim - 2 * ghost)
        pos = coords_ax * owned - 0.5 + jnp.float32(ghost)
        lo = jnp.clip(jnp.floor(pos), 0.0, jnp.float32(ax_dim - 2))
        return lo.astype(jnp.int32), jnp.clip(pos - lo, 0.0, 1.0)

    def _tiled_sampling_kernel(res_ref, sc_ref, seed_ref, vol_ref, *refs):
        p = pl.program_id(0)
        s = pl.program_id(1)
        coords_scr, corners_scr = refs[-2], refs[-1]

        @pl.when(s == 0)
        def _draw():
            rows = jax.lax.broadcasted_iota(jnp.int32, (n_batch_p, 1), 0)
            c = counter_coords(seed_ref[p, 0], seed_ref[p, 1], rows,
                               n_uniform, sigma)
            coords_scr[...] = c.T

        @pl.when(s < n_bricks)
        def _bank():
            bxi = s // (nby * nbz)
            byi = (s // nbz) % nby
            bzi = s % nbz
            los = [corner_axes(coords_scr[ax, :], n)[0]
                   for ax, n in enumerate((nx, ny, nz))]
            flat = vol_ref[0].reshape(bx * by * bz, C)
            k = 0
            for dx in (0, 1):
                for dy in (0, 1):
                    for dz in (0, 1):
                        cx = los[0] + dx
                        cy = los[1] + dy
                        cz = los[2] + dz
                        own = ((cx // bx == bxi) & (cy // by == byi)
                               & (cz // bz == bzi))
                        rx = jnp.clip(cx - bxi * bx, 0, bx - 1)
                        ry = jnp.clip(cy - byi * by, 0, by - 1)
                        rz = jnp.clip(cz - bzi * bz, 0, bz - 1)
                        vals = jnp.take(flat, (rx * by + ry) * bz + rz,
                                        axis=0)            # (N, C)
                        for ch in range(C):
                            corners_scr[k * C + ch, :] = jnp.where(
                                own, vals[:, ch], corners_scr[k * C + ch, :])
                        k += 1

        @pl.when(s >= n_bricks)
        def _train():
            i = s - n_bricks
            sl = pl.ds(i * BLOCK_N, BLOCK_N)
            coords = jnp.stack([coords_scr[ax, sl] for ax in range(3)],
                               axis=-1)                    # (BN, 3) f32
            ws = [corner_axes(coords[:, ax], n)[1]
                  for ax, n in enumerate((nx, ny, nz))]
            acc = None
            k = 0
            for dx in (0, 1):
                for dy in (0, 1):
                    for dz in (0, 1):
                        vals = jnp.stack(
                            [corners_scr[k * C + ch, sl] for ch in range(C)],
                            axis=-1)                       # (BN, C)
                        ww = (ws[0] if dx else 1.0 - ws[0]) \
                            * (ws[1] if dy else 1.0 - ws[1]) \
                            * (ws[2] if dz else 1.0 - ws[2])
                        term = ww[:, None] * vals
                        acc = term if acc is None else acc + term
                        k += 1
            _train_step_core(res_ref, sc_ref, coords, acc, refs[:-7],
                             *refs[-7:-2],
                             p=p, i=i, n_tiles=n_tiles,
                             n_hidden=n_hidden, n_valid=n_batch, b1=beta1,
                             b2=beta2, eps=eps, wd=weight_decay, cdt=cdt,
                             has_master=has_master)

    outs = pl.pallas_call(
        _tiled_sampling_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(P, n_bricks + n_tiles),
            in_specs=[vol_spec] + state_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(resolutions.astype(jnp.int32), scalars.astype(jnp.float32),
      seeds.astype(jnp.uint32), volumes, *operands)
    return _unpack_outs(outs, has_master)
