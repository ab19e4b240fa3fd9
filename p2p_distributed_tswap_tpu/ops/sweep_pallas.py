"""Pallas TPU kernel for the fast-sweeping directional relax.

The round-3 flagship step profile (an ablation on the chip, SCALING.md)
put the replan sweeps at ~88% of step time, so this is THE kernel worth
hand-writing (VERDICT r2 item 6).  The XLA path implements each
directional sweep as a Hillis-Steele doubling scan — log2(axis) rounds of
roll/where/minimum over the whole (R, H, W) batch, ~50 full-array memory
passes per sweep.  A TPU core can instead hold a (H, 128-lane) strip in
VMEM and run the TRUE sequential min-plus recurrence along the scan axis,
vectorized across 128 lanes: one read + one write of the array per sweep,
a ~25x traffic reduction at the 1024^2 flagship.

Recurrence per scan step (segmented min-plus with unit cost; identical
integer math to ops.distance._sweep's affine-trick scan, bit-for-bit):

    run    = min(run + 1, d[i])           # relax from predecessor
    run    = INF            if blocked[i]  # obstacles reset the segment
    out[i] = min(run, INF)  if free else INF

Layout: grid (R, W // 128); each program owns a (H, 128) block of one
field row and scans the full H extent (no cross-program dependency along
the scan axis, so results are exact in one pass — the outer fixpoint loop
in distance_fields is unchanged).  The W-axis sweeps reuse the same kernel
on a transposed view; XLA's transpose costs two passes, still far below
the doubling scan.

Eligibility (``sweep_eligible``): TPU backend, H and W multiples of 128
(covers the 256/512/1024/4096 benchmark grids; the reference's 100x100
falls back to the XLA path, which is already sub-millisecond there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = np.int32(1 << 30)
LANES = 128
# Tests set this to run the kernel through the Pallas interpreter on CPU
# (the compiled path needs a real TPU); production leaves it False.
INTERPRET = False


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    try:
        if jax.default_backend() != "tpu":
            return False
        # CPU-pinned processes (tests/conftest.py, pin_cpu_backend) keep
        # the TPU plugin registered, so default_backend() alone lies:
        # honor the configured default device.  It may be a Device object
        # OR a platform string ('cpu') — treat both forms.
        dd = jax.config.jax_default_device
        if dd is None:
            return True
        platform = dd if isinstance(dd, str) else getattr(dd, "platform", "")
        return platform == "tpu"
    except RuntimeError:
        return False


def sweep_eligible(h: int, w: int) -> bool:
    """Both axes get scanned (W via transpose), so both must be
    lane-aligned."""
    return _on_tpu() and h % LANES == 0 and w % LANES == 0


SUBLANES = 8  # VPU tile height for int32; also the fori_loop stride


def _scan_kernel(reverse: bool, h: int, d_ref, m_ref, o_ref):
    # Tile-strided scan: one (8, 128) aligned VMEM read/write per loop
    # iteration, with the sequential recurrence unrolled statically across
    # the 8 sublanes — 8x fewer loop iterations than a per-row loop and
    # aligned tile accesses instead of (1, 128) slices.
    nt = h // SUBLANES

    def body(t, run):
        base = ((nt - 1 - t) if reverse else t) * SUBLANES
        tile_d = d_ref[pl.ds(base, SUBLANES), :]
        tile_b = m_ref[pl.ds(base, SUBLANES), :] != 0
        rows = [None] * SUBLANES
        order = range(SUBLANES - 1, -1, -1) if reverse else range(SUBLANES)
        for k in order:
            run = jnp.minimum(run + 1, tile_d[k:k + 1, :])
            run = jnp.where(tile_b[k:k + 1, :], INF, run)
            rows[k] = jnp.where(tile_b[k:k + 1, :], INF,
                                jnp.minimum(run, INF))
        o_ref[pl.ds(base, SUBLANES), :] = jnp.concatenate(rows, axis=0)
        return run

    jax.lax.fori_loop(0, nt, body, jnp.full((1, LANES), INF, jnp.int32))


def _sweep_rows(d: jnp.ndarray, blocked: jnp.ndarray,
                reverse: bool) -> jnp.ndarray:
    """Sequential segmented min-plus scan along axis 1 of ``d`` (R, H, W),
    128 lanes at a time.  ``blocked``: (H, W) int32, nonzero = obstacle."""
    r, h, w = d.shape
    kernel = functools.partial(_scan_kernel, reverse, h)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((r, h, w), jnp.int32),
        grid=(r, w // LANES),
        in_specs=[
            pl.BlockSpec((None, h, LANES), lambda ri, si: (ri, 0, si),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h, LANES), lambda ri, si: (0, si),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((None, h, LANES), lambda ri, si: (ri, 0, si),
                               memory_space=pltpu.VMEM),
        interpret=INTERPRET,
        name="sweep_rows",
    )(d, blocked)


def sweep(d: jnp.ndarray, free2d: jnp.ndarray, axis: int,
          reverse: bool) -> jnp.ndarray:
    """Drop-in directional sweep: exact replacement for
    ops.distance._sweep's result on eligible shapes.

    Dispatches to the FULL-ROW kernel (_sweep8_rows: segments of one grid
    row packed onto the 8 VPU sublanes, any batch size) when the row shape
    supports it — W a multiple of 1024 or at most 1024, H compatible with
    the HBLK streaming — falling back to the round-3 single-field-strip
    kernel otherwise.

    Args:
      d: (R, H, W) int32 distance batch.
      free2d: (H, W) bool, True = traversable.
      axis: 1 (scan along H) or 2 (scan along W, via transpose).
      reverse: scan direction.
    """
    blocked = (~free2d).astype(jnp.int32)
    if axis == 1:
        return _dispatch_rows(d, blocked, reverse)
    assert axis == 2
    out = _dispatch_rows(d.swapaxes(1, 2), blocked.T, reverse)
    return out.swapaxes(1, 2)


def _dispatch_rows(d: jnp.ndarray, blocked: jnp.ndarray,
                   reverse: bool) -> jnp.ndarray:
    r, h, w = d.shape
    if sweep8_eligible(h, w):
        return _sweep8_rows(d, blocked, reverse)
    return _sweep_rows(d, blocked, reverse)


# --- full-row kernel (round 4) ----------------------------------------
#
# The roofline (analysis/roofline.py, SCALING.md) puts the flagship step at
# ~6% of the HBM bound: the sweep is VECTOR-ISSUE bound, because the
# single-field kernel's recurrence advances on (1, 128)-wide row slices —
# 7/8 of every VPU issue wasted, and a separate program per 128-lane strip.
# The fix needs NO data movement: viewing each grid row's W cells as
# (S segments x 128 lanes) — a pure reshape — makes ONE aligned (S, 128)
# tile hold up to 1024 consecutive cells of a row, so each scan step
# advances a whole row per issue (every (segment, lane) cell's column scan
# is independent; the recurrence only chains along H).  H streams through
# a sequential grid dimension with the running minimum carried in VMEM
# scratch, so VMEM stays ~6 MB/program and ANY lane-aligned H works
# (including 4096).  Fields are a parallel grid dimension — no multiple-
# of-8 batch restriction.
#
# (Two rejected designs, measured on-chip: transposing fields onto the
# sublane dim costs a 56 ms/32 MB leading-dim relayout that dwarfs the
# win, and dynamic per-row ref indexing inside the kernel lowers ~27x
# slower than chunked pl.ds access.)

HBLK = 512     # rows per sequential block: 3 x 2 MB VMEM at S = 8
MAX_SEGS = 8   # sublane packing: segments of one row per tile


def _segments(w: int) -> int:
    """Sublane segment count for a W-cell row; 0 = row shape unsupported."""
    q = w // LANES
    if q >= MAX_SEGS and q % MAX_SEGS == 0:
        return MAX_SEGS
    if 1 <= q <= MAX_SEGS:
        return q
    return 0


def sweep8_eligible(h: int, w: int) -> bool:
    """Row-shape gate for the full-row kernel: batch size is unrestricted
    (fields are a parallel grid dimension).  H must be sublane-aligned —
    _scan8_kernel iterates hblk // SUBLANES tiles and would silently drop
    the last h % SUBLANES rows otherwise."""
    return (_segments(w) > 0 and h % SUBLANES == 0
            and (h % HBLK == 0 or h <= HBLK))


def _scan8_kernel(reverse: bool, hblk: int, segs: int,
                  d_ref, m_ref, o_ref, run_ref):
    hi = pl.program_id(2)

    @pl.when(hi == 0)
    def _init():
        run_ref[...] = jnp.full((segs, LANES), INF, jnp.int32)

    nt = hblk // SUBLANES

    def body(t, run):
        base = ((nt - 1 - t) if reverse else t) * SUBLANES
        chunk = d_ref[0, pl.ds(base, SUBLANES), 0]      # (8, S, 128)
        mrows = m_ref[pl.ds(base, SUBLANES), 0] != 0    # (8, S, 128)
        rows = [None] * SUBLANES
        order = range(SUBLANES - 1, -1, -1) if reverse else range(SUBLANES)
        for k in order:
            bl = mrows[k]
            run = jnp.minimum(run + 1, chunk[k])
            run = jnp.where(bl, INF, run)
            rows[k] = jnp.where(bl, INF, jnp.minimum(run, INF))
        o_ref[0, pl.ds(base, SUBLANES), 0] = jnp.stack(rows, axis=0)
        return run

    run_ref[...] = jax.lax.fori_loop(0, nt, body, run_ref[...])


def _sweep8_rows(d: jnp.ndarray, blocked: jnp.ndarray,
                 reverse: bool) -> jnp.ndarray:
    """Segmented min-plus scan along axis 1 of ``d`` (R, H, W), one full
    row (up to S x 128 cells) per issue.  Bit-identical to _sweep_rows."""
    r, h, w = d.shape
    segs = _segments(w)
    nchunk = w // (segs * LANES)
    hblk = min(h, HBLK)
    nh = h // hblk
    d5 = d.reshape(r, h, nchunk, segs, LANES)          # pure view
    m4 = blocked.reshape(h, nchunk, segs, LANES)
    kernel = functools.partial(_scan8_kernel, reverse, hblk, segs)

    def hmap(hi):
        return (nh - 1 - hi) if reverse else hi

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(d5.shape, jnp.int32),
        grid=(r, nchunk, nh),
        in_specs=[
            pl.BlockSpec((1, hblk, 1, segs, LANES),
                         lambda ri, ci, hi: (ri, hmap(hi), ci, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hblk, 1, segs, LANES),
                         lambda ri, ci, hi: (hmap(hi), ci, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, hblk, 1, segs, LANES),
                               lambda ri, ci, hi: (ri, hmap(hi), ci, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((segs, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=INTERPRET,
        name="sweep8_rows",
    )(d5, m4)
    return out.reshape(r, h, w)
