"""Fused Pallas direction-field kernels: goal seed -> BFS fixpoint ->
next-hop direction codes, everything on-chip.

Two variants share this module:

- **multi** (``_multi_kernel``, ISSUE 9 "v2", the default under
  ``MAPD_FUSED=1``): EIGHT fields per Pallas program, packed across the
  sublane dimension — grid ``(ceil(G/8),)``, the layout the round-3/4
  roofline named as the GO signal.  The single-field kernel below lost
  on-chip because its sequential row recurrence advances on (1, W) row
  slices, idling 7/8 of every VPU issue; with fields on sublanes the
  same recurrence advances a full (8, W) tile per grid row — one row of
  ALL EIGHT fields per issue.  Layout: the distance scratch is
  ``(H + 2, 8, W)`` int32 — grid rows live on the UNTILED leading
  dimension (so single-row halos and arbitrary dynamic row indices are
  legal; the tiled plane is the (8 fields, W lanes) tile), with a
  one-row INF halo above and below.  Lane (x) passes run the in-register
  segmented doubling scan per (8, W) row plane; the one shared obstacle
  mask rides as ``(H, 1, W)`` and broadcasts up the sublane dim.
  STATUS: bit-identical to the XLA pipeline in interpreter mode
  (tests/test_field_fused.py); this container has no TPU attached, so
  the on-chip win could NOT be measured this round — the kernel stays
  OPT-IN (``MAPD_FUSED=1``) until a real-step measurement lands
  (results/field_engine_r11.json records the NO-GO-by-default decision
  and the measurement recipe).

- **single** (``_kernel``, the round-3 experiment, ``MAPD_FUSED=single``):
  one whole field per program.  Validated bit-identical on-chip and
  measured SLOWER in real steps (medium 35 -> 66 ms/step, flagship
  127 -> 156): grid programs serialize per core and the per-(8, W)-tile
  loop bodies underfill the VPU, while the XLA pipeline overlaps its
  doubling scans across the whole field batch.  Kept as the measured
  baseline the multi-field variant is built from.

The single-field kernel keeps one whole field resident in VMEM and does
EVERYTHING on-chip:

- seeds the distance field from the goal cell,
- iterates fast-sweeping rounds (4 directional passes) to the exact BFS
  fixpoint with an on-chip convergence flag,
- derives the reference-ordered next-hop codes (DIR_DXDY tie-break,
  stay conditions) — emitting (H, W) uint8 codes per field.

Per-field HBM traffic drops to: read mask once + write codes once.

Layout: grid (G,); each program owns one field.  The distance scratch is
(H+16, W): one full 8-row INF halo TILE above and below the field, so
every ref access — sweeps, and the neighbor-tile reads in the code
extraction — is an 8-aligned (8, W) block (Mosaic requires dynamic
sublane indices provably divisible by the tile height; single-row halos
do not lower).  Row (y) passes run the sequential min-plus recurrence
over (8, W) sublane tiles; lane (x) passes run an in-register segmented
doubling scan along lanes per (8, W) tile (all VMEM, no HBM traffic).
Row-neighbor values for the code extraction come from statically sliced
register concatenations of the adjacent aligned tiles.

Eligibility (``fused_eligible``): TPU backend, H % 8 == 0,
W % 128 == 0, and the VMEM working set (distance scratch + mask + codes
+ doubling temporaries) fits — fields up to ~1024x1024.  Larger grids
(4096^2) keep the strip kernel.

Bit-identity: the integer math is the same recurrence as
ops.distance._sweep_xla + directions_from_distance; verified in
interpreter mode (tests/test_field_fused.py) and on-chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2p_distributed_tswap_tpu.ops.sweep_pallas import _on_tpu

INF = np.int32(1 << 30)
DIR_STAY = np.uint8(4)
SUB = 8          # sublane tile height
LANES = 128
# VMEM budget for the (H+16, W) int32 distance scratch; leaves room for
# the mask, codes, and doubling temporaries inside ~16 MB of VMEM.
MAX_SCRATCH_BYTES = 6 << 20
HALO = SUB  # one aligned tile of INF halo rows above and below

# Tests flip this to run through the Pallas interpreter on CPU.
INTERPRET = False


# Multi-field kernel VMEM budget: the (H+2, 8, W) int32 distance scratch
# PLUS the (H, 8, W) int32 codes output block must fit beside the mask and
# doubling temporaries inside ~16 MB of VMEM — fields up to ~256x256 (the
# reference-regime shapes); larger grids keep the strip-kernel pipeline.
MULTI_MAX_BYTES = 12 << 20


def fused_mode() -> str:
    """'' (off, the default), 'multi' (MAPD_FUSED=1 or =multi: 8 fields
    per program), or 'single' (MAPD_FUSED=single: the round-3 one-field
    experiment, kept as the measured baseline)."""
    import os

    v = os.environ.get("MAPD_FUSED", "")
    if v in ("1", "multi"):
        return "multi"
    if v == "single":
        return "single"
    return ""


def multi_eligible(h: int, w: int) -> bool:
    """Shape/VMEM gate for the multi-field kernel (backend gate rides
    ``fused_eligible``): lane-aligned W, 8-aligned H (the row recurrence
    streams 8-row chunks), scratch + codes block within budget."""
    return (h % SUB == 0 and w % LANES == 0
            and ((h + 2) + h) * SUB * w * 4 <= MULTI_MAX_BYTES)


def fused_eligible(h: int, w: int) -> bool:
    """OPT-IN only (MAPD_FUSED=1 -> multi-field kernel, =single -> the
    round-3 one-field experiment).  The single-field variant measured
    SLOWER than the strip-kernel pipeline in real steps (medium
    35 -> 66 ms/step, flagship 127 -> 156; round 3); the multi-field
    variant is the roofline's GO-signal layout but has no on-chip
    measurement yet (no TPU in this environment — see
    results/field_engine_r11.json), so neither defaults on.  The
    multi-field kernel also aborts the v5e compiler (ROADMAP A2)."""
    mode = fused_mode()
    if not mode or not _on_tpu():
        return False
    if mode == "single":
        return (h % SUB == 0 and w % LANES == 0
                and (h + 2 * HALO) * w * 4 <= MAX_SCRATCH_BYTES)
    return multi_eligible(h, w)


def _lane_seg_scan(v, blocked, reverse: bool, w: int):
    """Segmented min-scan along lanes (axis 1) of an (8, W) tile with
    resets at blocked cells — the in-register doubling form of
    ops.distance._seg_min_scan.  The reset flags ride as int32 0/1:
    Mosaic cannot rotate sub-32-bit vectors."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    r = blocked.astype(jnp.int32)
    off = 1
    while off < w:
        if reverse:
            # pltpu.roll requires a non-negative shift; w - off is the
            # circular equivalent of rolling by -off
            valid = lane < w - off
            shift = w - off
        else:
            valid = lane >= off
            shift = off
        sv = jnp.where(valid, pltpu.roll(v, shift, 1), INF + w)
        sr = jnp.where(valid, pltpu.roll(r, shift, 1), 0)
        v = jnp.where(r != 0, v, jnp.minimum(v, sv))
        r = r | sr
        off *= 2
    return v


def _kernel(h: int, w: int, max_rounds: int,
            goal_ref, m_ref, o_ref, d_ref):
    nt = h // SUB
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, w), 1)
    row_in_tile = jax.lax.broadcasted_iota(jnp.int32, (SUB, w), 0)

    # ---- seed: halo tiles INF, interior = 0 at the goal cell (if free) ----
    g = goal_ref[pl.program_id(0)]

    def seed_tile(t, _):
        base = t * SUB
        cell = (base + row_in_tile) * w + lane
        blocked = m_ref[pl.ds(base, SUB), :] != 0
        d_ref[pl.ds(HALO + base, SUB), :] = jnp.where(
            (cell == g) & ~blocked, jnp.int32(0), INF)
        return 0

    jax.lax.fori_loop(0, nt, seed_tile, 0)
    inf_tile = jnp.full((SUB, w), INF, jnp.int32)
    d_ref[pl.ds(0, SUB), :] = inf_tile
    d_ref[pl.ds(HALO + h, SUB), :] = inf_tile

    # ---- one directional pass along rows (y), sequential recurrence ----
    def y_pass(reverse: bool):
        def tile_body(t, carry):
            run, changed = carry
            tt = (nt - 1 - t) if reverse else t
            base = tt * SUB
            tile_d = d_ref[pl.ds(HALO + base, SUB), :]
            tile_b = m_ref[pl.ds(base, SUB), :] != 0
            rows = [None] * SUB
            order = range(SUB - 1, -1, -1) if reverse else range(SUB)
            for k in order:
                run = jnp.minimum(run + 1, tile_d[k:k + 1, :])
                run = jnp.where(tile_b[k:k + 1, :], INF, run)
                rows[k] = jnp.where(tile_b[k:k + 1, :], INF,
                                    jnp.minimum(run, INF))
            out = jnp.concatenate(rows, axis=0)
            changed = changed | jnp.any(out != tile_d)
            d_ref[pl.ds(HALO + base, SUB), :] = out
            return run, changed

        init = jnp.full((1, w), INF, jnp.int32)
        _, changed = jax.lax.fori_loop(0, nt, tile_body,
                                       (init, jnp.bool_(False)))
        return changed

    # ---- one directional pass along lanes (x), per (8, W) tile ----
    def x_pass(reverse: bool):
        coord = jnp.where(jnp.bool_(reverse), -lane, lane)

        def tile_body(t, changed):
            base = t * SUB
            tile_d = d_ref[pl.ds(HALO + base, SUB), :]
            tile_b = m_ref[pl.ds(base, SUB), :] != 0
            v = jnp.where(tile_b, INF + w, tile_d - coord)
            m = _lane_seg_scan(v, tile_b, reverse, w)
            relaxed = jnp.where(tile_b, INF,
                                jnp.minimum(tile_d, m + coord))
            relaxed = jnp.minimum(relaxed, INF)
            changed = changed | jnp.any(relaxed != tile_d)
            d_ref[pl.ds(HALO + base, SUB), :] = relaxed
            return changed

        return jax.lax.fori_loop(0, nt, tile_body, jnp.bool_(False))

    # ---- fixpoint: sweep rounds until no pass changes anything ----
    def round_cond(carry):
        changed, i = carry
        return changed & (i < max_rounds)

    def round_body(carry):
        _, i = carry
        c = x_pass(False)
        c = c | x_pass(True)
        c = c | y_pass(False)
        c = c | y_pass(True)
        return c, i + 1

    jax.lax.while_loop(round_cond, round_body,
                       (jnp.bool_(True), jnp.int32(0)))

    # ---- next-hop codes (reference neighbor order, first-min strict) ----
    def code_tile(t, _):
        base = t * SUB
        cur = d_ref[pl.ds(HALO + base, SUB), :]
        # adjacent tiles are aligned reads (halo tiles cover t=0 / t=nt-1);
        # the +-1-row neighbor views are register concatenations
        prev_t = d_ref[pl.ds(base, SUB), :]
        next_t = d_ref[pl.ds(HALO + SUB + base, SUB), :]
        up = jnp.concatenate([prev_t[SUB - 1:SUB, :], cur[:SUB - 1, :]],
                             axis=0)                    # row - 1 (dy = -1)
        down = jnp.concatenate([cur[1:, :], next_t[0:1, :]],
                               axis=0)                  # row + 1 (dy = +1)
        right = jnp.where(lane < w - 1, pltpu.roll(cur, w - 1, 1), INF)
        left = jnp.where(lane >= 1, pltpu.roll(cur, 1, 1), INF)
        blocked = m_ref[pl.ds(base, SUB), :] != 0

        # codes ride as int32 inside the kernel: Mosaic rejects the
        # relayouts that mixing i1 masks with 8-bit vectors requires
        best = jnp.full((SUB, w), int(DIR_STAY), jnp.int32)
        best_val = jnp.full((SUB, w), INF, jnp.int32)
        # DIR_DXDY order: (0,1)=down, (1,0)=right, (0,-1)=up, (-1,0)=left
        for k, nv in enumerate((down, right, up, left)):
            better = nv < best_val
            best = jnp.where(better, jnp.int32(k), best)
            best_val = jnp.minimum(best_val, nv)
        stay = ((cur == 0) | (cur >= INF) | (best_val >= INF)
                | (best_val >= cur) | blocked)
        o_ref[pl.ds(base, SUB), :] = jnp.where(stay, jnp.int32(DIR_STAY),
                                               best)
        return 0

    jax.lax.fori_loop(0, nt, code_tile, 0)


def fused_direction_fields(free: jnp.ndarray, goals_idx: jnp.ndarray,
                           max_rounds: int = 128) -> jnp.ndarray:
    """(G, H, W) uint8 next-hop codes — drop-in replacement for
    ops.distance.direction_fields on eligible shapes.  Dispatches by
    ``fused_mode()``: multi-field (8 per program) by default, the
    single-field round-3 kernel under MAPD_FUSED=single."""
    if fused_mode() != "single":
        return multi_direction_fields(free, goals_idx, max_rounds)
    return single_direction_fields(free, goals_idx, max_rounds)


def single_direction_fields(free: jnp.ndarray, goals_idx: jnp.ndarray,
                            max_rounds: int = 128) -> jnp.ndarray:
    """(G, H, W) uint8 next-hop codes, one field per program (the
    round-3 kernel — measured slower on-chip; kept as the baseline)."""
    h, w = free.shape
    g = goals_idx.shape[0]
    mask = (~free).astype(jnp.int8)
    kernel = functools.partial(_kernel, h, w, max_rounds)
    codes = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g, h, w), jnp.int32),
        grid=(g,),
        in_specs=[
            # whole goals vector in SMEM; each program picks its own entry
            # (rank-1 SMEM blocks must cover the array on TPU)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((h, w), lambda gi: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((None, h, w), lambda gi: (gi, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((h + 2 * HALO, w), jnp.int32)],
        interpret=INTERPRET,
    )(goals_idx.astype(jnp.int32), mask)
    return codes.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Multi-field kernel (ISSUE 9 "v2"): 8 fields per program across sublanes.
#
# The distance scratch is (H + 2, SUB, W) int32: grid row y lives at
# leading index y + 1 (single-row INF halos at 0 and H + 1 — legal
# because the leading dimension is UNTILED, so dynamic row indices need
# no 8-alignment; the tiled plane is the (8 fields, W lanes) tile).  The
# sequential row (y) recurrence streams 8-row chunks via pl.ds on the
# leading dim — chunked access, not per-row dynamic indexing, which the
# round-4 kernel measured ~27x slower to lower — and advances one
# (SUB, W) tile per grid row: every sublane of every issue is a live
# field.  Lane (x) passes run the in-register doubling scan over whole
# (8, SUB, W) chunks.  The single shared obstacle mask rides as
# (H, 1, W) int8 and broadcasts up the sublane dim per row.
# ---------------------------------------------------------------------------


def _lane_seg_scan3(v, r, reverse: bool, w: int):
    """_lane_seg_scan generalized to (..., W) chunks: segmented min-scan
    along the LAST axis with int32 reset flags ``r``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    off = 1
    while off < w:
        if reverse:
            valid = lane < w - off
            shift = w - off
        else:
            valid = lane >= off
            shift = off
        sv = jnp.where(valid, pltpu.roll(v, shift, v.ndim - 1), INF + w)
        sr = jnp.where(valid, pltpu.roll(r, shift, v.ndim - 1), 0)
        v = jnp.where(r != 0, v, jnp.minimum(v, sv))
        r = r | sr
        off *= 2
    return v


def _multi_kernel(h: int, w: int, max_rounds: int,
                  goal_ref, m_ref, o_ref, d_ref):
    nt = h // SUB  # 8-grid-row chunks streamed along the leading dim
    i0 = pl.program_id(0) * SUB
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

    def mask_chunk(t):
        """(SUB grid rows, SUB fields, W) bool: the shared mask rows
        t*8..t*8+7, each broadcast across the 8 field sublanes."""
        mc = m_ref[pl.ds(t * SUB, SUB)] != 0           # (8, 1, W)
        return jnp.broadcast_to(mc, (SUB, SUB, w))

    # ---- seed: halo rows INF; interior row y = 0 at each field's goal ----
    def seed_chunk(t, _):
        blocked = m_ref[pl.ds(t * SUB, SUB)] != 0      # (8, 1, W)
        rows = []
        for k in range(SUB):
            cell = (t * SUB + k) * w + lane1           # (1, W) cell ids
            per_field = [jnp.where((cell == goal_ref[i0 + s])
                                   & ~blocked[k], jnp.int32(0), INF)
                         for s in range(SUB)]
            rows.append(jnp.concatenate(per_field, axis=0))  # (SUB, W)
        d_ref[pl.ds(1 + t * SUB, SUB)] = jnp.stack(rows, axis=0)
        return 0

    jax.lax.fori_loop(0, nt, seed_chunk, 0)
    inf_row = jnp.full((SUB, w), INF, jnp.int32)
    d_ref[0] = inf_row
    d_ref[h + 1] = inf_row

    # ---- row (y) pass: sequential recurrence, one (SUB, W) tile/row ----
    def y_pass(reverse: bool):
        def chunk_body(t, carry):
            run, changed = carry
            tt = (nt - 1 - t) if reverse else t
            chunk = d_ref[pl.ds(1 + tt * SUB, SUB)]    # (8, SUB, W)
            mrows = m_ref[pl.ds(tt * SUB, SUB)] != 0   # (8, 1, W)
            rows = [None] * SUB
            order = range(SUB - 1, -1, -1) if reverse else range(SUB)
            for k in order:
                bl = jnp.broadcast_to(mrows[k], (SUB, w))
                run = jnp.minimum(run + 1, chunk[k])
                run = jnp.where(bl, INF, run)
                rows[k] = jnp.where(bl, INF, jnp.minimum(run, INF))
            out = jnp.stack(rows, axis=0)
            changed = changed | jnp.any(out != chunk)
            d_ref[pl.ds(1 + tt * SUB, SUB)] = out
            return run, changed

        init = jnp.full((SUB, w), INF, jnp.int32)
        _, changed = jax.lax.fori_loop(0, nt, chunk_body,
                                       (init, jnp.bool_(False)))
        return changed

    # ---- lane (x) pass: doubling scan per (8, SUB, W) chunk ----
    def x_pass(reverse: bool):
        lane3 = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB, w), 2)
        coord = jnp.where(jnp.bool_(reverse), -lane3, lane3)

        def chunk_body(t, changed):
            chunk = d_ref[pl.ds(1 + t * SUB, SUB)]
            blocked = mask_chunk(t)
            v = jnp.where(blocked, INF + w, chunk - coord)
            m = _lane_seg_scan3(v, blocked.astype(jnp.int32), reverse, w)
            relaxed = jnp.where(blocked, INF,
                                jnp.minimum(chunk, m + coord))
            relaxed = jnp.minimum(relaxed, INF)
            changed = changed | jnp.any(relaxed != chunk)
            d_ref[pl.ds(1 + t * SUB, SUB)] = relaxed
            return changed

        return jax.lax.fori_loop(0, nt, chunk_body, jnp.bool_(False))

    # ---- fixpoint ----
    def round_cond(carry):
        changed, i = carry
        return changed & (i < max_rounds)

    def round_body(carry):
        _, i = carry
        c = x_pass(False)
        c = c | x_pass(True)
        c = c | y_pass(False)
        c = c | y_pass(True)
        return c, i + 1

    jax.lax.while_loop(round_cond, round_body,
                       (jnp.bool_(True), jnp.int32(0)))

    # ---- next-hop codes (reference neighbor order, first-min strict) ----
    lane3 = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB, w), 2)

    def code_chunk(t, _):
        cur = d_ref[pl.ds(1 + t * SUB, SUB)]
        # row neighbors are OVERLAPPING leading-dim window reads (the
        # halo rows cover the grid edges) — no register concatenation
        # needed, the leading dim is untiled
        up = d_ref[pl.ds(t * SUB, SUB)]                # row y - 1
        down = d_ref[pl.ds(2 + t * SUB, SUB)]          # row y + 1
        right = jnp.where(lane3 < w - 1, pltpu.roll(cur, w - 1, 2), INF)
        left = jnp.where(lane3 >= 1, pltpu.roll(cur, 1, 2), INF)
        blocked = mask_chunk(t)
        best = jnp.full((SUB, SUB, w), int(DIR_STAY), jnp.int32)
        best_val = jnp.full((SUB, SUB, w), INF, jnp.int32)
        # DIR_DXDY order: (0,1)=down, (1,0)=right, (0,-1)=up, (-1,0)=left
        for k, nv in enumerate((down, right, up, left)):
            better = nv < best_val
            best = jnp.where(better, jnp.int32(k), best)
            best_val = jnp.minimum(best_val, nv)
        stay = ((cur == 0) | (cur >= INF) | (best_val >= INF)
                | (best_val >= cur) | blocked)
        o_ref[pl.ds(t * SUB, SUB)] = jnp.where(stay, jnp.int32(DIR_STAY),
                                               best)
        return 0

    jax.lax.fori_loop(0, nt, code_chunk, 0)


def multi_direction_fields(free: jnp.ndarray, goals_idx: jnp.ndarray,
                           max_rounds: int = 128) -> jnp.ndarray:
    """(G, H, W) uint8 next-hop codes, EIGHT fields per program.  Any G
    works: the goal vector pads to a multiple of 8 by repeating the last
    goal (duplicate fields are computed and dropped — bounded waste,
    zero extra programs for G % 8 == 0 batches)."""
    h, w = free.shape
    g = goals_idx.shape[0]
    g8 = -(-g // SUB)
    goals = goals_idx.astype(jnp.int32)
    if g8 * SUB != g:
        goals = jnp.concatenate(
            [goals, jnp.broadcast_to(goals[-1:], (g8 * SUB - g,))])
    mask = (~free).astype(jnp.int8).reshape(h, 1, w)
    kernel = functools.partial(_multi_kernel, h, w, max_rounds)
    codes = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g8, h, SUB, w), jnp.int32),
        grid=(g8,),
        in_specs=[
            # whole goals vector in SMEM; each program reads its 8 entries
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((h, 1, w), lambda gi: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((None, h, SUB, w),
                               lambda gi: (gi, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((h + 2, SUB, w), jnp.int32)],
        interpret=INTERPRET,
    )(goals, mask)
    # (G8, H, SUB, W): fields ride the sublane dim in-kernel; one output
    # transpose unpacks them to the (G, H, W) contract
    return (codes.transpose(0, 2, 1, 3).reshape(g8 * SUB, h, w)[:g]
            .astype(jnp.uint8))
