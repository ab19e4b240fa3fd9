"""ops/sweep_pallas.py — the Pallas sequential sweep kernel.

The compiled kernel needs a real TPU; CI runs it through the Pallas
interpreter (sweep_pallas.INTERPRET) and checks BIT-IDENTITY against the
XLA doubling-scan sweep on random obstacle fields — the two formulations
are the same integer recurrence, so any mismatch is a bug, not noise.
On-chip bit-identity at 256²/512² was verified during round 3
(SCALING.md "Pallas: GO").
"""

import jax.numpy as jnp
import numpy as np
import pytest

from p2p_distributed_tswap_tpu.ops import distance, sweep_pallas


@pytest.fixture(autouse=True)
def _interpret_mode():
    sweep_pallas.INTERPRET = True
    yield
    sweep_pallas.INTERPRET = False


def _xla_sweep(d, free_b, axis, reverse):
    h, w = d.shape[1], d.shape[2]
    xc = jnp.arange(w, dtype=jnp.int32).reshape(1, 1, w)
    yc = jnp.arange(h, dtype=jnp.int32).reshape(1, h, 1)
    coord = xc if axis == 2 else yc
    return distance._sweep_xla(d, free_b, axis, reverse,
                               -coord if reverse else coord)


@pytest.mark.parametrize("axis,reverse", [(1, False), (1, True),
                                          (2, False), (2, True)])
def test_kernel_matches_xla_sweep(axis, reverse):
    rng = np.random.default_rng(axis * 2 + reverse)
    h = w = 128  # one lane strip, 16 sublane tiles: exercises the tiling
    free = rng.random((h, w)) > 0.25
    d = np.where(rng.random((3, h, w)) > 0.97,
                 rng.integers(0, 50, (3, h, w)), int(distance.INF))
    d = np.where(free[None], d, int(distance.INF)).astype(np.int32)
    free_j = jnp.asarray(free)
    free_b = jnp.broadcast_to(free_j[None], d.shape)
    ref = np.asarray(_xla_sweep(jnp.asarray(d), free_b, axis, reverse))
    pal = np.asarray(sweep_pallas.sweep(jnp.asarray(d), free_j, axis,
                                        reverse))
    np.testing.assert_array_equal(ref, pal)


@pytest.mark.parametrize("axis,reverse", [(1, False), (1, True),
                                          (2, False), (2, True)])
@pytest.mark.parametrize("w", [128, 1024])
def test_fullrow_kernel_matches_xla_sweep(axis, reverse, w):
    """The round-4 full-row kernel (segments of one row packed onto the
    sublanes) must stay bit-identical to the XLA doubling scan.  w=128
    degenerates to one segment; w=1024 exercises the full 8-segment tile
    packing (the production flagship shape)."""
    rng = np.random.default_rng(10 + axis * 2 + reverse + w)
    h = 128
    r = 3  # odd batch: the kernel has no batch-size restriction
    free = rng.random((h, w)) > 0.25
    d = np.where(rng.random((r, h, w)) > 0.95,
                 rng.integers(0, 60, (r, h, w)), int(distance.INF))
    d = np.where(free[None], d, int(distance.INF)).astype(np.int32)
    free_j = jnp.asarray(free)
    free_b = jnp.broadcast_to(free_j[None], d.shape)
    ref = np.asarray(_xla_sweep(jnp.asarray(d), free_b, axis, reverse))
    blocked = (~free_j).astype(jnp.int32)
    if axis == 1:
        pal = sweep_pallas._sweep8_rows(jnp.asarray(d), blocked, reverse)
    else:
        pal = sweep_pallas._sweep8_rows(
            jnp.asarray(d).swapaxes(1, 2), blocked.T, reverse).swapaxes(1, 2)
    np.testing.assert_array_equal(ref, np.asarray(pal))


@pytest.mark.parametrize("reverse", [False, True])
def test_fullrow_kernel_carries_across_hblocks(monkeypatch, reverse):
    """Shrink HBLK so the 128-row grid needs multiple sequential blocks,
    AND use w=2048 so the lane-chunk grid dimension (nchunk=2) is
    exercised: the running minimum must carry across block boundaries in
    scratch, independently per (field, chunk)."""
    monkeypatch.setattr(sweep_pallas, "HBLK", 32)
    rng = np.random.default_rng(99 + reverse)
    h, w = 128, 2048
    free = rng.random((h, w)) > 0.2
    d = np.where(rng.random((2, h, w)) > 0.9,
                 rng.integers(0, 40, (2, h, w)), int(distance.INF))
    d = np.where(free[None], d, int(distance.INF)).astype(np.int32)
    free_j = jnp.asarray(free)
    free_b = jnp.broadcast_to(free_j[None], d.shape)
    ref = np.asarray(_xla_sweep(jnp.asarray(d), free_b, 1, reverse))
    pal = sweep_pallas._sweep8_rows(
        jnp.asarray(d), (~free_j).astype(jnp.int32), reverse)
    np.testing.assert_array_equal(ref, np.asarray(pal))


def test_eligibility_gate():
    # Backend gate: this suite runs on the CPU backend, so even an
    # aligned grid must stay on the XLA path
    assert sweep_pallas.sweep_eligible(256, 256) is False
    # unaligned grids never eligible regardless of backend
    assert not sweep_pallas.sweep_eligible(100, 100)
    assert not sweep_pallas.sweep_eligible(256, 100)
    # sweep8_eligible is an importable entry point of its own: H not a
    # multiple of SUBLANES would silently truncate the last rows inside
    # _scan8_kernel, so the gate must reject it directly (advisor r4-3)
    assert not sweep_pallas.sweep8_eligible(100, 256)
    assert not sweep_pallas.sweep8_eligible(12, 256)
    assert sweep_pallas.sweep8_eligible(16, 256)
