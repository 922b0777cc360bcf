"""Bytes per update against hand-counted shapes, and the peak table."""
import pytest

from harness import cost


def test_half_sweep_bytes_hand_count():
    # 2 lanes of a 4x4 grid, 2 labels: state read 2*16*4 = 128, colour
    # written 2*8*4 = 64, random words 2*8*4 = 64, unary 8*2*4 = 64,
    # pairwise 2*2*4 = 16, clamp 16
    assert cost.half_sweep_bytes(2, 4, 4, 2) == 128 + 64 + 64 + 64 + 16 + 16


def test_sweep_and_round_bytes_hand_count():
    # two half-sweeps (704) + counts 2*2*16*2*4 = 512 + moments
    # 2*2*2*16*4 = 512
    assert cost.sweep_bytes(2, 4, 4, 2) == 704 + 512 + 512
    assert cost.round_bytes(2, 4, 4, 2, 16) == 16 * 1728


def test_penguin_bytes_per_update():
    """500x333, 8 lanes, 2 labels: 48 bytes per lane-site per sweep plus
    the shared unary, pairwise and mask reads."""
    lanes, h, w = 8, 500, 333
    per = cost.sweep_bytes(lanes, h, w, 2) / (lanes * h * w)
    # shared per half-sweep: unary 4 B + mask 1 B per site, twice a sweep
    assert per == pytest.approx(48 + 10 / 8, rel=1e-6)


def test_peaks_known_and_unknown():
    assert cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        cost.peaks("TPU v9 imaginary")
