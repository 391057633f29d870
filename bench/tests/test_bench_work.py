"""bench/work.py against counts made by hand at tiny shapes."""
import pytest

from bench import work

# C=4 components, D=2, R=3 (P=6), K=2, U=5 utterances, F=10 frames
SHAPES = dict(C=4, D=2, R=3, K=2, U=5, F=10)
# L and A 2*5*4*6 each = 480, projection 2*5*4*2*3 = 240, solves
# 5*27/3 = 45, M-step 4*(9 + 2*9*2) = 180, precompute 2*4*9*2 = 144
AT_REST = 480 + 240 + 45 + 180 + 144
# preselect 2*10*4*2*2 = 320, rescore 2*10*2*(4+2) = 240, moments
# 2*10*2*(2+4) = 240 (first order only: 2*10*2*2 = 80)
REALIGN = AT_REST + 320 + 240 + 240


def test_packed_width():
    assert work.packed(3) == 6 and work.packed(400) == 80200


@pytest.mark.parametrize("realign,sigma,want", [
    (True, True, REALIGN), (True, False, REALIGN - 240 + 80),
    (False, True, AT_REST), (False, False, AT_REST)])
def test_em_iteration_flops(realign, sigma, want):
    got = work.em_iteration_flops(realign=realign, update_sigma=sigma,
                                  **SHAPES)
    assert got == pytest.approx(want)


def test_estep_least_time_takes_the_larger_bound():
    # 4*U*C*P = 480 operations; bytes 4*((20+24+30) + (20+30+24)) = 592
    assert work.estep_least_seconds(C=4, R=3, U=5, peak_flops=1e3,
                                    peak_bytes=1e3) == \
        (pytest.approx(0.592), "memory")
    assert work.estep_least_seconds(C=4, R=3, U=5, peak_flops=1e2,
                                    peak_bytes=1e3) == \
        (pytest.approx(4.8), "compute")


def test_rescore_least_time_reads_each_row_at_most_once():
    # E = 1+2+4 = 7: 2*10*2*6 = 240 operations; bytes
    # 4*(10*2 + 10*2 + min(4, 20)*7) = 272
    assert work.rescore_least_seconds(C=4, D=2, K=2, frames=10,
                                      peak_flops=1e3, peak_bytes=1e3) == \
        (pytest.approx(0.272), "memory")
    # one frame selects K=2 rows of the C=4: 4*(2 + 2 + 2*7) = 72 bytes
    assert work.rescore_least_seconds(C=4, D=2, K=2, frames=1,
                                      peak_flops=1e9, peak_bytes=1.0)[0] \
        == pytest.approx(72.0)
