"""The unified kernel's operation and byte count against a hand count."""
import pytest

from bench.kernels import emulator_block_unified as k


# per block and batch row, multiply-adds counted twice:
#   stage 0 voltage term  D*H*W*16
#   stage 1 once           D*W*(H/2) * (16*2) * 8
#   per rail: stage 2      D*W*(H/8) * (8*4) * 4
#             stage 3      D*W*(H/64) * (4*8) * 32
#             W stage      D*(W') * (32*2) * 32
#             fc           128*32 + 32*16 + 16*1      (case A)
HAND = {
    "rram_ps32_a": 2 * (4 * 64 * 2 * 16 + 4 * 2 * 32 * 32 * 8
                        + 2 * (4 * 2 * 8 * 32 * 4 + 4 * 2 * 1 * 32 * 32
                               + 4 * 1 * 64 * 32
                               + 128 * 32 + 32 * 16 + 16 * 1)),
    "rram_ps32_b": 2 * (2 * 64 * 8 * 16 + 2 * 8 * 32 * 32 * 8
                        + 2 * (2 * 8 * 8 * 32 * 4 + 2 * 8 * 1 * 32 * 32
                               + 2 * 4 * 64 * 32
                               + 256 * 32 + 32 * 16 + 16 * 4)),
}


@pytest.mark.parametrize("geometry", sorted(HAND))
def test_flops_per_block_row(geometry):
    assert k.flops_per_block_row(geometry) == HAND[geometry]


def test_case_a_is_about_a_quarter_megaflop():
    assert k.flops_per_block_row("rram_ps32_a") == 264256


@pytest.mark.parametrize("K,N,nb,no", [(7168, 19200, 28, 19200),
                                       (19200, 7168, 75, 7168),
                                       (4096, 1024, 16, 1024),
                                       (100, 3, 1, 3)])
def test_lattice_and_launch_counts(K, N, nb, no):
    assert k.lattice(K, N, "rram_ps32_a") == (nb, no)
    M = 4
    assert k.flops(M, K, N, "rram_ps32_a") == M * nb * no * 264256
    g = nb * no * 4 * 64 * 2 * 4
    assert k.bytes_moved(M, K, N, "rram_ps32_a") == (
        g + 2 * M * nb * 4 * 64 * 4 + 2 * M * nb * no * 4)


def test_bound_takes_the_larger_side():
    peaks = {"bf16_flops_s": 1.97e14, "hbm_bytes_s": 8.19e11}
    f = k.flops(4, 7168, 19200, "rram_ps32_a") / peaks["bf16_flops_s"]
    b = k.bytes_moved(4, 7168, 19200, "rram_ps32_a") / peaks["hbm_bytes_s"]
    assert k.bound_s(4, 7168, 19200, "rram_ps32_a", peaks) == max(f, b)
    assert f > b                     # the gate site at 4 rows: compute
