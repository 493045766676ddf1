"""Work of one launch of the unified emulator kernel
(``emulator_block_unified_pallas``): the Conv4Xbar net's operations that
depend on the wordline drive, counted from the shapes -- not from the
kernel's HLO, which also counts the block-diagonal ``kron(I, w)``
inflation and the conductance-only precompute.

Per block and batch row: stage 0's voltage term on every cell and
channel, stage 1 once (the dual-rail delta factorization shares it), and
the later conv stages and the FC head once per rail.  The
conductance-only part (``g0``, its CELU and stage-1 projection) is left
out: it is invariant from deploy to deploy.  A multiply-add is two
operations.

Bytes: the deployed conductance leaf once per launch, as it is held (f32,
both bitlines of every cell), the drive and rail mask in, both rails out.
"""
from bench.reference.crossbar import GEOMETRIES, stages

# how the kernel's device ops show in the trace: today an unnamed
# ``pallas_call``, whose op carries ``custom_call_target="tpu_custom_call"``
# -- the only Pallas kernel on the served path; a stable ``name=`` on the
# call would show as "emulator_block_unified"
NAMES = ("emulator_block_unified", "tpu_custom_call")


def lattice(K: int, N: int, geometry: str):
    """(NB, NO): block groups over K and output groups over N."""
    _, D, H, _, no = GEOMETRIES[geometry]
    return -(-K // (H * D)), -(-N // no)


def flops_per_block_row(geometry: str) -> int:
    _, D, H, W, no = GEOMETRIES[geometry]
    st = stages(geometry)
    c0 = st[0][1]
    total = 2 * D * H * W * c0                       # stage 0, voltage term
    h, w = H, W
    per_rail = 0
    for i, (c_in, c_out, kh, kw, sw) in enumerate(st[1:], start=1):
        if kw == 1:
            h //= kh
            n = 2 * D * w * h * c_in * kh * c_out
        else:
            w = (w - kw) // sw + 1
            n = 2 * D * h * w * c_in * kw * c_out
        if i == 1:
            total += n                               # stage 1 once
        else:
            per_rail += n
    dims = [st[-1][1] * D * h * w, 32, 16, no]       # fc head (Table 2)
    per_rail += sum(2 * a * b for a, b in zip(dims, dims[1:]))
    return total + 2 * per_rail


def flops(M: int, K: int, N: int, geometry: str) -> int:
    NB, NO = lattice(K, N, geometry)
    return M * NB * NO * flops_per_block_row(geometry)


def bytes_moved(M: int, K: int, N: int, geometry: str) -> int:
    _, D, H, W, no = GEOMETRIES[geometry]
    NB, NO = lattice(K, N, geometry)
    g = NB * NO * D * H * W * 4
    drive = 2 * M * NB * D * H * 4
    rails = 2 * M * NB * NO * no * 4
    return g + drive + rails


def bound_s(M: int, K: int, N: int, geometry: str, peaks: dict) -> float:
    """Least time the chip could take for the launch."""
    return max(flops(M, K, N, geometry) / peaks["bf16_flops_s"],
               bytes_moved(M, K, N, geometry) / peaks["hbm_bytes_s"])
