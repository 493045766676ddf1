"""Share of its roofline that the unified emulator kernel reaches in the
traced window: the least time the chip could take for every launch the
window made (operations or bytes, ``kernels/emulator_block_unified.py``,
whichever bounds) over the device time of the kernel's ops.

The kernel's ops are matched by name; where their count is not the
number of launches the window made, another kernel matches too (or this
one is not on the path), and the metric is left out rather than read
from the wrong ops."""


def read(r):
    k = r.kernel("emulator_block_unified")
    t, n = r.kernel_time(k.NAMES)
    launches = r.launches()
    if n == 0 or t <= 0 or n != len(launches):
        return None
    geometry = r.ctx.conf["crossbar"]["geometry"]
    low = sum(k.bound_s(m, kk, nn, geometry, r.peaks())
              for m, kk, nn in launches)
    return 100.0 * low / t
