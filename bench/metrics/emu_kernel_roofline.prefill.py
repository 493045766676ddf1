"""``emu_kernel_roofline`` in a prefill cell: the same reader
(``metrics/emu_kernel_roofline.py``, loaded, not copied) over this cell's
launches, 32 rows each over the attention sites."""
from bench import harness as H


def read(r):
    return H.load_module("metrics", "emu_kernel_roofline").read(r)
