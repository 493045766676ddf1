from repro.kernels.emulator_block.ops import (  # noqa: F401
    emulator_block_unified)
