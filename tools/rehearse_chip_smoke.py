"""Rehearse chip_smoke.py WITHOUT the chip (no chip time): the same
phases end to end at a tiny size on the CPU backend, Pallas kernels in
interpret mode. Finds wrong paths, arguments and control flow before a
chip call is spent on them; says nothing about lowering (that is
tools/aot_tpu_compile.py) or speed.

The switch is on THIS side: the script patches chip_smoke's sizes, the
lane tile, the device sniff and the kernels' interpret flag — the
program itself has no option for any of it.

Usage: python tools/rehearse_chip_smoke.py            (~15 min, 1 chip path)
       python tools/rehearse_chip_smoke.py --chips 4   (mesh path on four
                                                        virtual CPU devices)
"""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

chips = 4 if sys.argv[1:] == ["--chips", "4"] else 1
os.environ["JAX_PLATFORMS"] = "cpu"
if chips == 4:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import chip_smoke  # noqa: E402
from cometbft_tpu.libs import jax_cache  # noqa: E402
from cometbft_tpu.ops import ed25519 as e5  # noqa: E402
from cometbft_tpu.ops import pallas_verify as pv  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

LANES = 16
chip_smoke.N_VALIDATORS = 8
if chips == 4:
    # 24-lane tiles: the mesh planner's 8-wide shard bucket holds 6 real
    # lanes + 2 canaries, so all four shards get lanes
    chip_smoke.N_BLOCKS, chip_smoke.TILE_BLOCKS = 9, 3
else:
    chip_smoke.N_BLOCKS, chip_smoke.TILE_BLOCKS = 8, 2   # one bucket a tile
chip_smoke.SEAM_VALIDATORS = 12
chip_smoke.N_REAL_SIGS = LANES - len(chip_smoke.edge_lanes())
validation.BATCH_VERIFY_THRESHOLD = 8
pv.TILE = LANES
jax_cache.is_device_platform = lambda: True
jax_cache.enable_compile_cache = jax_cache.raise_compiler_stack_limit
for name in ("pt_add_tiled", "pt_decompress_tiled", "rlc_window_sums",
             "rlc_epilogue"):
    setattr(pv, name, functools.partial(getattr(pv, name), interpret=True))
e5.verify_rlc_kernel_pallas = functools.partial(e5.verify_rlc_kernel_pallas,
                                                interpret=True)

sys.argv = [sys.argv[0], "--chips", str(chips)]
sys.exit(chip_smoke.main())
