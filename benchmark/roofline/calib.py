"""Operations and bytes the fused calibration NEEDS for one call, from
its shapes alone — what the roofline share of the Pallas kernel is taken
against. The algorithm's minimum, not what the kernel happens to move:
each raw pixel read once in the type it arrives in, each calibrated pixel
written once in the type the model takes, and the three constant planes
(pedestal, gain, mask) read once per call. (The shipped kernel is handed
float32 pixels by a convert that XLA runs ahead of it, and so moves more;
that shows as a share under 100%, as it should.)"""

import numpy as np


def fused_calibrate(batch: int, panels: int, height: int, width: int,
                    raw_dtype: str = "uint16", out_dtype: str = "bfloat16") -> dict:
    pixels = panels * height * width
    out_bytes = 2 if out_dtype == "bfloat16" else np.dtype(out_dtype).itemsize
    constants = pixels * (4 + 4 + 1)  # pedestal f32, gain f32, mask u8
    moved = batch * pixels * (np.dtype(raw_dtype).itemsize + out_bytes) + constants
    # per pixel: subtract, divide, compare+and (background test), two
    # accumulations, subtract baseline, select: ~8 elementwise operations
    return {"bytes": float(moved), "flops": float(8 * batch * pixels)}
