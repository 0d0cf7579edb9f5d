"""``cols_hbm_frac``: the strided-column-pass kernels' achieved HBM
bandwidth over the chip's peak (percent), the kernels found by family
(:mod:`chipbench.lib.families`)."""

from chipbench.lib.families import COL_KERNELS, hbm_frac


def reduce(tr: dict):
    return hbm_frac(tr, COL_KERNELS)
