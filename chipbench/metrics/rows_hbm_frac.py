"""``rows_hbm_frac``: the row-pass kernels' achieved HBM bandwidth over the
chip's peak (percent), the kernels found by family
(:mod:`chipbench.lib.families`)."""

from chipbench.lib.families import ROW_KERNELS, hbm_frac


def reduce(tr: dict):
    return hbm_frac(tr, ROW_KERNELS)
