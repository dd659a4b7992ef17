"""Paper Figs. 4-5: sigmoid FloatSD8-quantization error, direct vs two-region.

Fig. 4 shows that direct quantization y = Q(sigma(x)) over the whole input
range has *unbalanced* error: large for x > 0 (sigma saturates toward 1 where
the log-linear FloatSD grid is coarse), tiny for x <= 0. The two-region
decomposition (Eqs. 7-8) mirrors the quantizer and balances the error.

Reports max/mean |error| per region for both schemes plus the LUT depth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import floatsd
from repro.core.qsigmoid import SIGMOID_LUT_BIAS, qsigmoid_raw, sigmoid_lut_values


def direct_q_sigmoid(x):
    """Fig. 4's scheme: Eq. (7) applied to the whole input range."""
    return floatsd.quantize(jax.nn.sigmoid(x), bias=SIGMOID_LUT_BIAS).values


def run(n: int = 20001, xmax: float = 8.0, verbose: bool = True) -> dict:
    x = jnp.linspace(-xmax, xmax, n)
    s = jax.nn.sigmoid(x)
    err_direct = np.asarray(jnp.abs(direct_q_sigmoid(x) - s))
    err_two = np.asarray(jnp.abs(qsigmoid_raw(x) - s))
    neg = np.asarray(x) <= 0
    pos = ~neg

    out = {
        "direct_max_err_neg": float(err_direct[neg].max()),
        "direct_max_err_pos": float(err_direct[pos].max()),
        "two_region_max_err_neg": float(err_two[neg].max()),
        "two_region_max_err_pos": float(err_two[pos].max()),
        "direct_mean_err": float(err_direct.mean()),
        "two_region_mean_err": float(err_two.mean()),
        # paper counts the 42 non-zero values; 0 (deep saturation) rides free
        "lut_depth_nonpos_branch": int((sigmoid_lut_values() > 0).sum()),
        # imbalance ratio: how many times worse the positive side is
        "direct_imbalance": float(err_direct[pos].max() / max(err_direct[neg].max(), 1e-12)),
        "two_region_imbalance": float(err_two[pos].max() / max(err_two[neg].max(), 1e-12)),
    }
    if verbose:
        print("Fig.4/5 sigmoid quantization error (input range +-%.0f):" % xmax)
        print(f"  direct  Q(sigma(x)):  max|e| x<=0 = {out['direct_max_err_neg']:.3e}, "
              f"x>0 = {out['direct_max_err_pos']:.3e}  (imbalance {out['direct_imbalance']:.1f}x)")
        print(f"  two-region (Eq.7-8):  max|e| x<=0 = {out['two_region_max_err_neg']:.3e}, "
              f"x>0 = {out['two_region_max_err_pos']:.3e}  (imbalance {out['two_region_imbalance']:.1f}x)")
        print(f"  mean|e|: direct {out['direct_mean_err']:.3e} -> two-region {out['two_region_mean_err']:.3e}")
        print(f"  LUT depth (non-positive branch): {out['lut_depth_nonpos_branch']} "
              "(paper: 'only 42 possible values')")
    return out


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    run()
