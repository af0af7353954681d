"""Effective sample size (Geyer's initial monotone sequence), kept with the
benchmark so that the standard errors ``correct`` is judged by cannot move
with the program.  Same arithmetic as ``repro.core.infer.diagnostics``."""
from __future__ import annotations

import numpy as np


def _autocovariance(x):
    """Autocovariance along axis 0 via FFT. x: (n, ...)."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    x = x - x.mean(0, keepdims=True)
    m = 1
    while m < 2 * n:
        m *= 2
    f = np.fft.rfft(x, n=m, axis=0)
    acov = np.fft.irfft(f * np.conj(f), n=m, axis=0)[:n]
    return acov / n


def effective_sample_size(x):
    """ESS of ``x`` with shape (num_chains, num_samples, ...)."""
    x = np.asarray(x, np.float64)
    if x.ndim == 1:
        x = x[None, :]
    c, n = x.shape[:2]
    acov = np.stack([_autocovariance(x[i]) for i in range(c)], 0)
    mean_var = acov[:, 0].mean(0)
    var_plus = mean_var * (n - 1) / n
    if c > 1:
        var_plus = var_plus + x.mean(1).var(0, ddof=1)
    rho = 1.0 - (mean_var - acov.mean(0)) / np.where(var_plus == 0, 1.0,
                                                     var_plus)
    rho[0] = 1.0
    t_max = (n - 1) // 2
    pair = rho[0:2 * t_max:2] + rho[1:2 * t_max:2]
    pair = np.where(pair > 0, pair, 0.0)
    pair = np.minimum.accumulate(pair, axis=0)
    keep = np.logical_and.accumulate(pair > 0, axis=0)
    tau = -1.0 + 2.0 * (pair * keep).sum(0)
    return c * n / np.maximum(tau, 1.0 / (c * n))

