"""BI-order FFT on the kernel substrate — completing the paper's trio
(scans, matrix computations, FFT) at the kernel layer.

The four-step (Bailey) factorization of a length-n DFT with n = n1 * n2:

  1. view the row as an (n1, n2) matrix A[j1, j2] = x[j1*n2 + j2];
  2. DFT each *column* (length n1):  B = W(n1) @ A;
  3. twiddle:  B[k1, j2] *= exp(-2*pi*i * k1*j2 / n);
  4. DFT each *row* (length n2):  C = B @ W(n2);
  5. read out transposed:  X[k2*n1 + k1] = C[k1, k2].

This is exactly the paper's Type 2 HBP recursion for FFT unrolled one
level: both factors are ~sqrt(n) (``planner.plan_fft``), so each small DFT
is a matrix product that fits the O(sqrt M) tile envelope, and
Q = (n/B) log_M n follows.  On the MXU the small DFTs *are* matmuls: every
O(n^1.5) flop runs through ``hbp_matmul``'s Morton-ordered Pallas grid
(complex arithmetic as four real products), with tile shapes planned from
the queried device.  The O(n) reshapes/twiddles between stages stay in XLA.

``fft_ref`` in ``repro.kernels.ref`` (``jnp.fft.fft``) is the oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.hbp_matmul import hbp_matmul


def _dft_factors(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Dense DFT matrix W[k, j] = exp(-2*pi*i*k*j/n) as (real, imag) f32."""
    kj = np.outer(np.arange(n), np.arange(n))
    w = np.exp(-2j * np.pi * kj / n)
    return w.real.astype(np.float32), w.imag.astype(np.float32)


def _cmatmul(ar, ai, br, bi, *, interpret: Optional[bool]):
    """(ar + i*ai) @ (br + i*bi) via four Morton-ordered Pallas matmuls."""
    rr = hbp_matmul(ar, br, interpret=interpret) - hbp_matmul(
        ai, bi, interpret=interpret)
    ri = hbp_matmul(ar, bi, interpret=interpret) + hbp_matmul(
        ai, br, interpret=interpret)
    return rr, ri


@functools.partial(jax.jit, static_argnames=("n1", "interpret"))
def bi_fft(x: jax.Array, *, n1: Optional[int] = None,
           interpret: Optional[bool] = None) -> jax.Array:
    """DFT along the last axis.  x: (rows, n) real or complex, n a power of
    two.  Returns complex64 (rows, n)."""
    rows, n = x.shape
    if n & (n - 1) != 0:
        raise ValueError(f"bi_fft needs a power-of-two length, got {n}")
    if n1 is None:
        from repro.kernels import planner

        n1 = planner.plan_fft(n)["n1"]
    n1 = max(min(n1, n), 1)
    while n % n1 != 0:
        n1 //= 2
    n2 = n // n1

    xr = jnp.real(x).astype(jnp.float32)
    xi = jnp.imag(x).astype(jnp.float32) if jnp.iscomplexobj(x) else jnp.zeros_like(xr)
    if n1 == 1 or n2 == 1:  # degenerate split: one dense DFT matmul
        wr, wi = _dft_factors(n)
        yr, yi = _cmatmul(xr, xi, jnp.asarray(wr).T, jnp.asarray(wi).T,
                          interpret=interpret)
        return jax.lax.complex(yr, yi)

    # step 1: (rows, n) -> columns-major fold (n1, rows*n2)
    ar = xr.reshape(rows, n1, n2).transpose(1, 0, 2).reshape(n1, rows * n2)
    ai = xi.reshape(rows, n1, n2).transpose(1, 0, 2).reshape(n1, rows * n2)

    # step 2: column DFTs — B = W(n1) @ A
    w1r, w1i = _dft_factors(n1)
    br, bi_ = _cmatmul(jnp.asarray(w1r), jnp.asarray(w1i), ar, ai,
                       interpret=interpret)

    # step 3: twiddle by exp(-2*pi*i * k1*j2 / n), broadcast over rows
    k1j2 = np.outer(np.arange(n1), np.arange(n2)).astype(np.float64)
    tw = np.exp(-2j * np.pi * k1j2 / n)
    twr = jnp.asarray(tw.real.astype(np.float32))[:, None, :]
    twi = jnp.asarray(tw.imag.astype(np.float32))[:, None, :]
    b3r = br.reshape(n1, rows, n2)
    b3i = bi_.reshape(n1, rows, n2)
    cr = b3r * twr - b3i * twi
    ci = b3r * twi + b3i * twr

    # step 4: row DFTs — C = B @ W(n2)  (W symmetric, so right-multiply)
    w2r, w2i = _dft_factors(n2)
    dr, di = _cmatmul(cr.reshape(n1 * rows, n2), ci.reshape(n1 * rows, n2),
                      jnp.asarray(w2r), jnp.asarray(w2i), interpret=interpret)

    # step 5: transposed readout X[r, k2*n1 + k1] = C[k1, r, k2]
    outr = dr.reshape(n1, rows, n2).transpose(1, 2, 0).reshape(rows, n)
    outi = di.reshape(n1, rows, n2).transpose(1, 2, 0).reshape(rows, n)
    return jax.lax.complex(outr, outi)
