"""The paper's Matern kernel with beta - d/2 = 1 (arXiv:1708.09707, section
6.2) of the plain reference:

    phi(y, y') = r K_1(r) / (2^(beta - 1) Gamma(beta)),  r = ||y - y'||,
    beta = d/2 + 1,

with phi(0) = 1 / (2^(beta - 1) Gamma(beta)) exactly (r K_1(r) -> 1).

r K_1(r) is computed two ways, neither of them the library's:

* r <= 1: the defining power series (Abramowitz and Stegun 9.6.11) in
  q = r^2 / 4,
      r K_1(r) = 1 + q (ln(q) A(q) - B(q)),
      A(q) = sum_k q^k / (k! (k+1)!),
      B(q) = sum_k (psi(k+1) + psi(k+2)) q^k / (k! (k+1)!),
  with its coefficients computed here from psi(n+1) = -gamma + H_n;
* r > 1: r K_1(r) = sqrt(r) e^-r g(t), g a Chebyshev series in
  t = 2/r - 1 whose coefficients :data:`CHEB` were fitted once to scipy:
      numpy.polynomial.chebyshev.chebinterpolate(
          lambda t: sqrt(r) * scipy.special.k1e(r), 12),  r = 2 / (t + 1)
  (relative error of the fit 1e-9 on [1, inf)).

The log and the exp ask for JAX's accurate forms: a TPU v5e's default
float32 log errs by up to 1.1e-4 (absolute), which put up to 3.2e-5 into
these values there.
"""
import math

EULER_GAMMA = 0.5772156649015329
TERMS = 8                 # of each series: q <= 1/4, the 8th term < 1e-12

CHEB = (1.453275667593843, 0.19051350202060688, -0.008406706460485738,
        0.0008815363164264272, -0.00012999576952154375,
        2.3441638819231225e-05, -4.853488996327269e-06,
        1.11399584622167e-06, -2.772677863284455e-07,
        7.371652523293427e-08, -2.069485821860459e-08,
        6.037643731646896e-09, -1.6777497664586276e-09)


def _psi(n: int) -> float:
    """Digamma at the whole number n >= 1."""
    return -EULER_GAMMA + sum(1.0 / j for j in range(1, n))


_FACT = [math.factorial(k) * math.factorial(k + 1) for k in range(TERMS)]
SERIES_A = tuple(1.0 / f for f in _FACT)
SERIES_B = tuple((_psi(k + 1) + _psi(k + 2)) / f for k, f in enumerate(_FACT))


def _horner(x, coef):
    acc = coef[-1]
    for c in coef[-2::-1]:
        acc = acc * x + c
    return acc


def _clenshaw(t, coef):
    b1 = b2 = 0.0
    for c in coef[:0:-1]:
        b1, b2 = 2.0 * t * b1 - b2 + c, b1
    return t * b1 - b2 + coef[0]


def r_k1(r):
    """r K_1(r) of r >= 0 (an array), 1 at r = 0."""
    import jax.numpy as jnp
    from jax import lax
    accurate = lax.AccuracyMode.HIGHEST
    small = r <= 1.0
    rs = jnp.where(small, r, 1.0)
    rl = jnp.where(small, 1.0, r)
    # q is kept off 0 so that ln(q) is finite; below 1e-30 the series is 1
    q = jnp.maximum(0.25 * rs * rs, 1e-30)
    series = 1.0 + q * (lax.log(q, accuracy=accurate) * _horner(q, SERIES_A)
                        - _horner(q, SERIES_B))
    fit = (jnp.sqrt(rl) * lax.exp(-rl, accuracy=accurate)
           * _clenshaw(2.0 / rl - 1.0, CHEB))
    return jnp.where(small, series, fit)


def scale(d: int) -> float:
    """1 / (2^(beta - 1) Gamma(beta)), beta = d/2 + 1: phi(0)."""
    beta = d / 2.0 + 1.0
    return 1.0 / (2.0 ** (beta - 1.0) * math.gamma(beta))


def kernel(y, yp):
    """Between (m, d) and (n, d): summed coordinate differences, exact zero
    distance on the diagonal."""
    import jax.numpy as jnp
    sq = sum((y[:, None, j] - yp[None, :, j]) ** 2 for j in range(y.shape[1]))
    return r_k1(jnp.sqrt(sq)) * scale(y.shape[1])
