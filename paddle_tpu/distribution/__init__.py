"""Probability distributions (paddle.distribution parity: reference
python/paddle/distribution/ — Distribution base :distribution.py, the
concrete families, kl_divergence/register_kl :kl.py, Transform stack
:transform.py).

TPU-first: every density/statistic is a jnp expression dispatched through
the op layer (so log_prob/entropy participate in the autograd tape and jit),
and sampling draws keys from the global Generator — reparameterized
`rsample` is differentiable through the same tape for the continuous
families (jax supplies implicit gradients for gamma-based samplers).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor
from ..framework.random import next_key
from ..ops._dispatch import nary, ensure_tensor

__all__ = [
    "Distribution", "ExponentialFamily", "Normal", "Uniform", "Bernoulli",
    "Beta", "Binomial", "Categorical", "Cauchy", "Chi2", "Dirichlet",
    "Exponential", "Gamma", "Geometric", "Gumbel", "Independent", "Laplace",
    "LogNormal", "Multinomial", "MultivariateNormal", "Poisson", "StudentT",
    "TransformedDistribution", "kl_divergence", "register_kl",
    "ContinuousBernoulli", "LKJCholesky",
]

_HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)


def _op(f, *tensors):
    return nary(f, [ensure_tensor(t) for t in tensors], "distribution")


class Distribution:
    """Reference distribution.py Distribution: batch_shape/event_shape,
    sample/log_prob/prob/entropy surface."""

    def __init__(self, batch_shape=(), event_shape=()):
        self._batch_shape = tuple(batch_shape)
        self._event_shape = tuple(event_shape)

    @property
    def batch_shape(self):
        return self._batch_shape

    @property
    def event_shape(self):
        return self._event_shape

    @property
    def mean(self):
        raise NotImplementedError

    @property
    def variance(self):
        raise NotImplementedError

    @property
    def stddev(self):
        v = self.variance
        return _op(jnp.sqrt, v)

    def sample(self, shape=()):
        out = self.rsample(shape)
        out.stop_gradient = True
        return out

    def rsample(self, shape=()):
        raise NotImplementedError

    def log_prob(self, value):
        raise NotImplementedError

    def prob(self, value):
        lp = self.log_prob(value)
        return _op(jnp.exp, lp)

    def entropy(self):
        raise NotImplementedError

    def kl_divergence(self, other):
        return kl_divergence(self, other)

    def _extend(self, shape):
        return tuple(shape) + self._batch_shape + self._event_shape


class ExponentialFamily(Distribution):
    """Marker base (reference exponential_family.py); Bregman-divergence
    entropy fallbacks are provided per-family analytically instead."""


# ---------------------------------------------------------------------------
# continuous families
# ---------------------------------------------------------------------------

class Normal(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = ensure_tensor(loc, dtype="float32")
        self.scale = ensure_tensor(scale, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(self.loc.shape,
                                                   self.scale.shape)))

    @property
    def mean(self):
        return self.loc

    @property
    def variance(self):
        return _op(jnp.square, self.scale)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda m, s: m + s * jax.random.normal(key, ext),
                   self.loc, self.scale)

    def log_prob(self, value):
        return _op(lambda m, s, v: -jnp.square(v - m) / (2 * jnp.square(s))
                   - jnp.log(s) - _HALF_LOG_2PI,
                   self.loc, self.scale, value)

    def entropy(self):
        return _op(lambda s: 0.5 + _HALF_LOG_2PI + jnp.log(s)
                   + jnp.zeros(self._batch_shape), self.scale)

    def cdf(self, value):
        return _op(lambda m, s, v: 0.5 * (1 + jax.scipy.special.erf(
            (v - m) / (s * math.sqrt(2.0)))), self.loc, self.scale, value)



class LogNormal(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = ensure_tensor(loc, dtype="float32")
        self.scale = ensure_tensor(scale, dtype="float32")
        self._base = Normal(loc, scale)
        super().__init__(self._base.batch_shape)

    @property
    def mean(self):
        return _op(lambda m, s: jnp.exp(m + jnp.square(s) / 2),
                   self.loc, self.scale)

    @property
    def variance(self):
        return _op(lambda m, s: (jnp.exp(jnp.square(s)) - 1)
                   * jnp.exp(2 * m + jnp.square(s)), self.loc, self.scale)

    def rsample(self, shape=()):
        return _op(jnp.exp, self._base.rsample(shape))

    def log_prob(self, value):
        return _op(lambda m, s, v: -jnp.square(jnp.log(v) - m)
                   / (2 * jnp.square(s)) - jnp.log(v * s) - _HALF_LOG_2PI,
                   self.loc, self.scale, value)

    def entropy(self):
        return _op(lambda m, s: m + 0.5 + _HALF_LOG_2PI + jnp.log(s),
                   self.loc, self.scale)


class Uniform(Distribution):
    def __init__(self, low, high, name=None):
        self.low = ensure_tensor(low, dtype="float32")
        self.high = ensure_tensor(high, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(self.low.shape,
                                                   self.high.shape)))

    @property
    def mean(self):
        return _op(lambda a, b: (a + b) / 2, self.low, self.high)

    @property
    def variance(self):
        return _op(lambda a, b: jnp.square(b - a) / 12, self.low, self.high)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda a, b: a + (b - a) * jax.random.uniform(key, ext),
                   self.low, self.high)

    def log_prob(self, value):
        return _op(lambda a, b, v: jnp.where(
            (v >= a) & (v < b), -jnp.log(b - a), -jnp.inf),
            self.low, self.high, value)

    def entropy(self):
        return _op(lambda a, b: jnp.log(b - a), self.low, self.high)


class Exponential(ExponentialFamily):
    def __init__(self, rate, name=None):
        self.rate = ensure_tensor(rate, dtype="float32")
        super().__init__(tuple(self.rate.shape))

    @property
    def mean(self):
        return _op(lambda r: 1.0 / r, self.rate)

    @property
    def variance(self):
        return _op(lambda r: 1.0 / jnp.square(r), self.rate)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda r: jax.random.exponential(key, ext) / r, self.rate)

    def log_prob(self, value):
        return _op(lambda r, v: jnp.where(v >= 0, jnp.log(r) - r * v,
                                          -jnp.inf), self.rate, value)

    def entropy(self):
        return _op(lambda r: 1.0 - jnp.log(r), self.rate)


class Laplace(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = ensure_tensor(loc, dtype="float32")
        self.scale = ensure_tensor(scale, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(self.loc.shape,
                                                   self.scale.shape)))

    @property
    def mean(self):
        return self.loc

    @property
    def variance(self):
        return _op(lambda s: 2 * jnp.square(s), self.scale)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda m, s: m + s * jax.random.laplace(key, ext),
                   self.loc, self.scale)

    def log_prob(self, value):
        return _op(lambda m, s, v: -jnp.abs(v - m) / s - jnp.log(2 * s),
                   self.loc, self.scale, value)

    def entropy(self):
        return _op(lambda s: 1 + jnp.log(2 * s), self.scale)


class Gumbel(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = ensure_tensor(loc, dtype="float32")
        self.scale = ensure_tensor(scale, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(self.loc.shape,
                                                   self.scale.shape)))

    _EULER = 0.5772156649015329

    @property
    def mean(self):
        return _op(lambda m, s: m + s * self._EULER, self.loc, self.scale)

    @property
    def variance(self):
        return _op(lambda s: (math.pi ** 2 / 6) * jnp.square(s), self.scale)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda m, s: m + s * jax.random.gumbel(key, ext),
                   self.loc, self.scale)

    def log_prob(self, value):
        def f(m, s, v):
            z = (v - m) / s
            return -(z + jnp.exp(-z)) - jnp.log(s)

        return _op(f, self.loc, self.scale, value)

    def entropy(self):
        return _op(lambda s: jnp.log(s) + 1 + self._EULER, self.scale)


class Cauchy(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = ensure_tensor(loc, dtype="float32")
        self.scale = ensure_tensor(scale, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(self.loc.shape,
                                                   self.scale.shape)))

    @property
    def mean(self):
        raise ValueError("Cauchy has no mean")

    @property
    def variance(self):
        raise ValueError("Cauchy has no variance")

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda m, s: m + s * jax.random.cauchy(key, ext),
                   self.loc, self.scale)

    def log_prob(self, value):
        return _op(lambda m, s, v: -jnp.log(math.pi * s
                   * (1 + jnp.square((v - m) / s))),
                   self.loc, self.scale, value)

    def entropy(self):
        return _op(lambda s: jnp.log(4 * math.pi * s), self.scale)


class Gamma(ExponentialFamily):
    def __init__(self, concentration, rate, name=None):
        self.concentration = ensure_tensor(concentration, dtype="float32")
        self.rate = ensure_tensor(rate, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(
            self.concentration.shape, self.rate.shape)))

    @property
    def mean(self):
        return _op(jnp.divide, self.concentration, self.rate)

    @property
    def variance(self):
        return _op(lambda a, r: a / jnp.square(r), self.concentration,
                   self.rate)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda a, r: jax.random.gamma(key, jnp.broadcast_to(
            a, ext)) / r, self.concentration, self.rate)

    def log_prob(self, value):
        return _op(lambda a, r, v: a * jnp.log(r) + (a - 1) * jnp.log(v)
                   - r * v - jax.scipy.special.gammaln(a),
                   self.concentration, self.rate, value)

    def entropy(self):
        return _op(lambda a, r: a - jnp.log(r)
                   + jax.scipy.special.gammaln(a)
                   + (1 - a) * jax.scipy.special.digamma(a),
                   self.concentration, self.rate)


class Chi2(Gamma):
    def __init__(self, df, name=None):
        df_t = ensure_tensor(df, dtype="float32")
        self.df = df_t
        super().__init__(_op(lambda d: d / 2, df_t),
                         _op(lambda d: jnp.full(d.shape, 0.5), df_t))


class Beta(ExponentialFamily):
    def __init__(self, alpha, beta, name=None):
        self.alpha = ensure_tensor(alpha, dtype="float32")
        self.beta = ensure_tensor(beta, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(self.alpha.shape,
                                                   self.beta.shape)))

    @property
    def mean(self):
        return _op(lambda a, b: a / (a + b), self.alpha, self.beta)

    @property
    def variance(self):
        return _op(lambda a, b: a * b / (jnp.square(a + b) * (a + b + 1)),
                   self.alpha, self.beta)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda a, b: jax.random.beta(
            key, jnp.broadcast_to(a, ext), jnp.broadcast_to(b, ext)),
            self.alpha, self.beta)

    def log_prob(self, value):
        return _op(lambda a, b, v: (a - 1) * jnp.log(v)
                   + (b - 1) * jnp.log1p(-v)
                   - jax.scipy.special.betaln(a, b),
                   self.alpha, self.beta, value)

    def entropy(self):
        def f(a, b):
            dg = jax.scipy.special.digamma
            return (jax.scipy.special.betaln(a, b) - (a - 1) * dg(a)
                    - (b - 1) * dg(b) + (a + b - 2) * dg(a + b))

        return _op(f, self.alpha, self.beta)


class Dirichlet(ExponentialFamily):
    def __init__(self, concentration, name=None):
        self.concentration = ensure_tensor(concentration, dtype="float32")
        shape = tuple(self.concentration.shape)
        super().__init__(shape[:-1], shape[-1:])

    @property
    def mean(self):
        return _op(lambda c: c / jnp.sum(c, -1, keepdims=True),
                   self.concentration)

    @property
    def variance(self):
        def f(c):
            c0 = jnp.sum(c, -1, keepdims=True)
            m = c / c0
            return m * (1 - m) / (c0 + 1)

        return _op(f, self.concentration)

    def rsample(self, shape=()):
        key = next_key()
        ext = tuple(shape) + self._batch_shape
        return _op(lambda c: jax.random.dirichlet(
            key, c, shape=ext if ext else None), self.concentration)

    def log_prob(self, value):
        def f(c, v):
            lognorm = (jnp.sum(jax.scipy.special.gammaln(c), -1)
                       - jax.scipy.special.gammaln(jnp.sum(c, -1)))
            return jnp.sum((c - 1) * jnp.log(v), -1) - lognorm

        return _op(f, self.concentration, value)

    def entropy(self):
        def f(c):
            dg = jax.scipy.special.digamma
            k = c.shape[-1]
            c0 = jnp.sum(c, -1)
            lognorm = (jnp.sum(jax.scipy.special.gammaln(c), -1)
                       - jax.scipy.special.gammaln(c0))
            return (lognorm + (c0 - k) * dg(c0)
                    - jnp.sum((c - 1) * dg(c), -1))

        return _op(f, self.concentration)


class StudentT(Distribution):
    def __init__(self, df, loc=0.0, scale=1.0, name=None):
        self.df = ensure_tensor(df, dtype="float32")
        self.loc = ensure_tensor(loc, dtype="float32")
        self.scale = ensure_tensor(scale, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(
            self.df.shape, self.loc.shape, self.scale.shape)))

    @property
    def mean(self):
        return _op(lambda d, m: jnp.where(d > 1, m, jnp.nan), self.df,
                   self.loc)

    @property
    def variance(self):
        return _op(lambda d, s: jnp.where(
            d > 2, jnp.square(s) * d / (d - 2), jnp.nan), self.df,
            self.scale)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        return _op(lambda d, m, s: m + s * jax.random.t(
            key, jnp.broadcast_to(d, ext)), self.df, self.loc, self.scale)

    def log_prob(self, value):
        def f(d, m, s, v):
            z = (v - m) / s
            gl = jax.scipy.special.gammaln
            return (gl((d + 1) / 2) - gl(d / 2)
                    - 0.5 * jnp.log(d * math.pi) - jnp.log(s)
                    - (d + 1) / 2 * jnp.log1p(jnp.square(z) / d))

        return _op(f, self.df, self.loc, self.scale, value)

    def entropy(self):
        def f(d, s):
            dg = jax.scipy.special.digamma
            gl = jax.scipy.special.gammaln
            return ((d + 1) / 2 * (dg((d + 1) / 2) - dg(d / 2))
                    + 0.5 * jnp.log(d) + jax.scipy.special.betaln(
                        d / 2, jnp.asarray(0.5, d.dtype)) + jnp.log(s))

        return _op(f, self.df, self.scale)


class MultivariateNormal(Distribution):
    def __init__(self, loc, covariance_matrix=None, precision_matrix=None,
                 scale_tril=None, name=None):
        self.loc = ensure_tensor(loc, dtype="float32")
        if sum(x is not None for x in (covariance_matrix, precision_matrix,
                                       scale_tril)) != 1:
            raise ValueError("give exactly one of covariance_matrix / "
                             "precision_matrix / scale_tril")
        if covariance_matrix is not None:
            cov = ensure_tensor(covariance_matrix, dtype="float32")
        elif precision_matrix is not None:
            p = ensure_tensor(precision_matrix, dtype="float32")
            cov = _op(jnp.linalg.inv, p)
        else:
            st = ensure_tensor(scale_tril, dtype="float32")
            cov = _op(lambda L: L @ jnp.swapaxes(L, -1, -2), st)
        self.covariance_matrix = cov
        self._tril = _op(jnp.linalg.cholesky, cov)
        d = self.loc.shape[-1]
        super().__init__(tuple(self.loc.shape[:-1]), (d,))

    @property
    def mean(self):
        return self.loc

    @property
    def variance(self):
        return _op(lambda c: jnp.diagonal(c, axis1=-2, axis2=-1),
                   self.covariance_matrix)

    def rsample(self, shape=()):
        key = next_key()
        ext = tuple(shape) + self._batch_shape + self._event_shape

        def f(m, L):
            eps = jax.random.normal(key, ext)
            return m + jnp.einsum("...ij,...j->...i", L, eps)

        return _op(f, self.loc, self._tril)

    def log_prob(self, value):
        def f(m, L, v):
            d = m.shape[-1]
            diff = v - m
            sol = jax.scipy.linalg.solve_triangular(
                L, diff[..., None], lower=True)[..., 0]
            maha = jnp.sum(jnp.square(sol), -1)
            logdet = jnp.sum(jnp.log(jnp.diagonal(L, axis1=-2, axis2=-1)),
                             -1)
            return -0.5 * maha - logdet - d * _HALF_LOG_2PI

        return _op(f, self.loc, self._tril, value)

    def entropy(self):
        def f(L):
            d = L.shape[-1]
            logdet = jnp.sum(jnp.log(jnp.diagonal(L, axis1=-2, axis2=-1)),
                             -1)
            return d / 2 * (1 + 2 * _HALF_LOG_2PI) + logdet

        return _op(f, self._tril)


# ---------------------------------------------------------------------------
# discrete families
# ---------------------------------------------------------------------------

class Bernoulli(ExponentialFamily):
    def __init__(self, probs, name=None):
        self.probs = ensure_tensor(probs, dtype="float32")
        super().__init__(tuple(self.probs.shape))

    @property
    def mean(self):
        return self.probs

    @property
    def variance(self):
        return _op(lambda p: p * (1 - p), self.probs)

    def sample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        out = _op(lambda p: jax.random.bernoulli(
            key, jnp.broadcast_to(p, ext)).astype(jnp.float32), self.probs)
        out.stop_gradient = True
        return out

    rsample = None  # discrete: no reparameterized path

    def log_prob(self, value):
        return _op(lambda p, v: v * jnp.log(p) + (1 - v) * jnp.log1p(-p),
                   self.probs, value)

    def entropy(self):
        return _op(lambda p: -(p * jnp.log(p) + (1 - p) * jnp.log1p(-p)),
                   self.probs)


class Geometric(Distribution):
    """P(X=k) = (1-p)^k p, k in {0, 1, ...} (reference geometric.py)."""

    def __init__(self, probs, name=None):
        self.probs = ensure_tensor(probs, dtype="float32")
        super().__init__(tuple(self.probs.shape))

    @property
    def mean(self):
        return _op(lambda p: (1 - p) / p, self.probs)

    @property
    def variance(self):
        return _op(lambda p: (1 - p) / jnp.square(p), self.probs)

    def sample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        out = _op(lambda p: (jax.random.geometric(
            key, jnp.broadcast_to(p, ext)) - 1).astype(jnp.float32),
            self.probs)
        out.stop_gradient = True
        return out

    def log_prob(self, value):
        return _op(lambda p, v: v * jnp.log1p(-p) + jnp.log(p),
                   self.probs, value)

    def entropy(self):
        return _op(lambda p: -((1 - p) * jnp.log1p(-p) + p * jnp.log(p)) / p,
                   self.probs)


class Poisson(ExponentialFamily):
    def __init__(self, rate, name=None):
        self.rate = ensure_tensor(rate, dtype="float32")
        super().__init__(tuple(self.rate.shape))

    @property
    def mean(self):
        return self.rate

    @property
    def variance(self):
        return self.rate

    def sample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        out = _op(lambda r: jax.random.poisson(
            key, jnp.broadcast_to(r, ext)).astype(jnp.float32), self.rate)
        out.stop_gradient = True
        return out

    def log_prob(self, value):
        return _op(lambda r, v: v * jnp.log(r) - r
                   - jax.scipy.special.gammaln(v + 1), self.rate, value)

    def entropy(self):
        # exact truncated sum for small rates; Stirling-series asymptote
        # 0.5*log(2*pi*e*r) - 1/(12r) - 1/(24r^2) - 19/(360r^3) above
        def f(r):
            k = jnp.arange(64, dtype=jnp.float32)
            logpmf = (k[..., :] * jnp.log(r[..., None]) - r[..., None]
                      - jax.scipy.special.gammaln(k + 1))
            p = jnp.exp(logpmf)
            exact = -jnp.sum(p * logpmf, -1)
            asym = (0.5 * jnp.log(2 * math.pi * math.e * r)
                    - 1 / (12 * r) - 1 / (24 * r ** 2)
                    - 19 / (360 * r ** 3))
            return jnp.where(r < 16.0, exact, asym)

        return _op(lambda r: f(jnp.atleast_1d(r)).reshape(jnp.shape(r)),
                   self.rate)


class Binomial(Distribution):
    def __init__(self, total_count, probs, name=None):
        self.total_count = ensure_tensor(total_count, dtype="float32")
        self.probs = ensure_tensor(probs, dtype="float32")
        super().__init__(tuple(np.broadcast_shapes(
            self.total_count.shape, self.probs.shape)))

    @property
    def mean(self):
        return _op(jnp.multiply, self.total_count, self.probs)

    @property
    def variance(self):
        return _op(lambda n, p: n * p * (1 - p), self.total_count,
                   self.probs)

    def sample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)
        # under x64 (this framework's global default) jax 0.4.x's
        # binomial kernel clamps f32 operands against f64 literals and
        # TypeErrors — run it in f64 there; without x64 requesting f64
        # would only emit truncation warnings, so skip the cast
        dt = (jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
        out = _op(lambda n, p: jax.random.binomial(
            key, jnp.broadcast_to(n, ext).astype(dt),
            jnp.broadcast_to(p, ext).astype(dt), dtype=dt
        ).astype(jnp.float32), self.total_count, self.probs)
        out.stop_gradient = True
        return out

    def log_prob(self, value):
        def f(n, p, v):
            gl = jax.scipy.special.gammaln
            return (gl(n + 1) - gl(v + 1) - gl(n - v + 1)
                    + v * jnp.log(p) + (n - v) * jnp.log1p(-p))

        return _op(f, self.total_count, self.probs, value)


class Categorical(Distribution):
    """Reference categorical.py: `logits` are unnormalized log-probs."""

    def __init__(self, logits, name=None):
        self.logits = ensure_tensor(logits, dtype="float32")
        shape = tuple(self.logits.shape)
        super().__init__(shape[:-1])
        self._n = shape[-1]

    @property
    def probs_t(self):
        return _op(lambda l: jax.nn.softmax(l, -1), self.logits)

    def sample(self, shape=()):
        key = next_key()
        ext = tuple(shape) + self._batch_shape
        out = _op(lambda l: jax.random.categorical(
            key, l, shape=ext).astype(jnp.int64), self.logits)
        out.stop_gradient = True
        return out

    def log_prob(self, value):
        def f(l, v):
            logp = jax.nn.log_softmax(l, -1)
            return jnp.take_along_axis(
                logp, v[..., None].astype(jnp.int32), -1)[..., 0]

        return _op(f, self.logits, value)

    def probs(self, value):
        return _op(jnp.exp, self.log_prob(value))

    def entropy(self):
        def f(l):
            logp = jax.nn.log_softmax(l, -1)
            return -jnp.sum(jnp.exp(logp) * logp, -1)

        return _op(f, self.logits)


class Multinomial(Distribution):
    def __init__(self, total_count, probs, name=None):
        self.total_count = int(total_count)
        self.probs = ensure_tensor(probs, dtype="float32")
        shape = tuple(self.probs.shape)
        super().__init__(shape[:-1], shape[-1:])

    @property
    def mean(self):
        return _op(lambda p: self.total_count * p, self.probs)

    @property
    def variance(self):
        return _op(lambda p: self.total_count * p * (1 - p), self.probs)

    def sample(self, shape=()):
        key = next_key()
        ext = tuple(shape) + self._batch_shape
        n = self.total_count

        def f(p):
            out_shape = ext + p.shape[-1:] if ext else None
            return jax.random.multinomial(
                key, n, p, shape=out_shape).astype(jnp.float32)

        out = _op(f, self.probs)
        out.stop_gradient = True
        return out

    def log_prob(self, value):
        def f(p, v):
            gl = jax.scipy.special.gammaln
            return (gl(jnp.sum(v, -1) + 1) - jnp.sum(gl(v + 1), -1)
                    + jnp.sum(v * jnp.log(p), -1))

        return _op(f, self.probs, value)


class ContinuousBernoulli(ExponentialFamily):
    """Reference distribution/continuous_bernoulli.py (Loaiza-Ganem &
    Cunningham 2019): support (0, 1), density C(l) l^x (1-l)^(1-x) with
    C(l) = 2 atanh(1-2l)/(1-2l) (-> 2 at l=1/2). Sampling by the
    closed-form inverse CDF (reparameterizable)."""

    def __init__(self, probs, lims=(0.499, 0.501), name=None):
        self.probs = ensure_tensor(probs, dtype="float32")
        self._lims = lims
        super().__init__(tuple(self.probs.shape))

    def _stable_l(self, l):
        lo, hi = self._lims
        near = (l > lo) & (l < hi)
        return jnp.where(near, lo, l), near

    def _log_norm(self, l):
        ls, near = self._stable_l(l)
        c = 2.0 * jnp.arctanh(1.0 - 2.0 * ls) / (1.0 - 2.0 * ls)
        # Taylor at l=1/2: C ~= 2 + (1-2l)^2 * 2/3
        t = 2.0 + (1.0 - 2.0 * l) ** 2 * (2.0 / 3.0)
        return jnp.log(jnp.where(near, t, c))

    @property
    def mean(self):
        def f(l):
            ls, near = self._stable_l(l)
            m = ls / (2.0 * ls - 1.0) \
                + 1.0 / (2.0 * jnp.arctanh(1.0 - 2.0 * ls))
            # Taylor at 1/2: 1/2 + (l - 1/2)/3
            return jnp.where(near, 0.5 + (l - 0.5) / 3.0, m)

        return _op(f, self.probs)

    @property
    def variance(self):
        # var = E[x^2]-mean^2; use the paper's closed form via mean
        def f(l):
            ls, near = self._stable_l(l)
            m = ls / (2.0 * ls - 1.0) \
                + 1.0 / (2.0 * jnp.arctanh(1.0 - 2.0 * ls))
            v = ls * (ls - 1.0) / (1.0 - 2.0 * ls) ** 2 \
                + 1.0 / (2.0 * jnp.arctanh(1.0 - 2.0 * ls)) ** 2
            return jnp.where(near, 1.0 / 12.0 - (l - 0.5) ** 2 / 15.0, v)

        return _op(f, self.probs)

    def rsample(self, shape=()):
        key = next_key()
        ext = self._extend(shape)

        def f(l):
            u = jax.random.uniform(key, ext, minval=1e-6, maxval=1 - 1e-6)
            ls, near = self._stable_l(l)
            x = (jnp.log1p((2.0 * ls - 1.0) * u / (1.0 - ls))
                 / (jnp.log(ls) - jnp.log1p(-ls)))
            return jnp.where(near, u, x)

        return _op(f, self.probs)

    def log_prob(self, value):
        return _op(lambda l, x: x * jnp.log(l) + (1 - x) * jnp.log1p(-l)
                   + self._log_norm(l), self.probs, value)

    def cdf(self, value):
        def f(l, x):
            ls, near = self._stable_l(l)
            c = (ls ** x * (1 - ls) ** (1 - x) + ls - 1.0) \
                / (2.0 * ls - 1.0)
            return jnp.clip(jnp.where(near, x, c), 0.0, 1.0)

        return _op(f, self.probs, value)

    def entropy(self):
        m = self.mean
        return _op(lambda l, mm: -(self._log_norm(l) + mm * jnp.log(l)
                                   + (1 - mm) * jnp.log1p(-l)),
                   self.probs, m)

    def icdf(self, value):
        def f(l, u):
            ls, near = self._stable_l(l)
            x = (jnp.log1p((2.0 * ls - 1.0) * u / (1.0 - ls))
                 / (jnp.log(ls) - jnp.log1p(-ls)))
            return jnp.where(near, u, x)

        return _op(f, self.probs, value)


class LKJCholesky(Distribution):
    """Reference distribution/lkj_cholesky.py — Cholesky factors of LKJ-
    distributed correlation matrices. Onion-method sampling (one Beta
    draw + one hypersphere direction per row) and the Stan-manual
    density over Cholesky factors:
    log p(L) = sum_i (2(eta-1) + d - i) log L_ii - log Z(d, eta).
    Numerics verified against torch.distributions.LKJCholesky
    (tests/test_distribution.py)."""

    def __init__(self, dim, concentration=1.0, sample_method="onion",
                 name=None):
        if dim < 2:
            raise ValueError("LKJCholesky needs dim >= 2")
        if sample_method == "cvine":
            raise NotImplementedError(
                "cvine sampling is not implemented; LKJCholesky samples "
                "with the onion method (identical distribution, "
                "different trajectories)")
        if sample_method != "onion":
            raise ValueError(f"unknown sample_method {sample_method!r}")
        self.dim = int(dim)
        self.concentration = ensure_tensor(concentration,
                                           dtype="float32")
        super().__init__(tuple(self.concentration.shape),
                         (self.dim, self.dim))

    def rsample(self, shape=()):
        key = next_key()
        k1, k2 = jax.random.split(key)
        d = self.dim
        batch = tuple(shape) + self._batch_shape

        def f(conc):
            marginal = conc + 0.5 * (d - 2)
            off = jnp.concatenate([jnp.zeros(1),
                                   jnp.arange(d - 1, dtype=jnp.float32)])
            a = off + 0.5
            b = marginal[..., None] - 0.5 * off
            y = jax.random.beta(k1, jnp.broadcast_to(a, batch + (d,)),
                                jnp.broadcast_to(b, batch + (d,)))
            u = jax.random.normal(k2, batch + (d, d))
            u = jnp.tril(u, -1)
            norm = jnp.linalg.norm(u, axis=-1, keepdims=True)
            u_sphere = u / jnp.maximum(norm, 1e-30)
            u_sphere = u_sphere.at[..., 0, :].set(0.0)
            w = jnp.sqrt(y[..., None]) * u_sphere
            diag = jnp.sqrt(jnp.clip(1.0 - jnp.sum(w ** 2, -1), 1e-30))
            return w + jnp.vectorize(jnp.diag,
                                     signature="(n)->(n,n)")(diag)

        return _op(f, self.concentration)

    def log_prob(self, value):
        d = self.dim

        def f(conc, L):
            diag = jnp.diagonal(L, axis1=-2, axis2=-1)[..., 1:]
            order = 2.0 * (conc[..., None] - 1.0) + d \
                - jnp.arange(2, d + 1, dtype=jnp.float32)
            unnorm = jnp.sum(order * jnp.log(diag), -1)
            dm1 = d - 1
            alpha = conc + 0.5 * dm1
            denom = jax.scipy.special.gammaln(alpha) * dm1
            num = jax.scipy.special.multigammaln(alpha - 0.5, dm1)
            pi_const = 0.5 * dm1 * math.log(math.pi)
            return unnorm - (pi_const + num - denom)

        return _op(f, self.concentration, value)


class Independent(Distribution):
    """Reinterpret batch dims as event dims (reference independent.py)."""

    def __init__(self, base, reinterpreted_batch_rank=1):
        self.base = base
        r = int(reinterpreted_batch_rank)
        self._r = r
        bshape = base.batch_shape
        super().__init__(bshape[:len(bshape) - r],
                         bshape[len(bshape) - r:] + base.event_shape)

    @property
    def mean(self):
        return self.base.mean

    @property
    def variance(self):
        return self.base.variance

    def rsample(self, shape=()):
        return self.base.rsample(shape)

    def sample(self, shape=()):
        return self.base.sample(shape)

    def log_prob(self, value):
        lp = self.base.log_prob(value)
        r = self._r
        return (_op(lambda x: jnp.sum(x, axis=tuple(range(-r, 0))), lp)
                if r else lp)

    def entropy(self):
        e = self.base.entropy()
        return _op(lambda x: jnp.sum(x, axis=tuple(range(-self._r, 0))), e)


# ---------------------------------------------------------------------------
# transforms + TransformedDistribution (reference transform.py)
# ---------------------------------------------------------------------------

class Transform:
    def forward(self, x):
        raise NotImplementedError

    def inverse(self, y):
        raise NotImplementedError

    def forward_log_det_jacobian(self, x):
        raise NotImplementedError


class AffineTransform(Transform):
    def __init__(self, loc, scale):
        self.loc = ensure_tensor(loc, dtype="float32")
        self.scale = ensure_tensor(scale, dtype="float32")

    def forward(self, x):
        return _op(lambda m, s, v: m + s * v, self.loc, self.scale, x)

    def inverse(self, y):
        return _op(lambda m, s, v: (v - m) / s, self.loc, self.scale, y)

    def forward_log_det_jacobian(self, x):
        return _op(lambda s, v: jnp.broadcast_to(jnp.log(jnp.abs(s)),
                                                 v.shape), self.scale, x)


class ExpTransform(Transform):
    def forward(self, x):
        return _op(jnp.exp, x)

    def inverse(self, y):
        return _op(jnp.log, y)

    def forward_log_det_jacobian(self, x):
        return ensure_tensor(x) * 1.0


class SigmoidTransform(Transform):
    def forward(self, x):
        return _op(jax.nn.sigmoid, x)

    def inverse(self, y):
        return _op(lambda v: jnp.log(v) - jnp.log1p(-v), y)

    def forward_log_det_jacobian(self, x):
        return _op(lambda v: -jax.nn.softplus(-v) - jax.nn.softplus(v), x)


class TanhTransform(Transform):
    def forward(self, x):
        return _op(jnp.tanh, x)

    def inverse(self, y):
        return _op(jnp.arctanh, y)

    def forward_log_det_jacobian(self, x):
        return _op(lambda v: 2.0 * (math.log(2.0) - v
                                    - jax.nn.softplus(-2.0 * v)), x)


class TransformedDistribution(Distribution):
    def __init__(self, base, transforms):
        self.base = base
        self.transforms = list(transforms)
        super().__init__(base.batch_shape, base.event_shape)

    def rsample(self, shape=()):
        x = self.base.rsample(shape)
        for t in self.transforms:
            x = t.forward(x)
        return x

    def sample(self, shape=()):
        out = self.rsample(shape)
        out.stop_gradient = True
        return out

    def log_prob(self, value):
        lp = None
        y = value
        for t in reversed(self.transforms):
            x = t.inverse(y)
            ld = t.forward_log_det_jacobian(x)
            lp = ld if lp is None else lp + ld
            y = x
        base_lp = self.base.log_prob(y)
        return base_lp - lp if lp is not None else base_lp


# ---------------------------------------------------------------------------
# KL divergence registry (reference kl.py: register_kl / kl_divergence)
# ---------------------------------------------------------------------------

_KL_REGISTRY = {}


def register_kl(p_cls, q_cls):
    def decorator(fn):
        _KL_REGISTRY[(p_cls, q_cls)] = fn
        return fn

    return decorator


def kl_divergence(p, q):
    for (pc, qc), fn in _KL_REGISTRY.items():
        if isinstance(p, pc) and isinstance(q, qc):
            return fn(p, q)
    raise NotImplementedError(
        f"no KL registered for ({type(p).__name__}, {type(q).__name__})")


@register_kl(Normal, Normal)
def _kl_normal(p, q):
    def f(m1, s1, m2, s2):
        vr = jnp.square(s1 / s2)
        return 0.5 * (vr - 1 - jnp.log(vr)) \
            + jnp.square(m1 - m2) / (2 * jnp.square(s2))

    return _op(f, p.loc, p.scale, q.loc, q.scale)


@register_kl(Uniform, Uniform)
def _kl_uniform(p, q):
    # inf when q's support does not cover p's (otherwise the log-ratio
    # could go negative as q shrinks)
    return _op(lambda a1, b1, a2, b2: jnp.where(
        (a2 > a1) | (b2 < b1), jnp.inf, jnp.log((b2 - a2) / (b1 - a1))),
        p.low, p.high, q.low, q.high)


@register_kl(Bernoulli, Bernoulli)
def _kl_bernoulli(p, q):
    def f(p1, p2):
        return (p1 * (jnp.log(p1) - jnp.log(p2))
                + (1 - p1) * (jnp.log1p(-p1) - jnp.log1p(-p2)))

    return _op(f, p.probs, q.probs)


@register_kl(Categorical, Categorical)
def _kl_categorical(p, q):
    def f(l1, l2):
        lp1 = jax.nn.log_softmax(l1, -1)
        lp2 = jax.nn.log_softmax(l2, -1)
        return jnp.sum(jnp.exp(lp1) * (lp1 - lp2), -1)

    return _op(f, p.logits, q.logits)


@register_kl(Exponential, Exponential)
def _kl_exponential(p, q):
    return _op(lambda r1, r2: jnp.log(r1) - jnp.log(r2) + r2 / r1 - 1,
               p.rate, q.rate)


@register_kl(Gamma, Gamma)
def _kl_gamma(p, q):
    def f(a1, r1, a2, r2):
        gl = jax.scipy.special.gammaln
        dg = jax.scipy.special.digamma
        return ((a1 - a2) * dg(a1) - gl(a1) + gl(a2)
                + a2 * (jnp.log(r1) - jnp.log(r2)) + a1 * (r2 - r1) / r1)

    return _op(f, p.concentration, p.rate, q.concentration, q.rate)


@register_kl(Beta, Beta)
def _kl_beta(p, q):
    def f(a1, b1, a2, b2):
        gl = jax.scipy.special.betaln
        dg = jax.scipy.special.digamma
        return (gl(a2, b2) - gl(a1, b1)
                + (a1 - a2) * dg(a1) + (b1 - b2) * dg(b1)
                + (a2 - a1 + b2 - b1) * dg(a1 + b1))

    return _op(f, p.alpha, p.beta, q.alpha, q.beta)


@register_kl(Dirichlet, Dirichlet)
def _kl_dirichlet(p, q):
    def f(c1, c2):
        gl = jax.scipy.special.gammaln
        dg = jax.scipy.special.digamma
        s1 = jnp.sum(c1, -1)
        return (gl(s1) - jnp.sum(gl(c1), -1)
                - gl(jnp.sum(c2, -1)) + jnp.sum(gl(c2), -1)
                + jnp.sum((c1 - c2) * (dg(c1) - dg(s1)[..., None]), -1))

    return _op(f, p.concentration, q.concentration)


@register_kl(ContinuousBernoulli, ContinuousBernoulli)
def _kl_continuous_bernoulli(p, q):
    # KL = E_p[log p - log q] = (C_p - C_q normalizers) + mean_p * (log
    # l_p - log l_q) + (1-mean_p) * (log(1-l_p) - log(1-l_q))
    m = p.mean
    return _op(lambda lp, lq, mm: (p._log_norm(lp) - q._log_norm(lq)
                                   + mm * (jnp.log(lp) - jnp.log(lq))
                                   + (1 - mm) * (jnp.log1p(-lp)
                                                 - jnp.log1p(-lq))),
               p.probs, q.probs, m)


@register_kl(Laplace, Laplace)
def _kl_laplace(p, q):
    def f(m1, s1, m2, s2):
        d = jnp.abs(m1 - m2)
        return (jnp.log(s2 / s1) + s1 / s2 * jnp.exp(-d / s1)
                + d / s2 - 1)

    return _op(f, p.loc, p.scale, q.loc, q.scale)


@register_kl(Geometric, Geometric)
def _kl_geometric(p, q):
    return _op(lambda p1, p2: (1 - p1) / p1
               * (jnp.log1p(-p1) - jnp.log1p(-p2))
               + jnp.log(p1) - jnp.log(p2), p.probs, q.probs)


@register_kl(Poisson, Poisson)
def _kl_poisson(p, q):
    return _op(lambda r1, r2: r1 * (jnp.log(r1) - jnp.log(r2)) - r1 + r2,
               p.rate, q.rate)


@register_kl(LogNormal, LogNormal)
def _kl_lognormal(p, q):
    return _kl_normal(p._base, q._base)


@register_kl(MultivariateNormal, MultivariateNormal)
def _kl_mvn(p, q):
    def f(m1, L1, m2, L2):
        d = m1.shape[-1]
        sol = jax.scipy.linalg.solve_triangular(
            L2, (m2 - m1)[..., None], lower=True)[..., 0]
        maha = jnp.sum(jnp.square(sol), -1)
        M = jax.scipy.linalg.solve_triangular(L2, L1, lower=True)
        tr = jnp.sum(jnp.square(M), (-2, -1))
        logdet = (jnp.sum(jnp.log(jnp.diagonal(L2, axis1=-2, axis2=-1)), -1)
                  - jnp.sum(jnp.log(jnp.diagonal(L1, axis1=-2, axis2=-1)),
                            -1))
        return 0.5 * (tr + maha - d) + logdet

    return _op(f, p.loc, p._tril, q.loc, q._tril)
