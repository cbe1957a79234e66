"""Plain float32 reference of one application of the SGWT filter bank.

Everything the comparison needs is made here from the configuration:
the spectral graph wavelet kernels (Hammond et al., the SGWT toolbox
defaults), their shifted-Chebyshev coefficients (the paper's Eq. (14),
midpoint rule at Chebyshev angles) and the three-term recurrence of the
paper's Algorithm 1 over a plain sparse product: L's rows padded to its
widest row, one gather per column of that table.  Nothing is
imported from the program and nothing it made is used.

Layout: signals are vertex-major, (n, b), so that each gather reads
whole rows of b lanes.  ``apply`` returns (eta, n, b).

``precision="high"`` is the control: every product of the matvec is
taken as an MXU "high" (bf16_3x) pass takes it, from bf16 high and low
parts of both factors, dropping the low-times-low term.
"""
import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


def wavelet_kernel(alpha=2.0, beta=2.0, x1=1.0, x2=2.0):
    """SGWT band-pass kernel: x^alpha below x1, a cubic spline between
    x1 and x2 matching value and slope, x^-beta above."""
    A = np.array([[1, x1, x1 ** 2, x1 ** 3],
                  [1, x2, x2 ** 2, x2 ** 3],
                  [0, 1, 2 * x1, 3 * x1 ** 2],
                  [0, 1, 2 * x2, 3 * x2 ** 2]], np.float64)
    a = np.linalg.solve(A, np.array([1.0, 1.0, alpha / x1, -beta / x2]))

    def g(x):
        x = np.maximum(np.asarray(x, np.float64), 0.0)
        lo = (x / x1) ** alpha
        mid = a[0] + a[1] * x + a[2] * x ** 2 + a[3] * x ** 3
        hi = np.where(x > 0, (x2 / np.maximum(x, 1e-30)) ** beta, 0.0)
        return np.where(x < x1, lo, np.where(x <= x2, mid, hi))

    return g


def sgwt_kernels(lmax, J, lpfactor=20.0):
    """[h, g(t_1 x), ..., g(t_J x)]: the scaling function and J wavelets
    at log-spaced scales, eta = J + 1."""
    g = wavelet_kernel()
    lmin = lmax / lpfactor
    scales = np.exp(np.linspace(np.log(2.0 / lmin), np.log(1.0 / lmax), J))
    grid = np.linspace(0.0, lmax, 4000)
    gamma = float(max(np.max(g(t * grid)) for t in scales))
    kernels = [lambda x: gamma * np.exp(-(np.asarray(x, np.float64)
                                         / (0.6 * lmin)) ** 4)]
    kernels += [lambda x, t=t: g(t * np.asarray(x, np.float64))
                for t in scales]
    return kernels


def coefficients(lmax, K, J, n_points=1000):
    """(eta, K + 1) shifted-Chebyshev coefficients on [0, lmax], with the
    paper's half-c0 convention."""
    alpha = lmax / 2.0
    phi = np.pi * (np.arange(n_points) + 0.5) / n_points
    ks = np.arange(K + 1)[:, None]
    return np.stack([
        (2.0 / n_points) * np.sum(
            np.cos(ks * phi) * g(alpha * (np.cos(phi) + 1.0)), axis=1)
        for g in sgwt_kernels(lmax, J)])


def _split(a):
    """a = hi + lo + rest, hi and lo rounded to bfloat16's 8 bits
    (``reduce_precision``, which the compiler keeps, where a round trip
    through bfloat16 may be simplified away)."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(a - hi, exponent_bits=8,
                                        mantissa_bits=7)


def padded_rows(graph):
    """L's rows as a (n, widest row) table of column ids and values, the
    short rows padded with zero values: the product is then a gather per
    column of the table, with no scatter."""
    width = np.diff(graph.indptr)
    slot = np.arange(graph.nnz) - np.repeat(graph.indptr[:-1], width)
    rows = graph.row_ids()
    cols = np.repeat(np.arange(graph.n, dtype=np.int32)[:, None],
                     width.max(), axis=1)
    vals = np.zeros(cols.shape, np.float32)
    cols[rows, slot] = graph.indices
    vals[rows, slot] = graph.data
    return cols, vals


def matvec(cols, vals, precision="highest"):
    """x (n, b) -> L x from the padded row table: one row gather and
    multiply-add per table column, summed in column order in float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")

    def mv(x):
        def add(y, col):
            c, v = col
            v, xs = v[:, None], x[c]
            if precision == "high":
                (v_hi, v_lo), (x_hi, x_lo) = _split(v), _split(xs)
                return y + (v_hi * x_hi + (v_hi * x_lo + v_lo * x_hi)), None
            return y + v * xs, None

        return jax.lax.scan(add, jnp.zeros_like(x), (cols.T, vals.T))[0]

    return mv


def apply(mv, x, coeffs, lmax):
    """Algorithm 1 written out: x (n, b) -> (eta, n, b)."""
    c = jnp.asarray(coeffs, jnp.float32)[:, :, None, None]
    alpha = lmax / 2.0
    t = mv(x) / alpha - x
    acc = 0.5 * c[:, 0] * x + c[:, 1] * t

    def order(carry, ck):
        t_prev, t, acc = carry
        t_next = (2.0 / alpha) * mv(t) - 2.0 * t - t_prev
        return (t, t_next, acc + ck * t_next), None

    return jax.lax.scan(order, (x, t, acc), jnp.moveaxis(c[:, 2:], 1, 0))[0][2]


class Reference:
    """The reference for one graph and filter bank, on one device."""

    def __init__(self, graph, K, J, device, precision="highest"):
        self.n = graph.n
        self.coeffs = coefficients(graph.lmax, K, J)
        self.eta = self.coeffs.shape[0]
        self.device = device
        self._rows = jax.device_put(padded_rows(graph), device)
        coeffs, lmax = self.coeffs, graph.lmax

        # (b, n) in the program's layout -> (b, eta, n); the row table is
        # an argument, not a constant compiled into the program
        def run(cols, vals, x):
            mv = matvec(cols, vals, precision)
            return jnp.transpose(apply(mv, x.T, coeffs, lmax), (2, 0, 1))

        self._apply = jax.jit(run)

    def chunk(self, batch):
        """Signals per reference call: the largest divisor of the batch
        that keeps the (eta, n, b) accumulator near 2 GiB."""
        cap = max(1, (1 << 29) // (self.eta * self.n))
        return max(d for d in range(1, min(batch, cap) + 1) if batch % d == 0)

    def __call__(self, x):
        """(b, n) signals on any device -> (b, eta, n) on this one."""
        return self._apply(*self._rows, jax.device_put(x, self.device))
