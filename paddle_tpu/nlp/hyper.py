"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): the residual path widened to `n`
streams, each sublayer reading one mix of them and writing back through
another, with the stream-to-stream matrix held doubly stochastic.

The state after a layer is `X` in R^{n x D} a token, held as [T, n D]
(stream j is columns j D .. (j + 1) D: every stream a lane-aligned slice,
`vec(X)` the row itself; a [T, n, D] array would put n = 4 on the chip's
8-row tile). A sublayer `F`
(attention or an FFN, with its own RMSNorm `norm_F`) has `phi` [n D,
n^2 + 2 n], a bias `b` [n^2 + 2 n], three scalars `a` = (a_pre, a_post,
a_res) and a norm scale over n D:

  x~ = RMSNorm(vec(X));  [u_pre | u_post | u_res] = x~ phi   (n | n | n^2)
  H_pre  = sigmoid(a_pre u_pre + b_pre)                      [n]
  H_post = 2 sigmoid(a_post u_post + b_post)                 [n]
  M0 = exp(clip(a_res mat(u_res) + b_res, lo, hi))           [n, n]
  H_res = M0 after `iters` rounds of (each column / (its sum + eps), then
          each row / (its sum + eps))                        Sinkhorn
  X' = H_res X + H_post^T F(norm_F(H_pre X))

Entry: the embedding copied into the n streams. Exit: the streams summed.
All coefficient arithmetic is float32; the streams keep the compute type.
Scopes: `hc_coef`, `hc_sinkhorn`, `hc_pre`, `hc_post`.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# one sublayer's leaves, under a prefix of the layer's choosing
LEAVES = ("phi", "b", "a", "norm")


def init_sublayer(key: jax.Array, n: int, dim: int,
                  dtype) -> Dict[str, jax.Array]:
    """phi normal(0, 0.02); b zero but for H_res's diagonal (the streams
    start out nearly unmixed); a small gains; norm ones."""
    b = jnp.zeros((n * n + 2 * n,), F32).at[2 * n:].set(
        3.0 * jnp.eye(n, dtype=F32).reshape(-1))
    return {
        "phi": (jax.random.normal(key, (n * dim, n * n + 2 * n), F32)
                * 0.02).astype(dtype),
        "b": b.astype(dtype), "a": jnp.full((3,), 0.1, dtype),
        "norm": jnp.ones((n * dim,), dtype)}


def _split(w: jax.Array, pieces: int):
    """w float32 -> `pieces` bfloat16 arrays that add up to it (to 8, 16,
    24 bits of its mantissa)."""
    out = []
    for _ in range(pieces):
        hi = w.astype(jnp.bfloat16)
        out.append(hi)
        w = w - hi.astype(F32)
    return out


@jax.custom_vjp
def dot_f32(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [T, K] @ w [K, m] float32, to float32's precision, WITHOUT a
    float32 copy of x: a float32 GEMM takes its operand as an array of its
    own, and x is the whole residual state (0.9 GB in float32 at 16k
    tokens of 4 x 3584, forward, and again for its cotangent). A bfloat16
    x is exact in bfloat16, so `x @ w` is the sum of three native GEMMs
    against w's three bfloat16 pieces, accumulated in float32. Any other x
    takes the plain GEMM at the highest precision."""
    return _dot_f32_fwd(x, w)[0]


def _dot_f32_fwd(x, w):
    if x.dtype != jnp.bfloat16:
        return jnp.dot(x.astype(F32), w, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=F32), (x, w)
    m = w.shape[1]
    z = jnp.dot(x, jnp.concatenate(_split(w, 3), 1),
                preferred_element_type=F32)
    return z[:, :m] + z[:, m:2 * m] + z[:, 2 * m:], (x, w)


def _dot_f32_bwd(res, dz):
    x, w = res
    if x.dtype != jnp.bfloat16:
        hi = jax.lax.Precision.HIGHEST
        return (jnp.dot(dz, w.T, precision=hi).astype(x.dtype),
                jnp.dot(x.astype(F32).T, dz, precision=hi))
    m = w.shape[1]
    # dx leaves in x's type, so two pieces of dz and of w carry all that
    # survives its rounding; dw stays float32: three pieces of dz
    (d0, d1), (w0, w1) = _split(dz, 2), _split(w, 2)
    dx = jnp.dot(jnp.concatenate([d0, d1, d0], 1),
                 jnp.concatenate([w0, w0, w1], 1).T,
                 preferred_element_type=x.dtype)
    dw = jnp.dot(x.T, jnp.concatenate(_split(dz, 3), 1),
                 preferred_element_type=F32)
    return dx, dw[:, :m] + dw[:, m:2 * m] + dw[:, 2 * m:]


dot_f32.defvjp(_dot_f32_fwd, _dot_f32_bwd)


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m [..., n, n] positive -> doubly stochastic: `iters` rounds of
    (columns, then rows) divided by their sums + eps."""
    # unrolled: 20 rounds on [T, 4, 4] fuse into one elementwise pass, and
    # a fori_loop's reverse mode would save every round
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def coefficients(X: jax.Array, hp: Dict[str, jax.Array], *, n: int,
                 iters: int, eps: float, clamp: Tuple[float, float],
                 norm_eps: float):
    """X [T, n D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]),
    float32."""
    T = X.shape[0]
    with jax.named_scope("hc_coef"):
        xf = X.astype(F32)
        r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + norm_eps)
        # x~ phi = r (X (norm phi)): the row scale leaves the GEMM
        u = r * dot_f32(X, hp["norm"].astype(F32)[:, None]
                        * hp["phi"].astype(F32))
        a, b = hp["a"].astype(F32), hp["b"].astype(F32)
        h_pre = jax.nn.sigmoid(a[0] * u[:, :n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * u[:, n:2 * n] + b[n:2 * n])
        m0 = jnp.exp(jnp.clip(a[2] * u[:, 2 * n:] + b[2 * n:], *clamp)
                     ).reshape(T, n, n)
    with jax.named_scope("hc_sinkhorn"):
        h_res = sinkhorn(m0, iters, eps)
    return h_pre, h_post, h_res


def _streams(X: jax.Array, n: int):
    D = X.shape[1] // n
    return [X[:, j * D:(j + 1) * D].astype(F32) for j in range(n)]


def pre(X: jax.Array, h_pre: jax.Array) -> jax.Array:
    """The sublayer's input: H_pre X, [T, D] in the streams' type."""
    with jax.named_scope("hc_pre"):
        # n is 4: a sum of n scaled streams is one elementwise pass over
        # X, where a [T, 1, n] x [T, n, D] batched dot is T tiny GEMMs
        n = h_pre.shape[1]
        return sum(h_pre[:, j, None] * x
                   for j, x in enumerate(_streams(X, n))).astype(X.dtype)


def post(X: jax.Array, y: jax.Array, h_res: jax.Array,
         h_post: jax.Array) -> jax.Array:
    """H_res X + H_post^T y: the streams mixed, the sublayer's output
    written back into each."""
    with jax.named_scope("hc_post"):
        n = h_post.shape[1]
        xs, yf = _streams(X, n), y.astype(F32)
        return jnp.concatenate(
            [(sum(h_res[:, i, j, None] * xs[j] for j in range(n))
              + h_post[:, i, None] * yf).astype(X.dtype)
             for i in range(n)], axis=1)


def sublayer(X: jax.Array, hp: Dict[str, jax.Array], fn: Callable, **kw):
    """One sublayer on the n-stream path X [T, n D]. `fn` maps the mixed
    input [T, D] to the sublayer's output [T, D] (its own RMSNorm inside), or to
    (output, anything else), which is handed back beside X'."""
    h_pre, h_post, h_res = coefficients(X, hp, **kw)
    y = fn(pre(X, h_pre))
    y, rest = y if isinstance(y, tuple) else (y, None)
    X = post(X, y, h_res, h_post)
    return X if rest is None else (X, rest)


def enter(x: jax.Array, n: int) -> jax.Array:
    """[T, D] -> [T, n D]: the embedding copied into every stream."""
    return jnp.tile(x, (1, n))


def leave(X: jax.Array, n: int) -> jax.Array:
    """[T, n D] -> [T, D]: the streams summed (float32 sum)."""
    return sum(_streams(X, n)).astype(X.dtype)
