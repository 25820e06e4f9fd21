"""Direct solve of small symmetric positive-definite systems.

:func:`spd_solve` is the float32 Cholesky factorization of ``H`` and the
two substitutions: the solve the bank's primal Newton kind
(game/random_effect.bank_primal) needs for ``step = -H^-1 g``.

One system is ``cho_solve((cholesky(H), True), g)``. Under ``jax.vmap`` a
``custom_vmap`` rule sees the whole batch ``[E, D, D]`` and picks the path
from the static ``D`` alone (:func:`solve_path`):

* ``D == 1``: a division.
* ``2 <= D <= MAX_LANE_DIM`` on the TPU: ONE Pallas kernel,
  ``photon_spd_solve``, with the SYSTEM ON THE LANES. XLA's batched
  Cholesky keeps each small matrix on a tile and walks its columns with
  the batch outside, so nothing of it is vectorised over the systems
  (7-8 us a 64 x 64 system on a v5e, 0.1 here). A block of 128 systems
  sits in VMEM as ``[D, D, 128]`` and every operation of the
  right-looking factorization (the pivot's root, the column scale, the
  rank-1 update of the trailing rows) and of the two substitutions is
  elementwise over the lanes. ``H`` is read once, only the solution is
  written.
* a larger ``D``, or any ``D`` on the CPU: XLA's Cholesky, which
  amortises its column walk over a large matrix (and on the CPU the
  kernel would run interpreted).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.custom_batching
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import cho_solve

from photon_ml_tpu.utils.backend import effective_platform

Array = jax.Array

KERNEL_NAME = "photon_spd_solve"

_LANES = 128  # systems a block
_SUBLANES = 8  # float32 rows a vreg: D pads to a multiple
# VMEM the kernel's matrices may take: the pipeline's two buffers of the
# block of H and the working copy the factorization runs in, each
# [Dp, Dp, 128] float32. A v5e core has 128 MiB; the vectors ([Dp, 128])
# ride in the limit's slack.
_VMEM_MATRIX_BYTES = 32 << 20
_VMEM_SLACK_BYTES = 4 << 20
_MATRIX_COPIES = 3

# The largest D (a multiple of 8) whose three blocks fit: 144.
MAX_LANE_DIM = (
    math.isqrt(_VMEM_MATRIX_BYTES // (_MATRIX_COPIES * _LANES * 4))
    // _SUBLANES * _SUBLANES
)


def solve_path(dim: int, platform: str) -> str:
    """Which way a BATCH of ``[dim, dim]`` systems is solved on
    ``platform``: ``division`` | ``lanes`` | ``xla``. The one decision,
    made from the shape; the batching rule and the bank's counter
    (``photon_bank_primal_systems_total{solve}``) both ask here."""
    if dim == 1:
        return "division"
    if platform == "tpu" and dim <= MAX_LANE_DIM:
        return "lanes"
    return "xla"


def _cho_solve(H: Array, g: Array) -> Array:
    return cho_solve((jnp.linalg.cholesky(H), True), g)


@jax.custom_batching.custom_vmap
def spd_solve(H: Array, g: Array) -> Array:
    """``H^-1 g`` for ONE symmetric positive-definite ``H`` ``[D, D]``
    and ``g`` ``[D]``, float32, by Cholesky. A ``H`` that is not positive
    definite gives a non-finite answer (as ``cholesky`` does), on every
    path."""
    return _cho_solve(H, g)


@spd_solve.def_vmap
def _spd_solve_batched(axis_size, in_batched, H, g):
    H_batched, g_batched = in_batched
    if not H_batched:
        H = jnp.broadcast_to(H, (axis_size,) + H.shape)
    if not g_batched:
        g = jnp.broadcast_to(g, (axis_size,) + g.shape)
    path = solve_path(H.shape[-1], effective_platform())
    if path == "division":
        h = H[:, 0]
        out = jnp.where(h > 0, g / h, jnp.nan)
    elif path == "lanes":
        out = lane_solve(H, g)
    else:
        out = jax.vmap(_cho_solve)(H, g)
    return out, True


def _lane_solve_kernel(h_ref, g_ref, x_ref, a_ref, c_ref, *, dim):
    """One block: ``h_ref`` ``[dim, dim, L]`` (row, column, system),
    ``g_ref`` / ``x_ref`` ``[dim, L]``. Scratch: ``a_ref`` the working
    matrix, whose row ``k`` ends as column ``k`` of the factor ``L``
    (zero above the diagonal); ``c_ref`` the current column below the
    diagonal, for the tile loop's row reads.

    The trailing matrix is kept whole (both triangles), so column ``k``
    is read as ROW ``k``: a leading index, which may be dynamic. Both
    loops are ``fori_loop``s over ``k`` (a row of the block is picked by
    a compare against the sublane index, not by a static slice): the
    kernel traces and lowers in the same few milliseconds at every
    ``dim``, and a bank program traces it once a capacity class."""
    lanes = g_ref.shape[-1]
    jj = jax.lax.broadcasted_iota(jnp.int32, (dim, lanes), 0)

    def row_of(v, k):
        return jnp.sum(jnp.where(jj == k, v, 0.0), axis=0, keepdims=True)

    a_ref[...] = h_ref[...]

    def factor_step(k, carry):
        # y: g through the forward substitution (g is one more row of
        # the matrix being factored); diag: L's diagonal, by row
        y, diag = carry
        row = a_ref[k]
        d_k = jnp.sqrt(row_of(row, k))
        col = jnp.where(jj >= k, row / d_k, 0.0)  # L[:, k]
        below = jnp.where(jj > k, col, 0.0)
        y_k = row_of(y, k) / d_k
        y = jnp.where(jj == k, y_k, y - below * y_k)
        diag = jnp.where(jj == k, d_k, diag)
        # A[i, :] -= L[i, k] L[:, k], eight rows a loop step from the
        # tile that holds row k + 1 on (a row i <= k of that tile
        # subtracts below[i] = 0; what a later step reads of a row
        # starts at its diagonal, so the columns before k may hold
        # anything)
        c_ref[...] = below

        def update_tile(t, carry):
            base = pl.multiple_of(t * _SUBLANES, _SUBLANES)
            c_tile = c_ref[pl.ds(base, _SUBLANES), :]
            for s in range(_SUBLANES):
                a_ref[base + s] = a_ref[base + s] - c_tile[s:s + 1, :] * col
            return carry

        jax.lax.fori_loop(
            (k + 1) // _SUBLANES, dim // _SUBLANES, update_tile, 0
        )
        a_ref[k] = col
        return y, diag

    y, diag = jax.lax.fori_loop(
        0, dim, factor_step, (g_ref[...], jnp.ones_like(g_ref[...]))
    )

    def back_step(i, x):
        # L' x = y, from the last row up: x holds y up to row k and the
        # solution below it
        k = dim - 1 - i
        below = jnp.where(jj > k, a_ref[k], 0.0)
        dot = jnp.sum(below * x, axis=0, keepdims=True)
        return jnp.where(jj == k, (x - dot) / diag, x)

    x_ref[...] = jax.lax.fori_loop(0, dim, back_step, y)


@partial(jax.jit, static_argnames=("interpret",))
def lane_solve(H: Array, g: Array, *, interpret: bool = False) -> Array:
    """``[E, D, D]``, ``[E, D]`` -> ``[E, D]``: the kernel over blocks of
    128 systems. ``D`` pads to a multiple of 8 with identity rows, ``E``
    to a multiple of 128 with systems whose lanes are dropped; XLA makes
    the block-major turn ``[E, D, D] -> [E / 128, D, D, 128]`` (a
    block's bytes contiguous) with the pad in one pass."""
    n, dim, _ = H.shape
    dim_p = -(-dim // _SUBLANES) * _SUBLANES
    blocks = -(-n // _LANES)
    pad_n, pad_d = blocks * _LANES - n, dim_p - dim
    H = jnp.pad(H, ((0, pad_n), (0, pad_d), (0, pad_d)))
    if pad_d:
        H = H + jnp.diag(jnp.arange(dim_p) >= dim).astype(H.dtype)
    g = jnp.pad(g, ((0, pad_n), (0, pad_d)))
    h_t = H.reshape(blocks, _LANES, dim_p, dim_p).transpose(0, 2, 3, 1)
    g_t = g.reshape(blocks, _LANES, dim_p).transpose(0, 2, 1)
    vector = pl.BlockSpec((None, dim_p, _LANES), lambda b: (b, 0, 0))
    x_t = pl.pallas_call(
        partial(_lane_solve_kernel, dim=dim_p),
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec(
                (None, dim_p, dim_p, _LANES), lambda b: (b, 0, 0, 0)
            ),
            vector,
        ],
        out_specs=vector,
        out_shape=jax.ShapeDtypeStruct((blocks, dim_p, _LANES), H.dtype),
        scratch_shapes=[
            pltpu.VMEM((dim_p, dim_p, _LANES), H.dtype),
            pltpu.VMEM((dim_p, _LANES), H.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_MATRIX_BYTES + _VMEM_SLACK_BYTES,
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(h_t, g_t)
    return x_t.transpose(0, 2, 1).reshape(blocks * _LANES, dim_p)[:n, :dim]
