"""Pallas TPU kernel: fused feature -> moment pipeline for ELM statistics.

Algorithm 1 steps 1-3 in ONE grid pass over the *raw* inputs: each
(bn, D) tile of X streams through the MXU computing the hidden tile

    H_tile = g(X_tile @ W_blk + b_blk)          (bn, bl), VMEM only

and both f32 moments accumulate in the same pass

    P[i, j] += H_i^T H_j        (L, L)
    Q[i]    += H_i^T T_tile     (L, M)

so the (N, L) hidden matrix is **never written to HBM** — the paper's
"extremely large" N_i streams through a VMEM-resident working set. This
replaces the two-pass pipeline (materialize H, then kernels/gram.py)
for every raw-input entry point; `core/stats.py` is the consumer.

Two arms, chosen in ``_stats_call`` from shapes alone:

* whole-L (``elm_stats_pallas_whole_l``): grid = (N/bn,). Each step
  builds the row tile's hidden layer once, (bn, L) wide, and adds P's
  upper block triangle in a static loop over ``block_l``-wide row
  blocks, P[i, i:] += H_i^T H[:, i:], then Q += H^T T. W, b, P and Q
  have constant index maps: fetched once a call, resident throughout.
* L-blocked (``elm_stats_pallas``): grid = (L/bl, L/bl, N/bn) with n
  innermost, so a (bl, bl) P block stays resident while N streams
  through. Each visit rebuilds its hidden tiles; Q's block is constant
  in (j, n) and accumulates on the diagonal visit (symmetric mode) or
  at j == 0.

The whole-L arm runs whenever its resident set (``whole_l_vmem_bytes``)
fits ``vmem.vmem_budget()``, half the core's VMEM (64 MiB on a v5e: to
L = 2048 at the default tiles); it requests that set plus a margin.
Wider L falls back to the L-blocked grid. Both add each tile's
contribution to an element over the same bn rows in the same order.

Dtype policy: operands (X, W, H tiles) may be bf16 — the MXU matmuls
run with f32 accumulation (`preferred_element_type`), the activation is
applied in f32, and the H tile is cast back to the operand dtype before
the gram matmul, matching what the unfused oracle computes on a
materialized bf16 H. The cross moment promotes h to T's dtype instead
(f32 targets are never quantized down to a bf16 feature dtype — same
rule as `stats.hidden_moments`). P/Q are always f32 (ridge
conditioning).

Ragged N: padded rows cannot simply be zero-filled like gram.py's
(g(0) = 0.5 for sigmoid!) — the kernel masks hidden rows past N to
exact zeros, so padded tiles contribute nothing to either moment.

Activations come from the shared registry `features.ACTIVATIONS`;
"rbf" is the gaussian branch h = exp(-gamma * ||x - c||^2) computed via
the ||x||^2 - 2 x.c^T + ||c||^2 expansion on the same (bn, bl) tile
(pass W = centers^T and b = gamma).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vmem import vmem_budget, vmem_params

#: Scoped VMEM for the L-blocked moment kernels (the whole-L arm asks
#: for its own resident set, ``whole_l_vmem_bytes``). An f32 dot at
#: HIGHEST keeps bf16 splits of its operands in VMEM beside the
#: double-buffered blocks: at the default tiles and D = 784, the
#: node-batched (vmapped) f32 kernel needs just over the 16 MiB default
#: when compiled for a TPU v5e.
_VMEM_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 2**20)


def mxu_precision(dtype):
    """Full f32 contraction for f32 operands, the default otherwise.

    Mosaic's default contracts f32 operands in a single bf16 pass
    (~1e-3 relative error, measured on a TPU v5e); an f32 caller asked
    for f32, so every in-kernel dot on f32 operands passes HIGHEST.
    """
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def hidden_tile(x_ref, w_ref, b_ref, *, activation, rows_in_tile, out_dtype):
    """g(X_tile @ W_blk + b_blk), rows past `rows_in_tile` masked to 0.

    The one in-kernel hidden-layer implementation, shared by the fused
    moment kernel here and the fused predict kernel
    (kernels/elm_predict.py) so the two planes cannot drift.
    """
    from repro.core.features import ACTIVATIONS  # shared registry, no cycle

    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        x, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32,
    )
    b = b_ref[...].astype(jnp.float32)  # (1, bl): bias, or gamma for rbf
    if activation == "rbf":
        xf = x.astype(jnp.float32)
        wf = w.astype(jnp.float32)
        x_sq = jnp.sum(xf * xf, axis=1, keepdims=True)  # (bn, 1)
        c_sq = jnp.sum(wf * wf, axis=0, keepdims=True)  # (1, bl)
        d2 = jnp.maximum(x_sq - 2.0 * s + c_sq, 0.0)
        h = jnp.exp(-b * d2)
    else:
        h = ACTIVATIONS[activation](s + b)
    bn = h.shape[0]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    h = jnp.where(row_ids < rows_in_tile, h, 0.0)
    return h.astype(out_dtype)


def _elm_stats_kernel(
    x_ref, wi_ref, wj_ref, bi_ref, bj_ref, t_ref, p_ref, q_ref,
    *, activation, num_rows, block_n, symmetric, operand_dtype,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    n = pl.program_id(2)
    rows_in_tile = num_rows - n * block_n  # clamped by the iota compare

    @pl.when(n == 0)
    def _init_p():
        p_ref[...] = jnp.zeros_like(p_ref)

    # Q's block is constant in (j, n): first visit for row-block i is
    # (j=0, n=0) — init there even when the P compute below is skipped.
    @pl.when((n == 0) & (j == 0))
    def _init_q():
        q_ref[...] = jnp.zeros_like(q_ref)

    tile = functools.partial(
        hidden_tile, x_ref,
        activation=activation, rows_in_tile=rows_in_tile,
        out_dtype=operand_dtype,
    )
    _accumulate(
        lambda: tile(wi_ref, bi_ref), lambda: tile(wj_ref, bj_ref),
        t_ref, p_ref, q_ref, i=i, j=j, symmetric=symmetric,
    )


def _accumulate(tile_i, tile_j, t_ref, p_ref, q_ref, *, i, j, symmetric):
    """P[i, j] += H_i^T H_j, and Q[i] += H_i^T T once per (i, n).

    Q accumulates on the diagonal visit (symmetric mode) or at j == 0,
    reusing that visit's h_i. That visit is a branch of its own which
    builds its own h_i: once the grid has more than one L block, Mosaic
    refuses an f32 tile that feeds one transposed-LHS matmul and, under
    a nested conditional, a second one (its MXU transpose-reuse
    transform trips on the shared producer).
    """
    TN = (((0,), (0,)), ((), ()))  # contract rows: H_i^T @ ·

    def visit(with_q: bool, diagonal: bool):
        def body():
            h_i = tile_i()
            # on the diagonal the j-tile IS the i-tile — reuse it
            h_j = h_i if diagonal else tile_j()
            p_ref[...] += jax.lax.dot_general(
                h_i, h_j, TN, precision=mxu_precision(h_i.dtype),
                preferred_element_type=jnp.float32,
            )
            if with_q:
                # T may be wider than the operand dtype (f32 targets
                # with bf16 features) — promote h rather than quantize T
                t = t_ref[...]
                q_ref[...] += jax.lax.dot_general(
                    h_i.astype(t.dtype), t, TN,
                    precision=mxu_precision(t.dtype),
                    preferred_element_type=jnp.float32,
                )

        return body

    if symmetric:
        pl.when(i == j)(visit(with_q=True, diagonal=True))
        pl.when(i < j)(visit(with_q=False, diagonal=False))
    else:
        pl.when(j == 0)(visit(with_q=True, diagonal=False))
        pl.when(j != 0)(visit(with_q=False, diagonal=False))


def _elm_stats_whole_l_kernel(
    x_ref, w_ref, b_ref, t_ref, p_ref, q_ref,
    *, activation, num_rows, block_n, block_l, symmetric, operand_dtype,
):
    n = pl.program_id(0)

    @pl.when(n == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)
        q_ref[...] = jnp.zeros_like(q_ref)

    tile = functools.partial(
        hidden_tile, x_ref, w_ref, b_ref,
        activation=activation, rows_in_tile=num_rows - n * block_n,
        out_dtype=operand_dtype,
    )
    _accumulate_whole_l(
        tile, t_ref, p_ref, q_ref, block_l=block_l, symmetric=symmetric
    )


def _accumulate_whole_l(tile, t_ref, p_ref, q_ref, *, block_l, symmetric):
    """P[i, i:] += H_i^T H[:, i:] for each ``block_l``-wide row block i
    (P[i, :] when not symmetric), and Q += H^T T, from one (bn, L)
    hidden tile built once."""
    TN = (((0,), (0,)), ((), ()))  # contract rows: H^T @ ·
    h = tile()
    for lo in range(0, h.shape[1], block_l):
        hi, start = lo + block_l, lo if symmetric else 0
        p_ref[lo:hi, start:] += jax.lax.dot_general(
            h[:, lo:hi], h[:, start:], TN,
            precision=mxu_precision(h.dtype),
            preferred_element_type=jnp.float32,
        )
    # f32 targets with bf16 features: promote h rather than quantize T
    t = t_ref[...]
    q_ref[...] += jax.lax.dot_general(
        h.astype(t.dtype), t, TN, precision=mxu_precision(t.dtype),
        preferred_element_type=jnp.float32,
    )


def preact_tile(z_ref, b_ref, *, activation, rows_in_tile, out_dtype):
    """g(Z_tile + b_blk), rows past `rows_in_tile` masked to 0.

    The vertical-mode twin of ``hidden_tile``: the feature matmul
    already happened across column-sliced nodes (core/vertical.py
    assembled Z = sum_i X_i W_i on the wire), so the tile only applies
    bias + nonlinearity. The activation runs in f32 and the tile is
    cast back to the operand dtype, matching the fused pipeline's
    policy. No "rbf" branch: a gaussian node has no additive
    preactivation form, so vertical mode rejects it upstream.
    """
    from repro.core.features import ACTIVATIONS  # shared registry, no cycle

    z = z_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)  # (1, bl)
    h = ACTIVATIONS[activation](z + b)
    bn = h.shape[0]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    h = jnp.where(row_ids < rows_in_tile, h, 0.0)
    return h.astype(out_dtype)


def _elm_preact_kernel(
    zi_ref, zj_ref, bi_ref, bj_ref, t_ref, p_ref, q_ref,
    *, activation, num_rows, block_n, symmetric, operand_dtype,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    n = pl.program_id(2)
    rows_in_tile = num_rows - n * block_n  # clamped by the iota compare

    @pl.when(n == 0)
    def _init_p():
        p_ref[...] = jnp.zeros_like(p_ref)

    @pl.when((n == 0) & (j == 0))
    def _init_q():
        q_ref[...] = jnp.zeros_like(q_ref)

    tile = functools.partial(
        preact_tile,
        activation=activation, rows_in_tile=rows_in_tile,
        out_dtype=operand_dtype,
    )
    _accumulate(
        lambda: tile(zi_ref, bi_ref), lambda: tile(zj_ref, bj_ref),
        t_ref, p_ref, q_ref, i=i, j=j, symmetric=symmetric,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "activation", "block_l", "block_n", "interpret", "symmetric"
    ),
)
def elm_preact_stats_pallas(
    Z: jax.Array,
    b: jax.Array,
    T: jax.Array,
    *,
    activation: str = "sigmoid",
    block_l: int = 256,
    block_n: int = 512,
    interpret: bool = False,
    symmetric: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """(P, Q) = (H^T H, H^T T) with H = g(Z + b) fused in VMEM.

    Z: (N, L) assembled preactivation, b: (L,), T: (N, M) -> P: (L, L)
    f32, Q: (L, M) f32. The grid is ``elm_stats_pallas``'s L-blocked
    one — only the tile producer changes (no feature matmul; Z streams
    straight from HBM in (bn, bl) tiles). Padded L columns evaluate g(0) != 0
    but land outside the [:L, :L] slice, exactly like padded W columns
    in the fused pipeline; padded N rows are masked in-kernel.
    """
    N, L = Z.shape
    M = T.shape[1]
    bl = min(block_l, L)
    bn = min(block_n, N)
    pN, pL, pM = (-N) % bn, (-L) % bl, (-M) % 128
    if pN or pL:
        Z = jnp.pad(Z, ((0, pN), (0, pL)))
    b2 = jnp.pad(b, (0, pL))[None, :].astype(jnp.float32)  # (1, L2), 2D
    if pN or pM:
        T = jnp.pad(T, ((0, pN), (0, pM)))
    T = T.astype(jnp.promote_types(Z.dtype, T.dtype))
    N2, L2, M2 = Z.shape[0], Z.shape[1], T.shape[1]
    grid = (L2 // bl, L2 // bl, N2 // bn)
    kernel = functools.partial(
        _elm_preact_kernel,
        activation=activation, num_rows=N, block_n=bn,
        symmetric=symmetric, operand_dtype=Z.dtype,
    )
    P, Q = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bl), lambda i, j, n: (n, i)),  # Z_i
            pl.BlockSpec((bn, bl), lambda i, j, n: (n, j)),  # Z_j
            pl.BlockSpec((1, bl), lambda i, j, n: (0, i)),   # b_i
            pl.BlockSpec((1, bl), lambda i, j, n: (0, j)),   # b_j
            pl.BlockSpec((bn, M2), lambda i, j, n: (n, 0)),  # T
        ],
        out_specs=[
            pl.BlockSpec((bl, bl), lambda i, j, n: (i, j)),
            pl.BlockSpec((bl, M2), lambda i, j, n: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L2, L2), jnp.float32),
            jax.ShapeDtypeStruct((L2, M2), jnp.float32),
        ],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
        name="elm_preact_stats_pallas",
    )(Z, Z, b2, b2, T)
    P = P[:L, :L]
    Q = Q[:L, :M]
    if symmetric:
        upper = jnp.triu(P)
        P = upper + upper.T - jnp.diag(jnp.diag(upper))
    return P, Q


#: rows whose moments one f32 accumulator of ``elm_stats_pallas`` sums.
#: The kernel adds a node's row tiles into one accumulator in turn; over
#: 2^20 rows (2,048 tiles) that order erred more than three bf16 passes
#: on a TPU v5e, so a taller node is summed in blocks of this many rows.
BLOCK_ROWS = 16384


def pairwise_sum(x: jax.Array) -> jax.Array:
    """``x.sum(0)`` as a balanced tree of adds: each term goes through
    about log2(len(x)) roundings, not up to len(x) of them."""
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        head = x[:h] + x[h:2 * h]
        x = jnp.concatenate([head, x[2 * h:]]) if x.shape[0] % 2 else head
    return x[0]


def by_row_blocks(moments, X: jax.Array, T: jax.Array, rows: int):
    """``moments(X, T)`` of one node, summed over blocks of ``rows``
    rows: the whole blocks in one batched call, added pairwise, then
    the rows left over. A node of fewer than two blocks is one call."""
    N = X.shape[0]
    blocks = N // rows
    if blocks < 2:
        return moments(X, T)
    head = blocks * rows
    P, Q = jax.vmap(moments)(
        X[:head].reshape(blocks, rows, X.shape[1]),
        T[:head].reshape(blocks, rows, T.shape[1]),
    )
    P, Q = pairwise_sum(P), pairwise_sum(Q)
    if head < N:
        dP, dQ = moments(X[head:], T[head:])
        P, Q = P + dP, Q + dQ
    return P, Q


@functools.partial(
    jax.jit,
    static_argnames=(
        "activation", "block_l", "block_n", "interpret", "symmetric"
    ),
)
def elm_stats_pallas(
    X: jax.Array,
    W: jax.Array,
    b: jax.Array,
    T: jax.Array,
    *,
    activation: str = "sigmoid",
    block_l: int = 256,
    block_n: int = 512,
    interpret: bool = False,
    symmetric: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """(P, Q) = (H^T H, H^T T) with H = g(X W + b) fused in VMEM.

    X: (N, D), W: (D, L), b: (L,), T: (N, M) -> P: (L, L) f32,
    Q: (L, M) f32. For activation="rbf" pass W = centers^T (D, L) and
    b = gamma (L,). symmetric=True computes only the upper block
    triangle of P (~2x fewer MXU flops) and mirrors it. A node of two
    ``BLOCK_ROWS`` blocks or more is summed in blocks (``by_row_blocks``)
    before P is mirrored.
    """
    D = X.shape[1]
    L = W.shape[1]
    M = T.shape[1]
    bl = min(block_l, L)
    # padded L/D extents are sliced or contribute exact zeros
    pL, pD = (-L) % bl, (-D) % 128
    if pL or pD:
        W = jnp.pad(W, ((0, pD), (0, pL)))
    b2 = jnp.pad(b, (0, pL))[None, :].astype(jnp.float32)  # (1, L2), 2D
    # feature matmul runs at the feature dtype (bf16 operands, f32
    # acc); the targets keep their own precision — the Q dot promotes
    # h to T's dtype instead of quantizing f32 targets down to bf16
    W = W.astype(X.dtype)
    T = T.astype(jnp.promote_types(X.dtype, T.dtype))
    call = functools.partial(
        _stats_call, W=W, b2=b2, activation=activation, block_l=bl,
        block_n=block_n, interpret=interpret, symmetric=symmetric,
    )
    P, Q = by_row_blocks(call, X, T, BLOCK_ROWS)
    P = P[:L, :L]
    Q = Q[:L, :M]
    if symmetric:
        upper = jnp.triu(P)
        P = upper + upper.T - jnp.diag(jnp.diag(upper))
    return P, Q


def whole_l_vmem_bytes(block_n, D, L, M, dtype) -> int:
    """VMEM the whole-L arm keeps at padded widths: the X, W, b, T, P
    and Q blocks, each buffered twice (the constant ones are fetched
    once all the same), and the (block_n, L) hidden tile in f32 with
    the three bf16 parts a HIGHEST dot splits it into."""
    size = jnp.dtype(dtype).itemsize
    blocks = size * (block_n * D + D * L) + 4 * (
        8 * L + block_n * M + L * L + L * M
    )
    return 2 * blocks + (4 + 3 * 2) * block_n * L


def _stats_call(
    X, T, *, W, b2, activation, block_l, block_n, interpret, symmetric
):
    """The kernel over one node's rows: the padded (L2, L2) P, of which
    only the upper blocks when symmetric, and the padded (L2, M2) Q.
    The whole-L arm where its resident set fits ``vmem_budget()``, else
    the L-blocked grid."""
    N = X.shape[0]
    bl, bn = block_l, min(block_n, N)
    # padded X *rows* are masked inside the kernel (g(0) != 0 in
    # general); padded D columns meet W's zero rows
    pN, pD, pM = (-N) % bn, W.shape[0] - X.shape[1], (-T.shape[1]) % 128
    if pN or pD:
        X = jnp.pad(X, ((0, pN), (0, pD)))
    if pN or pM:
        T = jnp.pad(T, ((0, pN), (0, pM)))
    N2, D2, L2, M2 = X.shape[0], X.shape[1], W.shape[1], T.shape[1]
    out_shape = [
        jax.ShapeDtypeStruct((L2, L2), jnp.float32),
        jax.ShapeDtypeStruct((L2, M2), jnp.float32),
    ]
    nbytes = whole_l_vmem_bytes(bn, D2, L2, M2, X.dtype)
    if nbytes <= vmem_budget():
        kernel = functools.partial(
            _elm_stats_whole_l_kernel,
            activation=activation, num_rows=N, block_n=bn, block_l=bl,
            symmetric=symmetric, operand_dtype=X.dtype,
        )
        return pl.pallas_call(
            kernel,
            grid=(N2 // bn,),
            in_specs=[
                pl.BlockSpec((bn, D2), lambda n: (n, 0)),   # X
                pl.BlockSpec((D2, L2), lambda n: (0, 0)),   # W
                pl.BlockSpec((1, L2), lambda n: (0, 0)),    # b
                pl.BlockSpec((bn, M2), lambda n: (n, 0)),   # T
            ],
            out_specs=[
                pl.BlockSpec((L2, L2), lambda n: (0, 0)),
                pl.BlockSpec((L2, M2), lambda n: (0, 0)),
            ],
            out_shape=out_shape,
            compiler_params=vmem_params(nbytes),
            interpret=interpret,
            name="elm_stats_pallas_whole_l",
        )(X, W, b2, T)
    grid = (L2 // bl, L2 // bl, N2 // bn)
    kernel = functools.partial(
        _elm_stats_kernel,
        activation=activation, num_rows=N, block_n=bn,
        symmetric=symmetric, operand_dtype=X.dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, X.shape[1]), lambda i, j, n: (n, 0)),  # X
            pl.BlockSpec((W.shape[0], bl), lambda i, j, n: (0, i)),  # W_i
            pl.BlockSpec((W.shape[0], bl), lambda i, j, n: (0, j)),  # W_j
            pl.BlockSpec((1, bl), lambda i, j, n: (0, i)),           # b_i
            pl.BlockSpec((1, bl), lambda i, j, n: (0, j)),           # b_j
            pl.BlockSpec((bn, M2), lambda i, j, n: (n, 0)),          # T
        ],
        out_specs=[
            pl.BlockSpec((bl, bl), lambda i, j, n: (i, j)),
            pl.BlockSpec((bl, M2), lambda i, j, n: (i, 0)),
        ],
        out_shape=out_shape,
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
        name="elm_stats_pallas",
    )(X, W, W, b2, b2, T)
