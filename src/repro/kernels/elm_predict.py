"""Pallas TPU kernel: fused predict pipeline Y = g(X W + b) @ beta.

The serving-side twin of kernels/elm_stats.py: the paper's output map
(eq. 2)

    f(x) = sum_l beta_l g(w_l, b_l, x)  =  H beta,  H = g(X W + b)

in ONE grid pass over the *raw* inputs. Each (bn, D) tile of X streams
through the MXU computing the hidden tile

    H_tile = g(X_tile @ W_blk + b_blk)          (bn, bl), VMEM only

and the f32 output block accumulates in the same pass

    Y[i] += H_tile @ beta_blk                   (bn, M)

so the (N, L) hidden matrix is **never written to HBM** — a query batch
costs one HBM read of X and one HBM write of Y, the rest lives in VMEM.
This replaces the two-pass path (materialize H, then H @ beta) on every
prediction entry point; `kernels/elm_predict_ops.py` is the dispatching
wrapper and `serving/elm_server.py` the request-level consumer.

Tiling: grid = (N/bn, L/bl) with l innermost so the (bn, M) f32 output
block stays resident while the hidden dimension streams through. The
same ``hidden_tile`` body as the stats kernel supplies H (shared
ACTIVATIONS registry; "rbf" via the ||x||^2 - 2 x.c^T + ||c||^2
expansion with W = centers^T and b = gamma).

Dtype policy: operands (X, W, H tiles) may be bf16 — the MXU matmuls
run with f32 accumulation (`preferred_element_type`), the activation is
applied in f32, and the H tile is cast back to the operand dtype before
the output matmul, matching the unfused oracle on a materialized bf16
H. beta may be wider than the features (f32 readout over bf16
features): the output dot promotes h to beta's dtype rather than
quantizing beta down — the same rule as elm_stats' cross moment. Y
accumulates in f32; the wrapper casts to the oracle's result dtype.

Ragged N: padded rows cannot simply be zero-filled (g(0) != 0 for
sigmoid), so hidden rows past N are masked to exact zeros — the padded
Y rows are then exact zeros too, and are sliced off. Padded L columns
are harmless by construction: beta's padded rows are zero, so the
g(0)-valued padded hidden columns contribute nothing.

Stacked multi-tenant path (``elm_predict_stacked_pallas``): a
micro-batch mixing many tenants carries per-row ids into a stacked
(T, L, M) beta tensor. The shared hidden tile g(XW+b) is computed
ONCE per (bn, bl) block and kept in VMEM scratch while an inner grid
axis walks the distinct tenants of the row block:

    Y[i] += where(tid == t, H_tile, 0) @ betas[t, l_blk]    (bn, M)

so serving T tenants costs one launch, not T: the feature work is
shared, only the readout is per-tenant (decentralized multi-task ELM,
arXiv 1904.11366). The per-block distinct tenant ids are
scalar-prefetched into SMEM and drive the beta BlockSpec's index map,
so only the (bl, M) tiles of tenants present in a row block leave HBM.
Masked padded rows carry tenant id 0; their hidden rows are exact
zeros so that tenant's beta contributes nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.elm_stats import hidden_tile, mxu_precision


def _elm_predict_kernel(
    x_ref, w_ref, b_ref, beta_ref, y_ref,
    *, activation, num_rows, block_n, operand_dtype,
):
    i = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    # rows past N are masked to exact zeros inside hidden_tile (only
    # the last row-block can be ragged; the iota compare clamps the rest)
    h = hidden_tile(
        x_ref, w_ref, b_ref,
        activation=activation,
        rows_in_tile=num_rows - i * block_n,
        out_dtype=operand_dtype,
    )
    beta = beta_ref[...]
    y_ref[...] += jax.lax.dot_general(
        h.astype(beta.dtype), beta,
        dimension_numbers=(((1,), (0,)), ((), ())),  # H @ beta
        precision=mxu_precision(beta.dtype),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_l", "block_n", "interpret"),
)
def elm_predict_pallas(
    X: jax.Array,
    W: jax.Array,
    b: jax.Array,
    beta: jax.Array,
    *,
    activation: str = "sigmoid",
    block_l: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Y = g(X W + b) @ beta with H fused in VMEM.

    X: (N, D), W: (D, L), b: (L,), beta: (L, M) -> Y: (N, M) f32.
    For activation="rbf" pass W = centers^T (D, L) and b = gamma (L,).
    """
    N, D = X.shape
    L = W.shape[1]
    M = beta.shape[1]
    bl = min(block_l, L)
    bn = min(block_n, N)
    # pad to tile multiples; padded X *rows* are masked inside the
    # kernel (g(0) != 0 in general), padded L rows of beta are zero so
    # the padded hidden columns contribute exact zeros, padded D/M
    # extents contribute zeros or are sliced
    pN, pL, pD, pM = (-N) % bn, (-L) % bl, (-D) % 128, (-M) % 128
    if pN or pD:
        X = jnp.pad(X, ((0, pN), (0, pD)))
    if pL or pD:
        W = jnp.pad(W, ((0, pD), (0, pL)))
    b2 = jnp.pad(b, (0, pL))[None, :].astype(jnp.float32)  # (1, L2), 2D
    if pL or pM:
        beta = jnp.pad(beta, ((0, pL), (0, pM)))
    # the feature matmul runs at the feature dtype (bf16 operands, f32
    # acc); the readout keeps its own precision — the output dot
    # promotes h to beta's dtype instead of quantizing beta down
    W = W.astype(X.dtype)
    beta = beta.astype(jnp.promote_types(X.dtype, beta.dtype))
    N2, L2, M2 = X.shape[0], W.shape[1], beta.shape[1]
    grid = (N2 // bn, L2 // bl)
    kernel = functools.partial(
        _elm_predict_kernel,
        activation=activation, num_rows=N, block_n=bn,
        operand_dtype=X.dtype,
    )
    Y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, X.shape[1]), lambda i, l: (i, 0)),   # X
            pl.BlockSpec((W.shape[0], bl), lambda i, l: (0, l)),   # W
            pl.BlockSpec((1, bl), lambda i, l: (0, l)),            # b
            pl.BlockSpec((bl, M2), lambda i, l: (l, 0)),           # beta
        ],
        out_specs=pl.BlockSpec((bn, M2), lambda i, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N2, M2), jnp.float32),
        interpret=interpret,
        name="elm_predict_pallas",
    )(X, W, b2, beta)
    return Y[:N, :M]


def _elm_predict_stacked_kernel(
    uniq_ref, count_ref, x_ref, w_ref, b_ref, tid_ref, beta_ref, y_ref,
    h_ref, *, activation, num_rows, block_n, operand_dtype, max_tenants,
):
    i = pl.program_id(0)
    l = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((l == 0) & (k == 0))
    def _init_y():
        y_ref[...] = jnp.zeros_like(y_ref)

    # the shared hidden tile, once per (row block, L block)
    @pl.when(k == 0)
    def _hidden():
        h = hidden_tile(
            x_ref, w_ref, b_ref,
            activation=activation,
            rows_in_tile=num_rows - i * block_n,
            out_dtype=operand_dtype,
        )
        h_ref[...] = h.astype(h_ref.dtype)

    # k-th distinct tenant of this row block: its (bl, M) beta tile was
    # fetched by the index map; only its rows contract against it (the
    # others add exact zeros, so per-row sums match a per-row gather)
    @pl.when(k < count_ref[i])
    def _contract():
        t = uniq_ref[i * max_tenants + k]
        h = jnp.where(tid_ref[...] == t, h_ref[...], 0.0)
        y_ref[...] += jax.lax.dot_general(
            h, beta_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),  # H @ beta_t
            precision=mxu_precision(h.dtype),
            preferred_element_type=jnp.float32,
        )


def _block_tenants(tids, block_n, num_tenants, max_tenants):
    """Per row block, its distinct tenant ids (ascending, flattened to
    (blocks * max_tenants,)) and how many there are."""
    blocks = jnp.sort(tids.reshape(-1, block_n), axis=1)
    first = jnp.concatenate(
        [
            jnp.ones((blocks.shape[0], 1), bool),
            blocks[:, 1:] != blocks[:, :-1],
        ],
        axis=1,
    )
    count = jnp.sum(first, axis=1, dtype=jnp.int32)
    uniq = jnp.sort(jnp.where(first, blocks, num_tenants), axis=1)
    uniq = jnp.minimum(uniq[:, :max_tenants], num_tenants - 1)
    return uniq.reshape(-1).astype(jnp.int32), count


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_l", "block_n", "interpret"),
)
def elm_predict_stacked_pallas(
    X: jax.Array,
    W: jax.Array,
    b: jax.Array,
    betas: jax.Array,
    tenant_ids: jax.Array,
    *,
    activation: str = "sigmoid",
    block_l: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Y[n] = g(X W + b)[n] @ betas[tenant_ids[n]] with H fused in VMEM.

    X: (N, D), W: (D, L), b: (L,), betas: (T, L, M), tenant_ids: (N,)
    int32 -> Y: (N, M) f32. One launch serves every tenant in the
    batch; the shared hidden tile is computed once per (row, L) block
    and each distinct tenant of the row block costs one (bl, M) beta
    fetch and one masked MXU contraction.
    """
    N, D = X.shape
    L = W.shape[1]
    T, _, M = betas.shape
    bl = min(block_l, L)
    bn = min(block_n, N)
    pN, pL, pD, pM = (-N) % bn, (-L) % bl, (-D) % 128, (-M) % 128
    if pN or pD:
        X = jnp.pad(X, ((0, pN), (0, pD)))
    if pL or pD:
        W = jnp.pad(W, ((0, pD), (0, pL)))
    b2 = jnp.pad(b, (0, pL))[None, :].astype(jnp.float32)
    if pL or pM:
        betas = jnp.pad(betas, ((0, 0), (0, pL), (0, pM)))
    # padded rows carry tenant 0 but their hidden rows are masked to
    # exact zeros, so the contribution is exactly zero
    tids = jnp.asarray(tenant_ids, jnp.int32)
    if pN:
        tids = jnp.pad(tids, (0, pN))
    W = W.astype(X.dtype)
    betas = betas.astype(jnp.promote_types(X.dtype, betas.dtype))
    N2, L2, M2 = X.shape[0], W.shape[1], betas.shape[2]
    K = min(T, bn)  # distinct tenants a row block can hold
    uniq, count = _block_tenants(tids, bn, T, K)

    def beta_block(i, l, k, uniq, count):
        # past the block's last tenant, repeat it: the block index does
        # not move, so the pipeline fetches nothing
        return (uniq[i * K + jnp.minimum(k, count[i] - 1)], l, 0)

    kernel = functools.partial(
        _elm_predict_stacked_kernel,
        activation=activation, num_rows=N, block_n=bn,
        operand_dtype=X.dtype, max_tenants=K,
    )
    Y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N2 // bn, L2 // bl, K),
            in_specs=[
                pl.BlockSpec((bn, X.shape[1]), lambda i, l, k, *_: (i, 0)),
                pl.BlockSpec((W.shape[0], bl), lambda i, l, k, *_: (0, l)),
                pl.BlockSpec((1, bl), lambda i, l, k, *_: (0, l)),
                pl.BlockSpec((bn, 1), lambda i, l, k, *_: (i, 0)),  # tids
                pl.BlockSpec((None, bl, M2), beta_block),
            ],
            out_specs=pl.BlockSpec((bn, M2), lambda i, l, k, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bn, bl), betas.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((N2, M2), jnp.float32),
        interpret=interpret,
        name="elm_predict_stacked_pallas",
    )(uniq, count, X, W, b2, tids[:, None], betas)
    return Y[:N, :M]
