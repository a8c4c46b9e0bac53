"""MXU associative-memory kernel: Hamming similarity as a +-1 matmul.

This is the TPU-native port of Acc-Demeter's AM crossbar (paper §5.4-5.5).
The PCM array computes ``Q.P + Qbar.Pbar = D - Ham(Q,P)`` as two analog
VMMs by Kirchhoff's law; on TPU the same identity becomes a single MXU
matmul over the +-1 encoding:

    S = Q_hat @ P_hat.T,  Q_hat = 2Q - 1 in {-1,+1}
    agreement = #matching bits = (D + S) / 2

The +-1 partial sums are integers with |S| <= D <= 2^24, exactly
representable in the fp32 accumulator — the kernel is *exact*, matching
the paper's insistence on exact XNOR+popcount (vs the 2-minterm
approximation it rejects, §5.3).

Grid: (B/bm, S/bn, D/bk), k innermost; fp32 accumulation in VMEM scratch;
block shapes default to MXU-aligned (128, 128, 512).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import interpret_default


def _kernel(q_ref, p_ref, o_ref, acc_ref, *, dim: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        q_ref[...], p_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        # agreement = (D + S) / 2 — exact: S and D share parity.
        o_ref[...] = ((dim + acc_ref[...]) * 0.5).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("dim", "bm", "bn", "bk",
                                              "interpret"))
def am_matmul(q_pm: jax.Array, p_pm: jax.Array, *, dim: int | None = None,
              bm: int = 128, bn: int = 128, bk: int = 512,
              interpret: bool | None = None) -> jax.Array:
    """Agreement scores between +-1-encoded queries and prototypes.

    Args:
      q_pm: ``(B, D_pad)`` bf16 in {-1, +1}, zero-padded on the trailing
        dim to a bk multiple (zeros contribute nothing to the +-1 dot).
      p_pm: ``(S, D_pad)`` likewise.
      dim: the LOGICAL HD dimension D (defaults to D_pad).

    Returns:
      ``(B, S)`` int32 agreement counts in [0, dim].
    """
    b, d = q_pm.shape
    s, d2 = p_pm.shape
    assert d == d2, (d, d2)
    dim = d if dim is None else dim
    bm, bn, bk = min(bm, b), min(bn, s), min(bk, d)
    assert b % bm == 0 and s % bn == 0 and d % bk == 0, (
        f"shapes ({b},{s},{d}) must tile by ({bm},{bn},{bk}); pad upstream")
    grid = (b // bm, s // bn, d // bk)

    return pl.pallas_call(
        functools.partial(_kernel, dim=dim),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(interpret),
    )(q_pm, p_pm)
