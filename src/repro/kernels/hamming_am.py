"""VPU associative-memory kernel: packed XNOR+popcount (paper Eq. 2).

The digital formulation the paper contrasts with its analog VMM: Hamming
distance over bit-packed uint32 words (XOR + popcount), kept here as the
*bandwidth-optimal* path — it moves 16x fewer HBM bytes than the bf16
+-1 matmul (2 B/bit -> 1/8 B/bit) at the price of living on the VPU
instead of the MXU.  The roofline analysis in EXPERIMENTS.md §Perf decides
which formulation wins per shape.

Grid: (B/bm, S/bn, W/bw), w innermost, int32 accumulation in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import interpret_default


def _kernel(q_ref, p_ref, o_ref, acc_ref, *, dim: int):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = jnp.bitwise_xor(q_ref[...][:, None, :], p_ref[...][None, :, :])
    acc_ref[...] += jnp.bitwise_count(x).astype(jnp.int32).sum(axis=-1)

    @pl.when(w == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = dim - acc_ref[...]


@functools.partial(jax.jit, static_argnames=("dim", "bm", "bn", "bw",
                                              "interpret"))
def hamming_am(q_packed: jax.Array, p_packed: jax.Array, *,
               dim: int | None = None, bm: int = 8, bn: int = 128,
               bw: int = 256, interpret: bool | None = None) -> jax.Array:
    """Agreement scores between packed queries and prototypes.

    Args:
      q_packed: ``(B, W)`` uint32 packed query HD vectors (zero-padded
        words XOR to zero and add no popcount).
      p_packed: ``(S, W)`` uint32 packed prototypes.
      dim: logical HD dimension (defaults to 32*W).

    Returns:
      ``(B, S)`` int32 agreement counts in [0, dim].
    """
    b, w = q_packed.shape
    s, w2 = p_packed.shape
    assert w == w2, (w, w2)
    dim = 32 * w if dim is None else dim
    bm, bn, bw = min(bm, b), min(bn, s), min(bw, w)
    assert b % bm == 0 and s % bn == 0 and w % bw == 0, (
        f"shapes ({b},{s},{w}) must tile by ({bm},{bn},{bw}); pad upstream")
    grid = (b // bm, s // bn, w // bw)

    return pl.pallas_call(
        functools.partial(_kernel, dim=dim),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bw), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(interpret),
    )(q_packed, p_packed)
