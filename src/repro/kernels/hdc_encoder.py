"""Pallas TPU kernel for the Demeter N-gram encoder (bind + bundle).

TPU port of Acc-Demeter's encoder unit (paper §5.3).  Design notes:

* **No gathers.** TPU Pallas has no efficient dynamic gather; the genome
  alphabet has only 4 symbols, so the IM row lookup ``B[c]`` becomes 4
  predicated selects — the VPU equivalent of the paper's one-cycle
  row-major IM read.
* **No runtime permutation.** The rolled item memories ``rho^j(IM)`` for
  j < N are precomputed host-side (N*4*W words, KBs) so every word-block
  of the HD space is fully independent -> embarrassingly parallel grid
  over (batch, word-block), zero cross-block traffic.  This is the TPU
  realization of the "free shift" flip-flop chain.
* **Token windows by lane rotation.** A read's tokens lie along lanes.
  The gram loop walks them in 128-position chunks: each chunk loads a
  128-aligned ``(bb, 256)`` slice (an aligned dynamic lane offset) and
  every gram rotates it so tokens ``i .. i+n-1`` sit in lanes
  ``0 .. n-1``.  An unaligned dynamic lane slice does not lower on TPU.
* **Counters layout** ``(32, bb, bw)``: one ``(bb, bw)`` tile per bit
  position, so unpacking, counting and the majority are plain 2-D
  per-tile ops.
* Bundling majority (with tie-break vector) and re-packing happen in the
  same kernel — one HBM write of W words per read, nothing else leaves.

Grid: (B/bb, W/bw); the whole gram loop for a read runs inside one grid
cell, mirroring the paper's streaming encoder.  :func:`encode_tile` is
shared with the fused encode->search kernel (``fused_profile``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import interpret_default

WORD_BITS = 32
LANES = 128


def padded_length(length: int) -> int:
    """Token row width the kernels read for ``length``-token sequences.

    Rounded up to the lane tile plus one spare tile, so the 256-lane
    chunk read for the last gram chunk stays in bounds.
    """
    return -(-length // LANES) * LANES + LANES


def pad_tokens(tokens: jax.Array) -> jax.Array:
    """Zero-pad ``(B, L)`` tokens to ``(B, padded_length(L))``.

    The pad is inert: a gram that reads it starts at or after the last
    valid gram and is masked out of the counters.
    """
    length = tokens.shape[1]
    return jnp.pad(tokens, ((0, 0), (0, padded_length(length) - length)))


def encode_tile(tokens_ref, m, im_rows, tie_row, counts_ref, *, n: int,
                alphabet: int, g: int) -> jax.Array:
    """Encode one ``(bb, bw)`` word tile of a batch tile, in VMEM.

    Args:
      tokens_ref: ``(bb, padded_length(L))`` int32 token ref.
      m: ``(bb, 1)`` int32 count of valid grams per row.
      im_rows: ``(n * alphabet, bw)`` uint32 rolled-IM rows of this tile.
      tie_row: ``(1, bw)`` uint32 tie-break words of this tile.
      counts_ref: ``(32, bb, bw)`` int32 scratch for the bit counters.
      g: int32 scalar, the grams the loop runs: at most the row width's
        ``L - n + 1`` and at least every row's ``m`` — the grams past
        every row's ``m`` add nothing, so any such bound gives the same
        words.

    Returns:
      ``(bb, bw)`` uint32 packed majority-bundled HD words.
    """
    _, bb, bw = counts_ref.shape
    if n > LANES:
        raise ValueError(f"n-gram size {n} exceeds one {LANES}-lane tile")
    counts_ref[...] = jnp.zeros_like(counts_ref)

    def add_gram(win, pos):
        # win: (bb, 2*LANES); lanes 0..n-1 hold tokens pos..pos+n-1.
        gram = jnp.zeros((bb, bw), jnp.uint32)
        for jj in range(n):                      # bind: XOR of rho^j(B[c])
            tok = win[:, jj:jj + 1]              # (bb, 1)
            for a in range(alphabet):            # gather-free IM lookup
                k = jj * alphabet + a
                gram = jnp.bitwise_xor(
                    gram, jnp.where(tok == a, im_rows[k:k + 1, :],
                                    jnp.uint32(0)))
        # Rows past their last valid gram add nothing to the counters.
        gram = jnp.where(pos < m, gram, jnp.uint32(0))
        for b in range(WORD_BITS):
            counts_ref[b] += ((gram >> b) & jnp.uint32(1)).astype(jnp.int32)

    def chunk(c, count):
        base = pl.multiple_of(c * LANES, LANES)
        toks = tokens_ref[:, pl.ds(base, 2 * LANES)]   # (bb, 256)

        def body(r, carry):
            win = pltpu.roll(toks, (2 * LANES - r) % (2 * LANES), 1)
            add_gram(win, base + r)
            return carry

        jax.lax.fori_loop(0, count, body, 0)

    def full_chunk(c, carry):
        chunk(c, LANES)
        return carry

    full, rem = divmod(g, LANES)
    jax.lax.fori_loop(0, full, full_chunk, 0)

    @pl.when(rem > 0)           # no empty chunk: its token read could
    def _rem():                 # end past the padded row
        chunk(full, rem)

    # Bundle: majority with tie-break (paper's thresholded counters).
    packed = jnp.zeros((bb, bw), jnp.uint32)
    for b in range(WORD_BITS):
        twice = 2 * counts_ref[b]
        tie_bit = (tie_row >> b) & jnp.uint32(1)
        bit = jnp.where(twice == m, tie_bit,
                        (twice > m).astype(jnp.uint32))
        packed = packed | (bit << b)
    return packed


def _kernel(tokens_ref, len_ref, im_ref, tie_ref, o_ref, counts_ref,
            *, n: int, alphabet: int, g: int):
    m = jnp.maximum(len_ref[...] - (n - 1), 0)   # (bb, 1) valid grams
    o_ref[...] = encode_tile(tokens_ref, m, im_ref[...], tie_ref[...],
                             counts_ref, n=n, alphabet=alphabet,
                             g=jnp.int32(g))


@functools.partial(jax.jit, static_argnames=("n", "alphabet", "bb", "bw",
                                             "interpret"))
def hdc_encode(tokens: jax.Array, lengths: jax.Array, im_rolled: jax.Array,
               tie: jax.Array, *, n: int, alphabet: int = 4, bb: int = 8,
               bw: int = 128, interpret: bool | None = None) -> jax.Array:
    """Encode a batch of symbol sequences into packed query HD vectors.

    Args:
      tokens: ``(B, L)`` int32 symbol ids in [0, alphabet).
      lengths: ``(B, 1)`` int32 true lengths.
      im_rolled: ``(N, alphabet, W)`` uint32 — ``item_memory.rolled``.
      tie: ``(1, W)`` uint32 tie-break vector.

    Returns:
      ``(B, W)`` uint32 packed HD vectors (majority-bundled n-grams).
    """
    b, length = tokens.shape
    n_im, a_im, w = im_rolled.shape
    assert n_im == n and a_im == alphabet
    g = max(length - n + 1, 0)
    bb, bw = min(bb, b), min(bw, w)
    assert b % bb == 0 and w % bw == 0, (
        f"(B={b}, W={w}) must tile by (bb={bb}, bw={bw}); pad upstream")
    toks = pad_tokens(tokens)
    grid = (b // bb, w // bw)

    return pl.pallas_call(
        functools.partial(_kernel, n=n, alphabet=alphabet, g=g),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, toks.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((n * alphabet, bw), lambda i, j: (0, j)),
            pl.BlockSpec((1, bw), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, w), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((WORD_BITS, bb, bw), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_default(interpret),
    )(toks, lengths, im_rolled.reshape(n * alphabet, w), tie)
