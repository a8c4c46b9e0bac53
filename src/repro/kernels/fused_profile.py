"""Fused encode->search Pallas megakernel (the whole query hot path).

Acc-Demeter's headline efficiency comes from *never materializing* the
encoded read hypervectors off-chip: the encoder unit streams each read's
n-gram tokens and the finished HD vector flows straight into the AM
crossbar (paper §5).  The software pipeline so far ran the two kernels
separately — ``hdc_encoder`` writes the full ``(B, W)`` encoded matrix to
HBM, ``hamming_am``/``am_matmul`` reads it back.  This kernel is the TPU
realization of the paper's dataflow: one grid cell encodes a
``(bb, bw)`` word tile of the query batch *in VMEM* and immediately folds
it into the Hamming accumulator against every prototype's matching word
tile, so the encoded queries live only as a VMEM temporary.

With the encoded queries VMEM-resident, the *prototype stream* is the
only remaining HBM traffic of the search, and its dataflow is what this
kernel optimizes (the software analogue of Acc-Demeter keeping the AM
inside the memristor array):

* **In-grid prototype chunking.**  The grid is three-axis,
  ``(S/bs, B/bb, W/bw)`` with the prototype-chunk axis *outermost* — one
  ``pallas_call`` covers the whole ``(B, S)`` output instead of one call
  (and one retrace) per host-side ``bs`` chunk.
* **Chunk-slab amortization.**  Each ``(bs, W)`` prototype slab is
  delivered as a single block whose index depends only on the chunk id
  ``k``, so the pipeline fetches it ONCE per chunk and every batch tile
  ``i`` and word tile ``j`` under that chunk reuses the VMEM-resident
  copy.  Prototype HBM bytes per call drop from
  ``(B/bb) * S * W * 4`` to ``S * W * 4`` — amortized ``B/bb``-fold.
* **Double-buffered prototype DMA** (``double_buffer=True``; the default
  whenever the kernel runs natively).  The prototype array stays in HBM
  (``memory_space=ANY``) and the kernel copies slab ``k+1`` into the
  spare half of a two-slot VMEM scratch *at the first cell of chunk
  ``k``*, overlapping the fetch with the whole slab's worth of
  XOR+popcount work.  The automatic pipeline only prefetches one grid
  step ahead — it would start fetching slab ``k+1`` during the *last*
  cell of chunk ``k``, too late to hide a multi-megabyte copy.  Interpret
  mode takes the automatic pipeline unless asked (same math, same bytes;
  both paths are bit-exact and parity-tested in ``tests/test_fused.py``,
  and both compile for v5e in ``tests/test_tpu_compile.py``).

* **Encoded-batch cache** (calls of two chunks or more).  The grid
  visits every ``(i, j)`` tile once per chunk, but a tile's encoding does
  not depend on the chunk.  So the cells of chunk 0 store each encoded
  ``(bb, bw)`` tile in a ``(B/bb, W/bw, bb, bw)`` VMEM scratch and every
  later chunk reads it back: each read is encoded once per call, whatever
  the chunk count (the encode is ~90% of a chunk's work at AFS20 widths).
  The grid runs in order on the one TensorCore (all axes "arbitrary"),
  so the scratch outlives the cells.  A one-chunk call has nothing to
  reuse and allocates no cache.

* **Length-bounded batch tiles.**  A tile's work follows its reads'
  lengths, not the padded shape.  :func:`tile_trips` gives each ``bb``-row
  tile its longest row's gram count, ``max(0, max(len) - n + 1)``, which
  reaches the kernel as a scalar-prefetch operand and bounds its gram
  loop in place of the static ``L - n + 1`` (grams past it are masked on
  every row of the tile, so the words are the same).  A tile with none
  to run — every row padding or shorter than ``n`` — encodes every row
  to the tie-break vector, so its cells skip the encode, the cache store
  and the search, and its last word tile writes :func:`tie_agreement`'s
  ``(1, S)`` row, computed once per call, to each row.  A tile of
  full-length rows runs today's loop count.

Per grid cell ``(k, i, j)`` of a batch tile with grams to run:

  1. **Encode** (chunk 0, or every cell of a one-chunk call) the
     ``(bb, bw)`` word tile with the encoder kernel's own
     :func:`~repro.kernels.hdc_encoder.encode_tile`: lane-rotated token
     windows, gather-free IM lookup (4 predicated selects), per-bit
     bundling counters in ``(32, bb, bw)`` scratch, majority threshold
     with the tie-break vector, re-pack to ``(bb, bw)`` uint32 — all
     VMEM.  Chunks after the first load the tile from the cache instead.
  2. **Search**: XOR the tile against word tile ``j`` of prototype slab
     ``k``, 128 prototype rows at a time, and accumulate popcounts into
     the persistent ``(bb, bs)`` Hamming scratch.
  3. On the last word tile, flush ``agreement = dim - hamming`` into the
     ``(i, k)`` output block — the only HBM write of the whole query
     path besides the final scores.  (A tile with none flushes the tie
     row.)

The word axis is innermost ("arbitrary": it carries the accumulator);
the IM, tie, and prototype arrays arrive word-split with the word tile
on a *leading* dim (``(W/bw, ..., bw)``), so the per-cell word tile is a
leading-dim dynamic index (lane-dim dynamic slices would need
128-alignment).  Bit-exact with ``reference`` encode + agreement by
construction — the encode is the encoder kernel's own code, and
``dim - popcount(xor)`` is the same exact integer identity both AM
kernels use.

The tile buffers are dominated by the two ``bs*W*4`` prototype slabs;
:func:`repro.kernels.ops.vmem_bytes` counts every buffer, and
:func:`repro.kernels.ops.fused_tile_plan` bounds ``bs`` so they fit
``ops.VMEM_BUDGET`` (a v5e core's default scoped VMEM is 16 MiB).  The
cache adds ``B*W*4`` bytes on top (20 MiB for 4096 reads at D=40960);
the plan raises the call's scoped-VMEM limit to hold it
(``vmem_limit_bytes``) and splits batches too large for the chip's VMEM
into several calls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hdc_encoder import (LANES, WORD_BITS, encode_tile,
                                       pad_tokens)
from repro.kernels.interpret import interpret_default


def tile_trips(lengths, *, bb: int, n: int, g: int):
    """Grams the encode runs for each ``bb``-row batch tile.

    A tile's longest row sets it: ``max(0, max(len) - n + 1)``, at most
    ``g``.  ``lengths`` is ``(B,)`` or ``(B, 1)`` with ``B % bb == 0``,
    a numpy or a jax array (the kernel computes it on the device, the
    session's counter on the host); returns ``(B / bb,)``.
    """
    return (lengths.reshape(-1, bb).max(axis=1) - (n - 1)).clip(0, g)


def tie_agreement(tie: jax.Array, p_packed: jax.Array, dim: int
                  ) -> jax.Array:
    """``(1, S)`` agreement of the tie-break vector with every prototype.

    A row with no valid gram encodes to the tie-break vector (every
    counter ties), so this is the score row of every row of a batch tile
    whose trip count is 0.
    """
    x = jnp.bitwise_xor(tie, p_packed)
    return dim - jnp.bitwise_count(x).astype(jnp.int32).sum(axis=1)[None, :]


def _query_tile(tokens_ref, len_ref, im_ref, tie_ref, counts_ref, q_cache,
                k, i, j, trip, *, n: int, alphabet: int) -> jax.Array:
    """Encoded word tile ``j`` of batch tile ``i``: ``(bb, bw)`` uint32.

    ``im_ref``/``tie_ref`` are the word-split ``(W/bw, n*alphabet, bw)``
    / ``(W/bw, 1, bw)`` views; ``j`` picks the tile on the leading dim.
    The gram loop runs ``trip`` grams, the tile's longest row's.
    With no cache (one chunk) every cell encodes.  With one, the cells of
    chunk ``k == 0`` encode and store the tile at ``[i, j]`` of the
    ``(B/bb, W/bw, bb, bw)`` cache, and every cell reads it from there.
    """
    def encode():
        m = jnp.maximum(len_ref[...] - (n - 1), 0)   # (bb, 1) valid grams
        return encode_tile(tokens_ref, m, im_ref[j], tie_ref[j], counts_ref,
                           n=n, alphabet=alphabet, g=trip)

    if not q_cache:
        return encode()
    (q_ref,) = q_cache

    @pl.when(k == 0)
    def _fill():
        q_ref[i, j] = encode()

    return q_ref[i, j]


def _search_tile(acc_ref, q, p_ref, j):
    """Fold one encoded tile into the Hamming accumulator.

    ``p_ref`` is the ``(W/bw, bs, bw)`` prototype slab.  Its rows are
    scored 128 at a time, which bounds the ``(bb, 128, bw)`` XOR
    temporary whatever ``bs`` is.
    """
    bs = p_ref.shape[1]
    sub = LANES if bs % LANES == 0 else bs

    def rows(r, carry):
        off = pl.multiple_of(r * sub, sub)
        x = jnp.bitwise_xor(q[:, None, :], p_ref[j, pl.ds(off, sub), :][None])
        acc_ref[:, pl.ds(off, sub)] += (
            jnp.bitwise_count(x).astype(jnp.int32).sum(axis=-1))
        return carry

    jax.lax.fori_loop(0, bs // sub, rows, 0)


def _kernel(trips_ref, tokens_ref, len_ref, im_ref, tie_ref, p_ref, ta_ref,
            o_ref, counts_ref, acc_ref, *q_cache, n: int, alphabet: int,
            dim: int):
    """Automatic-pipeline variant: the ``(W/bw, bs, bw)`` prototype slab
    is a BlockSpec block indexed by the chunk id only, so the pipeline
    fetches it once per chunk and double-buffers the fetch across chunks.
    ``q_cache`` is the encoded-batch scratch, or empty for one chunk.

    A batch tile with grams to run (``trips_ref[i] > 0``) encodes and
    searches; one without (every row padding or under ``n`` bases) skips
    both and, on the last word tile, writes the tie-break vector's score
    row ``ta_ref`` to each row.
    """
    k, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    trip = trips_ref[i]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(trip > 0)
    def _live():
        q = _query_tile(tokens_ref, len_ref, im_ref, tie_ref, counts_ref,
                        q_cache, k, i, j, trip, n=n, alphabet=alphabet)
        _search_tile(acc_ref, q, p_ref, j)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        @pl.when(trip > 0)
        def _scored():
            o_ref[...] = dim - acc_ref[...]

        @pl.when(trip == 0)
        def _tied():
            o_ref[...] = jnp.broadcast_to(ta_ref[...], o_ref.shape)


def _kernel_dma(trips_ref, tokens_ref, len_ref, im_ref, tie_ref, p_hbm,
                ta_ref, o_ref, counts_ref, acc_ref, p_buf, sem, *q_cache,
                n: int, alphabet: int, dim: int):
    """Manual double-buffer variant: prototypes stay in HBM and slab
    ``k+1``'s async copy is issued at the FIRST cell of chunk ``k`` —
    the whole slab's compute window hides the next fetch.  The rotation
    runs whether or not batch tile 0 has grams to run; the cell's work is
    :func:`_kernel`'s."""
    k, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def slab_dma(slot, chunk):
        return pltpu.make_async_copy(p_hbm.at[chunk], p_buf.at[slot],
                                     sem.at[slot])

    @pl.when((i == 0) & (j == 0))
    def _rotate():
        @pl.when(k == 0)
        def _warmup():
            slab_dma(0, 0).start()

        slab_dma(k % 2, k).wait()

        @pl.when(k + 1 < pl.num_programs(0))
        def _prefetch():
            slab_dma((k + 1) % 2, k + 1).start()

    _kernel(trips_ref, tokens_ref, len_ref, im_ref, tie_ref,
            p_buf.at[k % 2], ta_ref, o_ref, counts_ref, acc_ref, *q_cache,
            n=n, alphabet=alphabet, dim=dim)


@functools.partial(jax.jit, static_argnames=("n", "alphabet", "dim", "bb",
                                             "bw", "bs", "interpret",
                                             "double_buffer",
                                             "vmem_limit_bytes"))
def fused_profile(tokens: jax.Array, lengths: jax.Array,
                  im_rolled: jax.Array, tie: jax.Array,
                  p_packed: jax.Array, *, n: int, dim: int,
                  alphabet: int = 4, bb: int = 8, bw: int = 128,
                  bs: int | None = None, interpret: bool | None = None,
                  double_buffer: bool | None = None,
                  vmem_limit_bytes: int | None = None) -> jax.Array:
    """Agreement of every read against every prototype, single kernel.

    Args:
      tokens: ``(B, L)`` int32 symbol ids in [0, alphabet).
      lengths: ``(B, 1)`` int32 true lengths.
      im_rolled: ``(N, alphabet, W)`` uint32 — ``item_memory.rolled``.
      tie: ``(1, W)`` uint32 tie-break vector.
      p_packed: ``(S, W)`` uint32 packed prototypes (zero-padded words
        and rows are inert: pad words XOR to zero against the pad words
        of the encoded queries, which are also zero).
      dim: the LOGICAL HD dimension D (<= 32*W).
      bs: prototype rows per chunk (the third grid axis); ``None`` means
        one chunk covering all of S.  Must divide S; pad upstream
        (``ops.fused_agreement`` pads once for the whole call).
      double_buffer: manually double-buffer the prototype-slab DMA
        (prototypes stay in HBM, two-slot VMEM scratch).  ``None`` picks
        it whenever the kernel runs natively; interpret mode takes the
        automatic pipeline unless asked.  Both variants are bit-exact.
      vmem_limit_bytes: the call's scoped-VMEM limit; ``None`` keeps the
        compiler's default (16 MiB on v5e).  A call of two chunks or more
        holds a ``B*W*4``-byte encoded-batch cache besides its tile
        buffers, and ``ops.fused_tile_plan`` sizes the limit for both.

    Each batch tile's gram loop runs its reads' own trip count
    (:func:`tile_trips`), and a tile with none takes the tie-break
    vector's score row (:func:`tie_agreement`); both are worked out here,
    on the device, from ``lengths``, ``tie`` and ``p_packed``.

    Returns:
      ``(B, S)`` int32 agreement counts in [0, dim] — bit-identical to
      ``am_agreement(hdc_encode(...), p_packed)``.
    """
    b, length = tokens.shape
    n_im, a_im, w = im_rolled.shape
    s, w2 = p_packed.shape
    assert n_im == n and a_im == alphabet and w == w2, (n_im, a_im, w, w2)
    g = max(length - n + 1, 0)
    bb, bw = min(bb, b), min(bw, w)
    bs = s if bs is None else min(bs, s)
    assert b % bb == 0 and w % bw == 0 and s % bs == 0, (
        f"(B={b}, S={s}, W={w}) must tile by (bb={bb}, bs={bs}, bw={bw}); "
        f"pad upstream")
    interpret = interpret_default(interpret)
    if double_buffer is None:
        double_buffer = not interpret
    wt, nk = w // bw, s // bs
    grid = (nk, b // bb, wt)
    trips = tile_trips(lengths, bb=bb, n=n, g=g).astype(jnp.int32)
    tie_row = tie_agreement(tie, p_packed, dim)

    # Word-split views: the per-cell word tile becomes a leading-dim
    # dynamic index instead of a lane-dim slice, and the IM / tie /
    # prototype block indices stop depending on j — the IM and tie are
    # fetched once per call, the prototype slab once per chunk.
    toks = pad_tokens(tokens)
    im3 = im_rolled.reshape(n * alphabet, wt, bw).transpose(1, 0, 2)
    tie3 = tie.reshape(wt, 1, bw)
    p4 = p_packed.reshape(nk, bs, wt, bw).transpose(0, 2, 1, 3)

    # Index maps take the scalar-prefetched trip counts last, unused.
    common_specs = [
        pl.BlockSpec((bb, toks.shape[1]), lambda k, i, j, t: (i, 0)),
        pl.BlockSpec((bb, 1), lambda k, i, j, t: (i, 0)),
        pl.BlockSpec((wt, n * alphabet, bw), lambda k, i, j, t: (0, 0, 0)),
        pl.BlockSpec((wt, 1, bw), lambda k, i, j, t: (0, 0, 0)),
    ]
    tie_row_spec = pl.BlockSpec((1, bs), lambda k, i, j, t: (0, k))
    scratch = [pltpu.VMEM((WORD_BITS, bb, bw), jnp.int32),
               pltpu.VMEM((bb, bs), jnp.int32)]
    if double_buffer:
        kernel = _kernel_dma
        p_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = scratch + [pltpu.VMEM((2, wt, bs, bw), jnp.uint32),
                             pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = _kernel
        p_spec = pl.BlockSpec((None, wt, bs, bw),
                              lambda k, i, j, t: (k, 0, 0, 0))
    if nk > 1:  # encoded-batch cache: chunk 0 encodes, later chunks reuse
        scratch = scratch + [pltpu.VMEM((b // bb, wt, bb, bw), jnp.uint32)]

    return pl.pallas_call(
        functools.partial(kernel, n=n, alphabet=alphabet, dim=dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=common_specs + [p_spec, tie_row_spec],
            out_specs=pl.BlockSpec((bb, bs), lambda k, i, j, t: (i, k)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, s), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(trips, toks, lengths, im3, tie3, p4, tie_row)
