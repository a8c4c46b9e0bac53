"""Public jit'd wrappers around the Pallas kernels.

These handle padding to block multiples, the packed<->+-1 conversions, and
formulation selection, so callers (core.profiler, launch drivers) can stay
shape-agnostic.  On CPU the kernels execute in interpret mode; the wrappers
are the single switch point between the MXU and VPU formulations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops, item_memory
from repro.core.hd_space import HDSpace
from repro.kernels import am_matmul as _am_matmul
from repro.kernels import fused_profile as _fused_profile
from repro.kernels import hamming_am as _hamming_am
from repro.kernels import hdc_encoder as _hdc_encoder


# Re-exported from its dependency-free home so standalone kernel tools
# (`python -m repro.kernels.autotune`) can load without pulling in the
# whole core->pipeline import graph.
pad_to_multiple = bitops.pad_to_multiple

_pad_to = pad_to_multiple


def to_pm1(packed: jax.Array) -> jax.Array:
    """Packed bits -> {-1,+1} bf16 (the MXU encoding of the AM crossbar)."""
    bits = bitops.unpack_bits(packed)
    return (2.0 * bits.astype(jnp.bfloat16) - 1.0)


@functools.partial(jax.jit, static_argnames=("dim", "formulation"))
def am_agreement(queries: jax.Array, prototypes: jax.Array, dim: int,
                 formulation: str = "matmul") -> jax.Array:
    """Agreement (matching bits) of every query vs every prototype.

    Args:
      queries: ``(B, W)`` uint32 packed.
      prototypes: ``(S, W)`` uint32 packed.
      formulation: "matmul" (MXU, default) or "packed" (VPU popcount).

    Returns:
      ``(B, S)`` int32 agreement in [0, dim].
    """
    b, s = queries.shape[0], prototypes.shape[0]
    if formulation == "matmul":
        bk = min(512, dim)
        q = _pad_to(_pad_to(to_pm1(queries), 0, 128), 1, bk)
        p = _pad_to(_pad_to(to_pm1(prototypes), 0, 128), 1, bk)
        out = _am_matmul.am_matmul(q, p, dim=dim, bk=bk)
    elif formulation == "packed":
        bw = min(256, dim // 32)
        q = _pad_to(_pad_to(queries, 0, 8), 1, bw)
        p = _pad_to(_pad_to(prototypes, 0, 128), 1, bw)
        out = _hamming_am.hamming_am(q, p, dim=dim, bw=bw)
    else:
        raise ValueError(f"unknown formulation {formulation!r}")
    return out[:b, :s]


@functools.partial(jax.jit, static_argnames=("space",))
def hdc_encode(tokens: jax.Array, lengths: jax.Array, im: jax.Array,
               tie: jax.Array, space: HDSpace) -> jax.Array:
    """Kernel-backed Demeter read conversion (step 3).

    Same contract as :func:`repro.core.encoder.encode`.
    """
    b = tokens.shape[0]
    im_rolled = item_memory.rolled(im, space.ngram)
    toks = _pad_to(tokens.astype(jnp.int32), 0, 8)
    lens = _pad_to(lengths.astype(jnp.int32)[:, None], 0, 8)
    bw = min(128, space.num_words)
    out = _hdc_encoder.hdc_encode(
        toks, lens, im_rolled, tie[None, :], n=space.ngram,
        alphabet=space.alphabet_size, bw=bw)
    return out[:b]


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


#: The compiler's default scoped-VMEM limit of a v5e core.
SCOPED_VMEM_DEFAULT = 16 * 2 ** 20

#: VMEM bytes a fused tile plan's buffers may hold, under the default
#: scoped limit.
VMEM_BUDGET = 12 * 2 ** 20

#: Room left above what a plan counts, for the compiler's own staging
#: and temporaries; also kept below the chip's capacity.
VMEM_HEADROOM = SCOPED_VMEM_DEFAULT - VMEM_BUDGET

#: A v5e TensorCore's VMEM (128 MiB).  Off the chip — interpret mode,
#: and compiles for a described v5e — plans assume it, so they split a
#: batch exactly as the chip would.
V5E_VMEM_CAPACITY = 128 * 2 ** 20


def vmem_capacity() -> int:
    """VMEM bytes of one TensorCore of the chip the kernels run on."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.get_tpu_info().vmem_capacity_bytes
    return V5E_VMEM_CAPACITY


def vmem_ceiling() -> int:
    """Most VMEM a fused call may count: :func:`vmem_capacity` less
    :data:`VMEM_HEADROOM` twice, once inside the raised scoped limit and
    once between that limit and the capacity."""
    return vmem_capacity() - 2 * VMEM_HEADROOM


def vmem_bytes(plan: dict[str, int], *, read_len: int, n: int,
               alphabet: int = 4) -> int:
    """Estimate the fused kernel's peak VMEM residency for a tile plan.

    Mirrors the buffers ``kernels/fused_profile`` allocates: pipelined
    input/output blocks (two of each), the word-split IM/tie full
    blocks, the 2-slot prototype slab (the automatic pipeline also keeps
    two in flight, so the estimate is path-independent), the counter and
    accumulator scratch, and the XOR temporaries of one 128-row block.
    Linear in ``bs``, which :func:`fused_tile_plan` uses to bound it.
    """
    bb, bw, bs = plan["bb"], plan["bw"], plan["bs"]
    w_pad = plan["w_pad"]
    total = 2 * bb * _hdc_encoder.padded_length(read_len) * 4  # tokens
    total += 2 * bb * 128 * 4             # lengths tile (lane-padded)
    total += 2 * n * alphabet * w_pad * 4  # rolled item memory
    total += 2 * 8 * w_pad * 4            # tie-break rows (sublane-padded)
    total += 2 * bs * w_pad * 4           # prototype slab, two slots
    total += 32 * bb * bw * 4             # bit-counter scratch
    total += bb * bs * 4                  # agreement accumulator scratch
    total += 2 * bb * bs * 4              # output tile
    total += 3 * bb * 128 * bw * 4        # XOR / popcount temporaries
    return total


def fused_tile_plan(b: int, s: int, w: int, *, read_len: int, n: int,
                    alphabet: int = 4, bb: int = 8, bw: int = 128,
                    bs: int = 4096) -> dict[str, int | None]:
    """The padded shapes + grid :func:`fused_agreement` will actually run.

    One place owns the clamp/pad arithmetic so the kernel launch and the
    analytic traffic accounting (``benchmarks/smoke.py`` /
    ``benchmarks/memory.py`` / ``repro.kernels.autotune``) can never
    drift apart.  ``bs`` is first bounded so the plan's
    :func:`vmem_bytes` fits :data:`VMEM_BUDGET` (at D=40960 the two
    prototype slabs alone take 10 KiB per row), then re-balanced so S
    pads ONCE to ``n_chunks * bs`` with less than one chunk of waste.

    A call of two chunks or more also holds the kernel's encoded-batch
    cache, ``b_call * w_pad * 4`` bytes (``cache_bytes``; 0 with one
    chunk).  Where the tile buffers plus the cache exceed
    :data:`VMEM_BUDGET`, ``vmem_limit_bytes`` raises the call's scoped
    VMEM to hold them with :data:`VMEM_HEADROOM` to spare, so the
    buffers and cache stay within :func:`vmem_ceiling`.  A batch whose
    cache cannot fit is split into ``n_calls`` kernel calls of ``b_call`` rows,
    each streaming the prototypes once.

    Returns a dict with the effective ``bb``/``bw``/``bs``, the padded
    ``b_pad``/``w_pad``/``s_pad``, ``n_chunks``, ``b_call``/``n_calls``,
    ``cache_bytes``, ``vmem_limit_bytes`` (``None``: the compiler's
    default), ``encodes`` (encode passes per read: 1 with the cache, as
    with one chunk) and ``proto_bytes_per_call`` — the prototype-stream
    HBM bytes one fused call moves (each ``(bs, W)`` slab is fetched once
    per chunk and reused across every batch tile; see
    ``kernels/fused_profile``).
    """
    bb = min(bb, 8 * ((b + 7) // 8))
    b_pad = _ceil_to(b, max(bb, 8))
    bw = min(bw, w)
    w_pad = _ceil_to(w, bw)
    shape = {"bb": bb, "bw": bw, "w_pad": w_pad}
    cost = dict(read_len=read_len, n=n, alphabet=alphabet)
    fixed = vmem_bytes({**shape, "bs": 0}, **cost)
    per_row = vmem_bytes({**shape, "bs": 1}, **cost) - fixed
    bs_fit = (VMEM_BUDGET - fixed) // per_row // 128 * 128
    # Re-balance the requested chunk rows over ceil(S/bs) chunks, rounded
    # to the 128-row output lane tile, then pad S to the chunk grid: the
    # total pad is < one chunk (vs up to 127 rows per chunk before).
    bs = max(128, min(bs, bs_fit, _ceil_to(s, 128)))
    n_chunks = -(-s // bs)
    bs = _ceil_to(-(-s // n_chunks), 128)
    n_chunks = -(-s // bs)
    s_pad = n_chunks * bs
    plan = {**shape, "bs": bs, "b_pad": b_pad, "s_pad": s_pad,
            "n_chunks": n_chunks, "b_call": b_pad, "n_calls": 1,
            "cache_bytes": 0, "vmem_limit_bytes": None,
            "encodes": 1, "proto_bytes_per_call": s_pad * w_pad * 4}
    if n_chunks == 1:
        return plan
    # The encoded-batch cache: split the batch into calls whose cache
    # fits under the chip's VMEM beside the tile buffers.
    tiles = vmem_bytes(plan, **cost)
    align = max(bb, 8)
    fit = (vmem_ceiling() - tiles) // (w_pad * 4)
    n_calls = -(-b_pad // max(align, fit // align * align))
    b_call = _ceil_to(-(-b_pad // n_calls), align)
    n_calls = -(-b_pad // b_call)
    cache_bytes = b_call * w_pad * 4
    limit = None
    if tiles + cache_bytes > VMEM_BUDGET:
        limit = _ceil_to(tiles + cache_bytes + VMEM_HEADROOM, 2 ** 20)
    return {**plan, "b_pad": n_calls * b_call, "b_call": b_call,
            "n_calls": n_calls, "cache_bytes": cache_bytes,
            "vmem_limit_bytes": limit}


def gram_steps(lengths: np.ndarray, plan: dict[str, int], *, read_len: int,
               n: int) -> dict[str, int]:
    """Row-gram iterations of a fused call's encode, counted on the host.

    ``gram_steps`` is what the kernel runs for these ``(B,)`` lengths:
    ``bb`` rows times each batch tile's trip count
    (:func:`repro.kernels.fused_profile.tile_trips`), summed over the
    ``plan``'s padded batch.  ``gram_steps_shape`` is what it would run
    with every tile at the static ``L - n + 1``: ``b_pad`` rows of it.
    """
    g = max(read_len - n + 1, 0)
    lens = np.zeros(plan["b_pad"], np.int64)
    lens[:np.size(lengths)] = np.ravel(lengths)
    trips = _fused_profile.tile_trips(lens, bb=plan["bb"], n=n, g=g)
    return {"gram_steps": int(plan["bb"] * trips.sum()),
            "gram_steps_shape": plan["b_pad"] * g}


@functools.partial(jax.jit, static_argnames=("space", "bb", "bw", "bs",
                                             "double_buffer"))
def fused_agreement(tokens: jax.Array, lengths: jax.Array, im: jax.Array,
                    tie: jax.Array, prototypes: jax.Array, space: HDSpace,
                    *, bb: int = 8, bw: int = 128, bs: int = 4096,
                    double_buffer: bool | None = None) -> jax.Array:
    """Fused steps 3+4: read tokens -> agreement, no encoded HBM matrix.

    ONE :func:`repro.kernels.fused_profile.fused_profile` call covers the
    whole ``(B, S)`` output (a batch too large for the chip's VMEM takes
    several, below): the ``bs`` prototype chunking is the
    kernel's outermost grid axis (no per-chunk retrace, no host concat),
    each ``(bs, W)`` prototype slab is fetched once per chunk and reused
    across every batch tile, and on TPU the next slab's DMA is manually
    double-buffered behind the current slab's compute.  The encoded
    query tiles live only in VMEM, so the ``(B, W)`` packed matrix (and
    the ±1 bf16 expansion of the matmul path) never touches HBM; with two
    chunks or more the kernel keeps them there as a ``B*W*4``-byte cache,
    so each read is encoded once per call, under the scoped-VMEM limit
    the plan asks for (:func:`fused_tile_plan`'s ``vmem_limit_bytes``).
    A batch too large for that cache on the chip is split into several
    calls of ``b_call`` rows (:func:`fused_tile_plan`).
    Each ``bb``-row batch tile runs only the grams its longest read
    needs, and a tile of padding rows (or reads under ``n`` bases) takes
    a precomputed tie-break score row without encoding or searching:
    the kernel works both out from ``lengths`` on the device, and
    :func:`gram_steps` counts the same grams on the host.
    Bit-identical to
    ``am_agreement(hdc_encode(tokens, lengths, im, tie, space), p, dim)``.

    Args:
      tokens: ``(B, L)`` int32 symbol ids; lengths: ``(B,)`` true lengths.
      prototypes: ``(S, W)`` uint32 packed prototypes.
      bb / bw: batch / word-tile sizes (VMEM shape knobs).
      bs: prototype rows per chunk — bounds the ``(bs, W)`` slab and the
        ``(bb, bs)`` accumulator resident in VMEM.  Re-balanced and
        padded once via :func:`fused_tile_plan`.
      double_buffer: forwarded to the kernel (``None`` = auto: manual
        DMA double-buffering on TPU, automatic pipeline in interpret
        mode).

    Returns:
      ``(B, S)`` int32 agreement in [0, space.dim].
    """
    b, s = tokens.shape[0], prototypes.shape[0]
    plan = fused_tile_plan(b, s, space.num_words, read_len=tokens.shape[1],
                           n=space.ngram, alphabet=space.alphabet_size,
                           bb=bb, bw=bw, bs=bs)
    im_rolled = item_memory.rolled(im, space.ngram)
    toks = _pad_to(tokens.astype(jnp.int32), 0, plan["b_call"])
    lens = _pad_to(lengths.astype(jnp.int32)[:, None], 0, plan["b_call"])
    # Pad the word axis to the tile and the prototype axis to the chunk
    # grid: zero IM/tie/prototype words encode (and score) as zeros, so
    # padding is inert to the exact agreement; pad rows are sliced off.
    im_rolled = _pad_to(im_rolled, 2, plan["bw"])
    tie_row = _pad_to(tie[None, :], 1, plan["bw"])
    protos = _pad_to(_pad_to(jnp.asarray(prototypes), 1, plan["bw"]),
                     0, plan["bs"])
    rows = plan["b_call"]
    out = jnp.concatenate([_fused_profile.fused_profile(
        toks[r:r + rows], lens[r:r + rows], im_rolled, tie_row, protos,
        n=space.ngram, dim=space.dim, alphabet=space.alphabet_size,
        bb=plan["bb"], bw=plan["bw"], bs=plan["bs"],
        double_buffer=double_buffer,
        vmem_limit_bytes=plan["vmem_limit_bytes"])
        for r in range(0, plan["b_pad"], rows)])
    return out[:b, :s]
