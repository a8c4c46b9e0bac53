"""Plain reference of Demeter profiling, independent of the program.

Written from the paper's definitions (arXiv:2206.01932, sections 3.1-3.5)
and the configuration file alone; it imports nothing of ``repro``.

- Item memory: four random packed hypervectors of ``D`` bits drawn with
  ``jax.random.bits`` from the HD space's seed; the tie-break vector from
  ``seed ^ 0x7EB4EA4``.  Bits are LSB-first in ``uint32`` words and the
  permutation ``rho`` rotates by whole words.
- Encoding (Eq. 1): gram ``i`` is ``XOR_j rho^j(IM[c_{i+j}])`` for
  ``j < n``; a sequence's vector is the bitwise majority over its
  ``max(L - n + 1, 0)`` grams, exact ties taking the tie-break bit.
- Reference database: genomes cut into windows of ``window`` bases
  (non-overlapping, plus a tail window ending at the genome's end), one
  prototype per window.
- Search (Eq. 2): agreement is ``D - popcount(q XOR p)``; a species'
  score is its best window; a read hits a species when the score is at
  least ``floor(D/2 + z*sqrt(D)/2)``.
- Abundance: unique reads count for their species; multi-mapped reads
  are split by the species' unique reads per reference base, uniformly
  where no hit species has unique support.

Everything heavy is plain ``jax.numpy`` in blocks of rows, so it runs on
the chip after the measured window without holding much memory.
``control=True`` holds agreements in bfloat16 (steps of 128 near D/2):
the lower-precision search a later change might be tempted by, which the
comparison of per-read scores must catch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32
_CHUNK = 64          # grams bundled per scan step


def threshold_bits(dim: int, z: float) -> int:
    """Integer agreement a hit needs: ``floor(D/2 + z*sqrt(D)/2)``."""
    return int(math.floor(dim / 2 + z * math.sqrt(dim) / 2))


def item_memory(dim: int, alphabet: int, seed: int) -> jax.Array:
    return jax.random.bits(jax.random.key(seed), (alphabet, dim // WORD_BITS),
                           dtype=jnp.uint32)


def tie_break(dim: int, seed: int) -> jax.Array:
    return jax.random.bits(jax.random.key(seed ^ 0x7EB4EA4),
                           (dim // WORD_BITS,), dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n",))
def _encode_block(tokens, lengths, im, tie, *, n: int):
    """``(B, Lp)`` tokens (``Lp = g_pad + n - 1``) -> ``(B, W)`` packed."""
    b, lp = tokens.shape
    alphabet, w = im.shape
    g_pad = lp - n + 1
    rolled = jnp.stack([jnp.roll(im, j, axis=1) for j in range(n)])
    m = jnp.maximum(lengths - n + 1, 0)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[:, None]

    def step(counts, i0):
        win = jax.lax.dynamic_slice_in_dim(tokens, i0, _CHUNK + n - 1, axis=1)
        gram = jnp.zeros((b, _CHUNK, w), jnp.uint32)
        for j in range(n):
            t = win[:, j:j + _CHUNK, None]
            row = rolled[j, alphabet - 1]
            for a in range(alphabet - 1):
                row = jnp.where(t == a, rolled[j, a], row)
            gram = gram ^ row
        valid = (i0 + jnp.arange(_CHUNK))[None, :] < m[:, None]
        bits = (gram[:, :, None, :] >> shifts[None, None]) & 1    # (B,C,32,W)
        bits = jnp.where(valid[:, :, None, None], bits, 0)
        return counts + bits.sum(axis=1, dtype=jnp.int32), None

    counts, _ = jax.lax.scan(step, jnp.zeros((b, WORD_BITS, w), jnp.int32),
                             jnp.arange(0, g_pad, _CHUNK))
    twice = 2 * counts
    mm = m[:, None, None]
    tie_bits = ((tie[None, :] >> shifts) & 1).astype(jnp.int32)[None]
    bits = jnp.where(twice == mm, tie_bits, (twice > mm).astype(jnp.int32))
    return (bits.astype(jnp.uint32) << shifts[None]).sum(axis=1,
                                                        dtype=jnp.uint32)


def encode(tokens: np.ndarray, lengths: np.ndarray, im, tie, n: int,
           rows: int) -> np.ndarray:
    """Encode ``(R, L)`` sequences in blocks of ``rows`` -> ``(R, W)``."""
    r, length = tokens.shape
    g_pad = -(-max(length - n + 1, 1) // _CHUNK) * _CHUNK
    out = []
    for i in range(0, r, rows):
        blk = np.zeros((rows, g_pad + n - 1), np.int32)
        part = tokens[i:i + rows]
        blk[:len(part), :length] = part
        lens = np.zeros(rows, np.int32)
        lens[:len(part)] = lengths[i:i + rows]
        q = _encode_block(jnp.asarray(blk), jnp.asarray(lens), im, tie, n=n)
        out.append(np.asarray(q)[:len(part)])
    return np.concatenate(out)


def windows(genome: np.ndarray, window: int) -> np.ndarray:
    """Non-overlapping windows plus a tail window ending at the end."""
    length = len(genome)
    if length <= window:
        out = np.zeros((1, window), genome.dtype)
        out[0, :length] = genome
        return out
    starts = list(range(0, length - window + 1, window))
    if starts[-1] + window < length:
        starts.append(length - window)
    return np.stack([genome[s:s + window] for s in starts])


@functools.partial(jax.jit, static_argnames=("dim", "control"))
def _agreement(q, protos, *, dim: int, control: bool):
    x = jnp.bitwise_xor(q[:, None, :], protos[None, :, :])
    agree = dim - jnp.bitwise_count(x).astype(jnp.int32).sum(axis=-1)
    # The control's bfloat16 is the program's output type, so the rounding
    # cannot be elided as excess precision inside the computation.
    return agree.astype(jnp.bfloat16) if control else agree


class Reference:
    """The reference database and search of one configuration."""

    def __init__(self, cfg: dict, genomes: np.ndarray):
        self.dim, self.n = cfg["dim"], cfg["ngram"]
        self.threshold = threshold_bits(self.dim, cfg["z_threshold"])
        self.im = item_memory(self.dim, cfg["alphabet"], cfg["space_seed"])
        self.tie = tie_break(self.dim, cfg["space_seed"])
        self.genome_lengths = np.full(len(genomes), genomes.shape[1], np.int64)
        wins = [windows(g, cfg["window"]) for g in genomes]
        self.bounds = np.cumsum([0] + [len(w) for w in wins])
        allw = np.concatenate(wins)
        self.prototypes = encode(allw, np.full(len(allw), allw.shape[1]),
                                 self.im, self.tie, self.n, rows=128)
        self._protos = jnp.asarray(self.prototypes)

    def scores(self, tokens: np.ndarray, lengths: np.ndarray, *,
               control: bool = False, rows: int = 256) -> np.ndarray:
        """Best window agreement per read and species, ``(R, species)``."""
        q = encode(tokens, lengths, self.im, self.tie, self.n, rows)
        out = []
        for i in range(0, len(q), rows):
            agree = np.asarray(_agreement(jnp.asarray(q[i:i + rows]),
                                          self._protos, dim=self.dim,
                                          control=control), np.float32
                               if control else np.int32)
            out.append(np.stack([agree[:, a:b].max(axis=1) for a, b in
                                 zip(self.bounds[:-1], self.bounds[1:])], 1))
        return np.concatenate(out)

    def classify(self, tokens: np.ndarray, lengths: np.ndarray, **kw
                 ) -> tuple[np.ndarray, np.ndarray]:
        """Hit mask ``(R, species)`` and category (0 none, 1 one, 2 more)."""
        hits = self.scores(tokens, lengths, **kw) >= self.threshold
        k = hits.sum(axis=1)
        return hits, np.where(k == 0, 0, np.where(k == 1, 1, 2))

    def report(self, hits: np.ndarray, category: np.ndarray) -> dict:
        """Abundance report of one request's classified reads."""
        uniq = hits[category == 1].sum(axis=0).astype(np.int64)
        m = hits[category == 2]
        lens = np.maximum(self.genome_lengths.astype(np.float64), 1.0)
        rate = uniq.astype(np.float64) / lens
        w = m * rate[None, :]
        mass = w.sum(axis=-1, keepdims=True)
        uniform = m / np.maximum(m.sum(axis=-1, keepdims=True), 1)
        w = np.where(mass > 0, w / np.maximum(mass, 1e-30), uniform)
        multi = w.sum(axis=0)
        mapped = uniq + multi
        return {"total": len(category),
                "unmapped": int((category == 0).sum()),
                "multi": len(m),
                "unique_counts": uniq,
                "abundance": mapped / max(mapped.sum(), 1e-30)}
