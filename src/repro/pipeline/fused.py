"""``pallas_fused``: the fused encode->search backend.

One :func:`repro.kernels.fused_profile.fused_profile` megakernel runs
Demeter steps 3 and 4 together — each read's k-mer stream is encoded
tile-by-tile in VMEM and every finished dim-tile folds straight into the
agreement accumulator against the prototypes' matching tile, so the
``(batch, dim)`` encoded query matrix never round-trips through HBM
(Acc-Demeter's in-memory dataflow, paper §5; same insight as Karunaratne
et al., *In-memory hyperdimensional computing*).

The backend exposes the fusion as the ``tokens_agreement`` capability;
:meth:`~repro.pipeline.session.ProfilingSession.classify_batch` dispatches
to it when present, so both ``profile()`` and the serving layer
(:class:`~repro.serve.profiler_service.ProfilingService`) run the fused
path with no changes of their own.  The Backend-protocol primitives
``encode`` / ``agreement`` remain (the standalone Pallas kernels): the
RefDB build still needs a bare encoder, and a ``sharded`` wrapper calls
``tokens_agreement`` per shard when fusing and ``agreement`` otherwise.

Options (``ProfilerConfig.backend_options``, all validated here so a bad
tile size is a :class:`ValueError` at session construction — never a
Pallas shape crash mid-profile):

    bb  batch-tile rows, power of two (default 8), at most the padded
        configured batch.
    bw  word-tile lanes, positive (default 128; clamped to W).
    bs  prototype rows per kernel chunk, multiple of 128 (default 4096)
        — bounds the VMEM-resident prototype slab and accumulator.
    autotune        bool: resolve bb/bw/bs from the on-disk tile cache
        (:mod:`repro.kernels.autotune`) at the first profiled batch,
        measuring once per (platform, device kind, B, W, S, dim) key.
        Explicit tile options win over autotune (warned once).
    autotune_cache  str: cache file override (else the
        ``REPRO_AUTOTUNE_CACHE`` env var / ``~/.cache/repro/``).
"""

from __future__ import annotations

import warnings

import jax
import numpy as np

from repro.pipeline.backend import _BackendBase, register_backend
from repro.pipeline.config import ProfilerConfig
from repro.pipeline.options import Option, OptionsSchema

_TILE_OPTIONS = ("bb", "bw", "bs")
_DEFAULTS = {"bb": 8, "bw": 128, "bs": 4096}

#: warn only once per process when explicit tiles silence autotune
_warned_autotune_override = False


def _pow2_tile(v) -> str | None:
    if v < 1:
        return "must be a positive int"
    if v & (v - 1):
        return "must be a power of two so every padded batch tiles evenly"
    return None


def _positive_tile(v) -> str | None:
    return None if v >= 1 else "must be a positive int"


def _proto_tile(v) -> str | None:
    if v < 1:
        return "must be a positive int"
    if v % 128:
        return "must be a multiple of 128 (the prototype-axis output tile)"
    return None


def _nonempty_path(v) -> str | None:
    return None if v else "must be a non-empty path"


#: Declared next to the registry entry: the single source of truth for
#: ``--list-backends``, CLI coercion, and construction-time validation.
FUSED_OPTIONS = OptionsSchema(backend="pallas_fused", options=(
    Option("bb", "int", default=_DEFAULTS["bb"], check=_pow2_tile,
           help="batch tile (reads per kernel step; power of two)"),
    Option("bw", "int", default=_DEFAULTS["bw"], check=_positive_tile,
           help="window tile (tokens per inner step)"),
    Option("bs", "int", default=_DEFAULTS["bs"], check=_proto_tile,
           help="prototype tile (output columns; multiple of 128)"),
    Option("autotune", "bool", default=False,
           help="measure candidate tilings once per (S, L) shape"),
    Option("autotune_cache", "str", default=None, check=_nonempty_path,
           help="JSON file persisting autotuner picks across processes"),
))


def _validated_options(config: ProfilerConfig
                       ) -> tuple[dict[str, int], set[str], bool,
                                  str | None]:
    """Consume schema-validated options + apply config-dependent checks.

    The per-value checks (types, power-of-two, 128-multiple) already ran
    in :class:`_BackendBase` via :data:`FUSED_OPTIONS`; only the check
    that needs the rest of the config — ``bb`` against the padded batch —
    lives here.  Returns ``(tiles, explicit, autotune, cache_path)`` where
    ``explicit`` names the tile options the user pinned.
    """
    opts = config.options
    tiles = {name: opts.get(name, _DEFAULTS[name]) for name in _TILE_OPTIONS}
    explicit = {name for name in _TILE_OPTIONS if name in opts}
    autotune = bool(opts.get("autotune", False))
    cache_path = opts.get("autotune_cache")
    padded_batch = 8 * ((config.batch_size + 7) // 8)
    if "bb" in explicit and tiles["bb"] > padded_batch:
        raise ValueError(
            f"pallas_fused option 'bb'={tiles['bb']} exceeds the padded "
            f"batch ({config.batch_size} reads pad to {padded_batch}); "
            f"lower bb or raise batch_size")
    return tiles, explicit, autotune, cache_path


@register_backend("pallas_fused", schema=FUSED_OPTIONS)
class PallasFusedBackend(_BackendBase):
    """Fused encode->search megakernel (interpret mode on CPU)."""

    name = "pallas_fused"

    def __init__(self, config: ProfilerConfig):
        super().__init__(config)
        (self.tiles, self._explicit, self._autotune,
         self._autotune_cache) = _validated_options(config)
        if self._autotune and self._explicit:
            global _warned_autotune_override
            if not _warned_autotune_override:
                _warned_autotune_override = True
                warnings.warn(
                    "pallas_fused: explicit tile options "
                    f"{sorted(self._explicit)} override autotune=true; "
                    "the autotuner will not run for this backend",
                    stacklevel=2)
            self._autotune = False
        #: (S, L) shape the cached tuning was resolved for
        self._tuned_for: tuple[int, int] | None = None
        #: the kernel's tile plans, per (B, L, S, tiles)
        self._plans: dict[tuple, dict[str, int | None]] = {}

    # -- Backend protocol (standalone kernels; RefDB build + sharded) ------
    def encode(self, tokens: jax.Array, lengths: jax.Array) -> jax.Array:
        from repro.kernels import ops
        return ops.hdc_encode(tokens, lengths, self.im, self.tie, self.space)

    def agreement(self, queries: jax.Array, prototypes: jax.Array
                  ) -> jax.Array:
        from repro.kernels import ops
        return ops.am_agreement(queries, prototypes, self.space.dim,
                                "matmul")

    def _resolve_tiles(self, num_prototypes: int, read_len: int
                       ) -> dict[str, int]:
        """Tiles for this batch; runs/reads the autotuner cache lazily.

        The tuner keys on the configured batch plus the live (S, L), so
        the first profiled batch pays the sweep (or a cache read) and
        every later batch — and every other process on the same device
        kind — reuses the same deterministic choice.
        """
        if not self._autotune:
            return self.tiles
        if self._tuned_for != (num_prototypes, read_len):
            from repro.kernels import autotune
            tiles, _ = autotune.tune(
                self.space, batch=self.config.batch_size,
                num_prototypes=num_prototypes, read_len=read_len,
                path=self._autotune_cache)
            self.tiles = {**self.tiles, **tiles}
            self._tuned_for = (num_prototypes, read_len)
        return self.tiles

    def kernel_plan(self, batch: int, read_len: int, num_prototypes: int,
                    lengths=None) -> dict[str, int]:
        """The ``session.dispatch`` span's arguments for this call.

        ``chunks``, the tile plan's prototype chunks, and ``encodes``,
        encode passes per read (1 with the kernel's encoded-batch cache),
        are worked out once per shape.  Given the call's ``lengths`` as a
        numpy array, also ``gram_steps`` and ``gram_steps_shape``
        (:func:`repro.kernels.ops.gram_steps`); a device array is left
        unread rather than waited for.
        """
        from repro.kernels import ops
        t = self._resolve_tiles(num_prototypes, read_len)
        key = (batch, read_len, num_prototypes, t["bb"], t["bw"], t["bs"])
        if key not in self._plans:
            self._plans[key] = ops.fused_tile_plan(
                batch, num_prototypes, self.space.num_words,
                read_len=read_len, n=self.space.ngram,
                alphabet=self.space.alphabet_size, bb=t["bb"],
                bw=min(t["bw"], self.space.num_words), bs=t["bs"])
        plan = self._plans[key]
        out = {"chunks": plan["n_chunks"], "encodes": plan["encodes"]}
        if isinstance(lengths, np.ndarray):
            out.update(ops.gram_steps(lengths, plan, read_len=read_len,
                                      n=self.space.ngram))
        return out

    # -- fused capability (ProfilingSession.classify_batch dispatch) -------
    def tokens_agreement(self, tokens: jax.Array, lengths: jax.Array,
                         prototypes: jax.Array) -> jax.Array:
        """Steps 3+4 fused: ``(B, L)`` tokens -> ``(B, S)`` agreement.

        The encoded queries exist only as VMEM tiles inside the kernel.
        """
        from repro.kernels import ops
        t = self._resolve_tiles(prototypes.shape[0], tokens.shape[1])
        return ops.fused_agreement(
            tokens, lengths, self.im, self.tie, prototypes, self.space,
            bb=t["bb"], bw=min(t["bw"], self.space.num_words), bs=t["bs"])
