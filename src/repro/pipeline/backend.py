"""Execution backends: named, registered implementations of the hot path.

Demeter's platform-independence claim (paper §3) is that the same
five-step algorithm runs on any substrate — software, Acc-Demeter's PCM
crossbar, a TPU.  A :class:`Backend` is the seam where the substrate
plugs in: it owns exactly the two bit-exact primitives that differ per
platform,

  ``encode(tokens, lengths) -> (B, W)``   packed query HD vectors (step 3)
  ``agreement(queries, prototypes) -> (B, S)``  matching-bit counts (step 4)

while everything around them (windowing, thresholding, species reduction,
abundance) is substrate-independent and lives in ``repro.core`` /
:mod:`repro.pipeline.session`.

Backends are discovered by name through a registry::

    session = ProfilingSession(ProfilerConfig(backend="pallas_matmul"))
    available_backends()   # ("pallas_matmul", "pallas_packed", ...)

Registered backends:

  reference        pure-jnp encoder + ±1 matmul agreement (BLAS on CPU).
  reference_packed pure-jnp encoder + packed XOR+popcount agreement.
  pallas_matmul    Pallas encoder kernel + MXU ±1 matmul kernel.
  pallas_packed    Pallas encoder kernel + VPU popcount kernel.
  pallas_fused     fused encode->search megakernel: the encoded queries
                   never leave VMEM (:mod:`repro.pipeline.fused`).
  pcm_sim          digital encoder + simulated in-memory AM search on the
                   PCM substrate (:mod:`repro.accel`; bit-exact at zero
                   device noise, configurably non-ideal — multi-bit
                   levels, noise, drift, faults — via ``backend_options``).
  racetrack_sim    the same simulated AM search on the racetrack-memory
                   substrate (shift-based access faults + domain-wall
                   read model; Khan et al., see PAPERS.md).
  sharded          prototype-axis model parallelism over a device mesh,
                   wrapping any of the above as its ``base``
                   (:mod:`repro.pipeline.sharded`, on the 1-D mesh of
                   its own ``make_profile_mesh``).

All are bit-exact twins at default options (enforced by
``tests/test_pipeline.py`` and, across mesh sizes, by
``tests/test_sharded.py``).  Backends may additionally expose two
optional capabilities the session discovers by name:

  ``place_refdb(db) -> RefDB``   device placement after build/load
                                 (pad + distribute across a mesh);
  ``species_scores(queries, prototypes, proto_species, num_species)``
                                 fused agreement + per-species reduction,
                                 merged across shards (skips the
                                 per-prototype agreement round-trip);
  ``tokens_agreement(tokens, lengths, prototypes)``
                                 steps 3+4 fused — raw read tokens to
                                 agreement with no encoded HBM matrix
                                 (``pallas_fused``, and ``sharded``
                                 wrapping a base that has it);
  ``tokens_species_scores(tokens, lengths, prototypes, proto_species,
                          num_species)``
                                 the fully fused form of the above
                                 (``sharded`` over a fused base: encode +
                                 search + species reduction per shard,
                                 one pmax of (B, species) cross-device).
"""

from __future__ import annotations

import functools
from typing import Callable, Protocol, runtime_checkable

import jax

from repro.core import assoc_memory, encoder, item_memory
from repro.core.hd_space import HDSpace
from repro.pipeline.config import ProfilerConfig
from repro.pipeline.options import OptionsSchema


@runtime_checkable
class Backend(Protocol):
    """The two substrate-dependent primitives of the pipeline."""

    name: str
    space: HDSpace

    def encode(self, tokens: jax.Array, lengths: jax.Array) -> jax.Array:
        """Read conversion (step 3): ``(B, L)`` tokens -> ``(B, W)`` packed."""
        ...

    def agreement(self, queries: jax.Array, prototypes: jax.Array
                  ) -> jax.Array:
        """AM search (step 4): ``(B, W) x (S, W)`` -> ``(B, S)`` int32
        matching-bit counts in ``[0, dim]``."""
        ...


BackendFactory = Callable[[ProfilerConfig], Backend]

_REGISTRY: dict[str, BackendFactory] = {}
_SCHEMAS: dict[str, OptionsSchema] = {}

#: Backends that register themselves when their module is imported.  The
#: registry resolves these lazily, so ``available_backends()`` and the
#: unknown-backend error are complete even when only this module (not the
#: ``repro.pipeline`` package, which imports them eagerly) has been
#: imported — e.g. ``profile_run --list-backends`` sees every backend no
#: matter which import path reached the registry first.  Third-party
#: backends registered after import via :func:`register_backend` appear
#: the moment they register (nothing is cached).
_LAZY_MODULES: dict[str, str] = {
    "pallas_fused": "repro.pipeline.fused",
    "pcm_sim": "repro.accel.backend_pcm",
    "racetrack_sim": "repro.accel.backend_pcm",
    "sharded": "repro.pipeline.sharded",
}


def register_backend(name: str, schema: OptionsSchema | None = None
                     ) -> Callable[[BackendFactory], BackendFactory]:
    """Decorator: register a ``ProfilerConfig -> Backend`` factory by name.

    ``schema`` declares the backend's options (displayed by
    ``--list-backends``, enforced uniformly at construction, and used to
    type ``--backend-option`` CLI values).  ``None`` declares an
    option-less backend: *any* provided option fails with the uniform
    unknown-option error instead of being silently ignored.
    """
    def deco(factory: BackendFactory) -> BackendFactory:
        if name in _REGISTRY:
            raise ValueError(f"backend {name!r} already registered")
        _REGISTRY[name] = factory
        _SCHEMAS[name] = (schema if schema is not None
                          else OptionsSchema(backend=name))
        return factory
    return deco


def available_backends() -> tuple[str, ...]:
    """Names of every registered backend (lazy entry points included)."""
    return tuple(sorted(set(_REGISTRY) | set(_LAZY_MODULES)))


def _materialize(name: str) -> None:
    """Import a lazy backend module so its registration runs."""
    if name not in _REGISTRY and name in _LAZY_MODULES:
        import importlib
        importlib.import_module(_LAZY_MODULES[name])  # registers on import


def options_schema(name: str) -> OptionsSchema:
    """The declared options schema of the backend registered as ``name``."""
    _materialize(name)
    try:
        return _SCHEMAS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {available_backends()}"
        ) from None


def resolve_backend(name: str, config: ProfilerConfig) -> Backend:
    """Instantiate the backend registered under ``name`` for ``config``."""
    _materialize(name)
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {available_backends()}"
        ) from None
    return factory(config)


class _BackendBase:
    """Shared state: the per-space item memory and tie-break vector.

    Construction validates ``config.backend_options`` against the options
    schema declared at registration, so every backend — including the
    option-less digital ones, which used to silently ignore typos — fails
    with the same friendly error on an unknown or ill-typed option.
    """

    name = "abstract"

    def __init__(self, config: ProfilerConfig):
        schema = _SCHEMAS.get(config.backend)
        if schema is not None:
            schema.validate(config.options)
        self.config = config
        self.space = config.space
        self.im = item_memory.make_item_memory(self.space)
        self.tie = item_memory.make_tie_break(self.space)


@register_backend("reference")
class ReferenceBackend(_BackendBase):
    """Pure-jnp software path: rolling-gram encoder + ±1 matmul agreement.

    The numerical oracle every other backend must match bit-exactly.  On
    CPU the agreement matmul maps to BLAS; on TPU XLA lowers it to the MXU.
    """

    name = "reference"

    def __init__(self, config: ProfilerConfig):
        super().__init__(config)
        self._encode = jax.jit(
            lambda t, l: encoder.encode(t, l, self.im, self.tie, self.space))
        self._agreement = jax.jit(functools.partial(
            assoc_memory.agreement_matmul, dim=self.space.dim))

    def encode(self, tokens: jax.Array, lengths: jax.Array) -> jax.Array:
        return self._encode(tokens, lengths)

    def agreement(self, queries: jax.Array, prototypes: jax.Array
                  ) -> jax.Array:
        return self._agreement(queries, prototypes)


@register_backend("reference_packed")
class ReferencePackedBackend(ReferenceBackend):
    """Software path with the bandwidth-optimal XOR+popcount agreement."""

    name = "reference_packed"

    def __init__(self, config: ProfilerConfig):
        super().__init__(config)
        self._agreement = jax.jit(functools.partial(
            assoc_memory.agreement_packed_chunked, dim=self.space.dim))


class _PallasBackendBase(_BackendBase):
    """Pallas kernel path (interpret mode on CPU, real kernels on TPU)."""

    formulation = "matmul"

    def encode(self, tokens: jax.Array, lengths: jax.Array) -> jax.Array:
        from repro.kernels import ops
        return ops.hdc_encode(tokens, lengths, self.im, self.tie, self.space)

    def agreement(self, queries: jax.Array, prototypes: jax.Array
                  ) -> jax.Array:
        from repro.kernels import ops
        return ops.am_agreement(queries, prototypes, self.space.dim,
                                self.formulation)


@register_backend("pallas_matmul")
class PallasMatmulBackend(_PallasBackendBase):
    """Pallas encoder kernel + MXU ±1 matmul AM-search kernel."""

    name = "pallas_matmul"
    formulation = "matmul"


@register_backend("pallas_packed")
class PallasPackedBackend(_PallasBackendBase):
    """Pallas encoder kernel + VPU packed-popcount AM-search kernel."""

    name = "pallas_packed"
    formulation = "packed"
