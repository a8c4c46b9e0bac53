"""``sharded``: prototype-axis model parallelism for the AM search.

The registry's scaling seam, made real.  Demeter's query hot path is one
big ``(B, W) x (S, W)`` agreement against the HD reference database; on a
single accelerator it is capped by that device's memory and FLOPs.
In-memory HDC hardware scales the same search by splitting the
associative memory across crossbar arrays — this backend is the digital
analogue: the *prototype* axis is partitioned across a 1-D ``('shard',)``
device mesh (``repro.distributed.sharding.PROFILE_RULES``), every shard
scores the (replicated, cheap) query batch against its local slice of
prototypes with **any base backend's** ``agreement``, and per-shard
partial species scores merge with an elementwise ``pmax`` — exact, so the
whole path stays bit-identical to the unsharded reference on any mesh
size (enforced in ``tests/test_sharded.py`` on 1 and 8 devices).

Two execution surfaces:

* ``agreement(queries, prototypes)`` — the Backend-protocol primitive,
  ``shard_map``-ped over the prototype axis with the ``(B, S)`` result
  left prototype-sharded (no gather on the hot path; XLA moves rows only
  if a consumer needs them elsewhere).
* ``species_scores(queries, prototypes, proto_species, num_species)`` —
  the fused fast path the session prefers when present: agreement *and*
  the per-species reduction run inside the map, so the only cross-device
  traffic is the ``(B, num_species)`` pmax — independent of S, the axis
  being scaled.

Options (``ProfilerConfig.backend_options``):

    base    name of the wrapped backend ("reference" default; any
            registered name except "sharded" itself).
    shards  mesh size (default: every local device).  Prototype counts
            that don't divide it are zero-padded; padding rows carry
            species id ``num_species``, which the segment reduction
            drops, so they can never reach a report.

``place_refdb`` is the device-placement step ``ProfilingSession`` runs
after build/load: pad S to the mesh, lay prototypes out shard-major, and
``device_put`` them so each device holds ``1/shards`` of the database —
the capacity win that lets the AM outgrow one device's memory.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import assoc_memory
from repro.core.assoc_memory import RefDB
from repro.distributed import sharding
from repro.core.bitops import pad_to_multiple
from repro.pipeline.backend import register_backend, resolve_backend
from repro.pipeline.config import ProfilerConfig
from repro.pipeline.options import Option, OptionsSchema, non_negative

#: Pallas kernels have no replication rule, so the shard bodies run with
#: shard_map's replication (VMA) check off.
_shard_map = functools.partial(jax.shard_map, check_vma=False)

#: Options consumed by this backend; everything else is forwarded to the
#: base backend's config (e.g. pcm_sim device knobs under base=pcm_sim).
_OWN_OPTIONS = ("base", "shards")


def _non_sharded(v) -> str | None:
    return None if v != "sharded" else "must name a non-sharded backend"


#: ``passthrough=True``: unknown options are forwarded to the wrapped
#: backend, whose own schema validates them — so a misspelled ``pcm_sim``
#: knob fails with the same error whether it rides directly or through
#: ``sharded``.
SHARDED_OPTIONS = OptionsSchema(backend="sharded", passthrough=True, options=(
    Option("base", "str", default="reference", check=_non_sharded,
           help="wrapped backend name (any registered name but 'sharded')"),
    Option("shards", "int", default=0, check=non_negative,
           help="mesh size; 0 = every local device"),
))


def pad_refdb(db: RefDB, multiple: int) -> RefDB:
    """Pad the prototype axis up to a multiple of ``multiple``.

    Padding rows are all-zero vectors tagged with species id
    ``num_species`` — out of range for the segment reduction, so they are
    dropped there, and sliced off by the ``agreement`` path.  Idempotent
    when S already divides.
    """
    if db.prototypes.shape[0] % multiple == 0:
        return db
    return dataclasses.replace(
        db,
        prototypes=pad_to_multiple(db.prototypes, 0, multiple),
        proto_species=pad_to_multiple(db.proto_species, 0, multiple,
                                      fill=db.num_species),
    )


def placement_shardings(mesh) -> tuple[NamedSharding, NamedSharding]:
    """(prototype, proto_species) shardings under PROFILE_RULES."""
    with sharding.use_rules(mesh, sharding.PROFILE_RULES):
        return (sharding.sharding_for(("protos", "hd_words")),
                sharding.sharding_for(("protos",)))


def place_refdb(db: RefDB, mesh) -> RefDB:
    """Pad S to the mesh and lay the database out across its devices.

    Prototypes and their species tags are split shard-major over the
    ``'shard'`` axis (each device holds ``S_padded / shards`` rows);
    genome lengths are tiny and stay replicated.
    """
    db = pad_refdb(db, mesh.size)
    proto_sh, species_sh = placement_shardings(mesh)
    return dataclasses.replace(
        db,
        prototypes=jax.device_put(db.prototypes, proto_sh),
        proto_species=jax.device_put(db.proto_species, species_sh),
    )


def per_device_bytes(db: RefDB, num_shards: int) -> int:
    """RefDB bytes resident on *each* device at ``num_shards`` shards.

    The sharded halves (prototypes + species tags) divide by the mesh
    size after padding; the genome-length vector is replicated.  With
    ``num_shards=1`` this equals :meth:`RefDB.memory_bytes`.
    """
    s, w = db.prototypes.shape
    rows = -(-s // num_shards)          # ceil: padded rows per shard
    return rows * w * 4 + rows * 4 + db.genome_lengths.size * 4


@register_backend("sharded", schema=SHARDED_OPTIONS)
class ShardedBackend:
    """Prototype-axis sharding wrapped around any base backend."""

    name = "sharded"

    def __init__(self, config: ProfilerConfig):
        own, base_options = SHARDED_OPTIONS.validate(config.options)
        base_name = own.get("base", "reference")
        shards = own.get("shards", 0)
        base_config = dataclasses.replace(
            config, backend=base_name, backend_options=base_options)
        self.config = config
        self.base = resolve_backend(base_name, base_config)
        self.space = base_config.space
        self.mesh = sharding.make_profile_mesh(shards or None)
        self.num_shards = self.mesh.size
        self._agreement = jax.jit(self._agreement_impl)
        self._scores = jax.jit(self._scores_impl,
                               static_argnames=("num_species",))
        # A fused base (tokens_agreement capability, e.g. pallas_fused)
        # stays fused under sharding: each shard streams the raw tokens
        # through the megakernel against its local prototypes — the
        # crossbar-per-array dataflow — so the capabilities are exposed
        # only when the base has them (instance attributes, so the
        # session's getattr dispatch sees exactly what the base offers).
        if getattr(self.base, "tokens_agreement", None) is not None:
            self.tokens_agreement = self._tokens_agreement
            self.tokens_species_scores = self._tokens_species_scores
            self.kernel_plan = self._kernel_plan
            self._tok_agree = jax.jit(self._tokens_agreement_impl)
            self._tok_scores = jax.jit(self._tokens_scores_impl,
                                       static_argnames=("num_species",))

    # -- step 3: reads are replicated; encoding is the base's, bit-exact --
    def encode(self, tokens: jax.Array, lengths: jax.Array) -> jax.Array:
        return self.base.encode(tokens, lengths)

    # -- step 4, protocol surface -----------------------------------------
    def agreement(self, queries: jax.Array, prototypes: jax.Array
                  ) -> jax.Array:
        """Per-prototype agreement, computed shard-locally.

        The ``(B, S)`` result stays sharded over S; slicing back to the
        caller's prototype count drops any mesh-padding columns.
        """
        s = prototypes.shape[0]
        p = pad_to_multiple(jnp.asarray(prototypes), 0, self.num_shards)
        return self._agreement(jnp.asarray(queries), p)[:, :s]

    def _agreement_impl(self, q, p):
        return _shard_map(
            lambda qb, pb: self.base.agreement(qb, pb),
            mesh=self.mesh,
            in_specs=(P(None, None), P("shard", None)),
            out_specs=P(None, "shard"))(q, p)

    # -- step 4, fused fast path (used by ProfilingSession when present) --
    def species_scores(self, queries: jax.Array, prototypes: jax.Array,
                       proto_species: jax.Array, num_species: int
                       ) -> jax.Array:
        """Agreement + per-species max, reduced in-shard and pmax-merged.

        Cross-device traffic is one ``(B, num_species)`` integer pmax —
        independent of the prototype count being scaled.  Bit-identical
        to ``species_scores(base.agreement(q, p))`` on the full set.
        """
        p = pad_to_multiple(jnp.asarray(prototypes), 0, self.num_shards)
        ps = pad_to_multiple(jnp.asarray(proto_species), 0, self.num_shards,
                             fill=num_species)
        return self._scores(jnp.asarray(queries), p, ps,
                            num_species=num_species)

    def _scores_impl(self, q, p, ps, *, num_species):
        def per_shard(qb, pb, psb):
            agree = self.base.agreement(qb, pb)
            partial = assoc_memory.species_scores(agree, psb, num_species)
            return jax.lax.pmax(partial, "shard")

        return _shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(P(None, None), P("shard", None), P("shard")),
            out_specs=P(None, None))(q, p, ps)

    # -- steps 3+4 fused per shard (only when the base is fused) ----------
    def _kernel_plan(self, batch: int, read_len: int, num_prototypes: int,
                     lengths=None) -> dict[str, int]:
        """The base kernel's plan on one shard's prototype slice (every
        shard encodes the whole, replicated batch)."""
        return self.base.kernel_plan(batch, read_len,
                                     -(-num_prototypes // self.num_shards),
                                     lengths)

    def _tokens_agreement(self, tokens: jax.Array, lengths: jax.Array,
                          prototypes: jax.Array) -> jax.Array:
        """Fused encode->search per shard: tokens in, agreement out.

        The (replicated, tiny) token stream reaches every shard, which
        runs the base megakernel against its local prototype slice — the
        encoded queries never exist off-VMEM on *any* device.
        """
        s = prototypes.shape[0]
        p = pad_to_multiple(jnp.asarray(prototypes), 0, self.num_shards)
        return self._tok_agree(jnp.asarray(tokens), jnp.asarray(lengths),
                               p)[:, :s]

    def _tokens_agreement_impl(self, t, l, p):
        return _shard_map(
            lambda tb, lb, pb: self.base.tokens_agreement(tb, lb, pb),
            mesh=self.mesh,
            in_specs=(P(None, None), P(None), P("shard", None)),
            out_specs=P(None, "shard"))(t, l, p)

    def _tokens_species_scores(self, tokens: jax.Array, lengths: jax.Array,
                               prototypes: jax.Array,
                               proto_species: jax.Array, num_species: int
                               ) -> jax.Array:
        """Fully fused: encode + search + species reduction in-shard.

        Cross-device traffic is the one ``(B, num_species)`` pmax, same
        as :meth:`species_scores` — but nothing upstream of it ever
        materializes either.
        """
        p = pad_to_multiple(jnp.asarray(prototypes), 0, self.num_shards)
        ps = pad_to_multiple(jnp.asarray(proto_species), 0, self.num_shards,
                             fill=num_species)
        return self._tok_scores(jnp.asarray(tokens), jnp.asarray(lengths),
                                p, ps, num_species=num_species)

    def _tokens_scores_impl(self, t, l, p, ps, *, num_species):
        def per_shard(tb, lb, pb, psb):
            agree = self.base.tokens_agreement(tb, lb, pb)
            partial = assoc_memory.species_scores(agree, psb, num_species)
            return jax.lax.pmax(partial, "shard")

        return _shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(P(None, None), P(None), P("shard", None), P("shard")),
            out_specs=P(None, None))(t, l, p, ps)

    # -- device placement (ProfilingSession hook) -------------------------
    def place_refdb(self, db: RefDB) -> RefDB:
        """Pad + distribute a built/loaded RefDB across the shard mesh."""
        return place_refdb(db, self.mesh)
