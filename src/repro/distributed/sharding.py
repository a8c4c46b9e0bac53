"""Logical-axis sharding rules (GSPMD) for params and activations.

Models annotate tensors with *logical* axis names; a rules table maps
logical names to mesh axes per execution mode.  This is the single place
where DP / FSDP / TP / EP / SP decisions live:

* ``train``   — batch over (pod, data); FSDP shards the ff/vocab "fsdp"
  dim of params over data; TP shards heads/ff/experts/vocab over model.
* ``prefill`` — batch over (pod, data); TP over model; params TP +
  FSDP (weights are all-gathered per layer by XLA as needed).
* ``decode``  — batch over (pod, data); KV cache sequence over model
  (flash-decoding combine in serve/decode_attn.py); TP over model.

``use_rules`` installs a rules table into a context; ``logical`` and
``constrain`` are no-ops when no mesh is active, so all model code runs
unchanged on a single CPU device (tests) and under pjit (dry-run).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

Rules = dict[str, tuple[str, ...] | str | None]

# Logical axis vocabulary used by the models:
#   batch, seq, embed, heads, kv_heads, qk_dim, v_dim, ff, experts,
#   expert_group, capacity, vocab, kv_seq, state, conv, fsdp(=param ff dim)

TRAIN_RULES: Rules = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": None,        # kv heads often < model axis; keep replicated
    "ff": "model",
    "experts": "model",
    "expert_group": ("pod", "data"),
    "vocab": "model",
    "kv_seq": None,
    "fsdp": "data",          # FSDP: shard the non-TP param dim over data
    "state": None,
    "ssm_heads": "model",
    # Megatron-style sequence parallelism: the residual stream between
    # blocks lives sequence-sharded over 'model'; XLA inserts the
    # all-gather before qkv/ffn and the reduce-scatter after wo/w_out.
    # This is what keeps 95 layers of saved remat residuals inside HBM.
    "residual_seq": "model",
}

PREFILL_RULES: Rules = dict(TRAIN_RULES, fsdp="data")

# Decode: params replicated over 'data' (fsdp=None) — FSDP sharding at
# decode costs a full per-token weight all-gather (§Perf H2a); TP shards
# alone fit HBM for every assigned arch once the KV cache is seq-sharded.
DECODE_RULES: Rules = dict(TRAIN_RULES, kv_seq="model", fsdp=None,
                           residual_seq=None)

# Demeter profiling: the AM search (queries x prototypes agreement) is
# partitioned over the *prototype* axis — the in-memory-HDC analogue of
# splitting the associative memory across crossbar arrays.  Reads and the
# packed HD dimension stay replicated: per-shard partial species scores
# merge with an elementwise max (classifier.merge_scores), so the only
# cross-device traffic is a (B, num_species) pmax.
PROFILE_RULES: Rules = {
    "reads": None,            # query batch: replicated (every shard scores it)
    "protos": "shard",        # prototype rows: split across the mesh
    "hd_words": None,         # packed HD dim: contiguous within a shard
    "species": None,          # per-species scores: replicated after merge
}


def make_profile_mesh(num_shards: int | None = None) -> Mesh:
    """1-D ``('shard',)`` mesh over the first ``num_shards`` local devices.

    The profiling analogue of ``launch.mesh``: prototype-axis model
    parallelism only (reads are cheap to replicate; the AM is not).
    """
    devices = jax.devices()
    n = len(devices) if num_shards is None else num_shards
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"num_shards must be in [1, {len(devices)}] (local devices), "
            f"got {n}")
    return Mesh(np.asarray(devices[:n]), ("shard",))


@contextlib.contextmanager
def use_rules(mesh: Mesh | None, rules: Rules | None):
    """Activate (mesh, rules) for logical()/constrain() in this thread."""
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, rules) if mesh is not None else None
    try:
        yield
    finally:
        _state.ctx = prev


def active_mesh() -> Mesh | None:
    ctx = getattr(_state, "ctx", None)
    return ctx[0] if ctx else None


def _resolve(axes: Sequence[str | None]) -> P:
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return P()
    mesh, rules = ctx
    spec = []
    for ax in axes:
        if ax is None:
            spec.append(None)
            continue
        target = rules.get(ax, None)
        if target is None:
            spec.append(None)
        elif isinstance(target, tuple):
            spec.append(tuple(t for t in target if t in mesh.axis_names))
        else:
            spec.append(target if target in mesh.axis_names else None)
    return P(*spec)


def spec_for(axes: Sequence[str | None]) -> P:
    """PartitionSpec for a tuple of logical axis names (public)."""
    return _resolve(axes)


def sharding_for(axes: Sequence[str | None]) -> NamedSharding | None:
    mesh = active_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, _resolve(axes))


def constrain(x: jax.Array, axes: Sequence[str | None]) -> jax.Array:
    """with_sharding_constraint by logical axes (no-op without a mesh)."""
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _resolve(axes)))


def _divisible(dim: int, mesh: Mesh, target) -> bool:
    if target is None:
        return True
    names = target if isinstance(target, tuple) else (target,)
    size = 1
    for n in names:
        if n in mesh.axis_names:
            size *= mesh.shape[n]
    return size > 0 and dim % size == 0


def axis_size(logical: str) -> int:
    """Mesh size behind a logical axis in the active rules (1 if none)."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return 1
    mesh, rules = ctx
    target = rules.get(logical)
    if target is None:
        return 1
    names = target if isinstance(target, tuple) else (target,)
    size = 1
    for n in names:
        if n in mesh.axis_names:
            size *= mesh.shape[n]
    return size


def safe_spec(shape: tuple[int, ...], axes: Sequence[str | None]) -> P:
    """Like spec_for, but drops axes whose mesh size doesn't divide the dim.

    Keeps lowering robust when e.g. kv_heads=4 meets model=16.
    """
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return P()
    mesh, rules = ctx
    out = []
    for dim, ax in zip(shape, axes):
        target = rules.get(ax) if ax else None
        if isinstance(target, tuple):
            target = tuple(t for t in target if t in mesh.axis_names) or None
        elif target is not None and target not in mesh.axis_names:
            target = None
        out.append(target if target and _divisible(dim, mesh, target) else None)
    return P(*out)


def constrain_safe(x: jax.Array, axes: Sequence[str | None]) -> jax.Array:
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, safe_spec(x.shape, axes)))
