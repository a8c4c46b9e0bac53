"""Tile-shape autotuner for the fused megakernel.

The fused kernel's throughput is set by three tile knobs — ``bb`` (batch
rows), ``bw`` (word lanes), ``bs`` (prototype rows per chunk) — whose
best values depend on the platform (VMEM size, DMA latency) and the live
problem shape.  This module sweeps candidate configs under a VMEM
feasibility filter (tile buffers within the budget; with the kernel's
encoded-batch cache, within the chip's VMEM), times
:func:`repro.kernels.ops.fused_agreement` on
deterministic synthetic inputs at the live shape, and persists the
winner in an on-disk JSON cache so every later session/service/fleet
process with the same (platform, device kind, B, W, S, dim) key reuses
the tuned tiles without re-measuring.

Wired into the pipeline as ``backend_options autotune=true`` on the
``pallas_fused`` backend (see :mod:`repro.pipeline.fused`); also usable
standalone::

    PYTHONPATH=src python -m repro.kernels.autotune --smoke

Cache location: ``~/.cache/repro/autotune.json``, overridable with the
``REPRO_AUTOTUNE_CACHE`` env var or an explicit ``path=`` argument.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import item_memory
from repro.core.hd_space import HDSpace
from repro.kernels import ops

#: Default on-disk cache (see module docstring for overrides).
DEFAULT_CACHE = Path("~/.cache/repro/autotune.json")

#: Candidate axes swept by :func:`candidate_plans`.  Values infeasible or
#: redundant at a given shape are clamped/deduped by ``fused_tile_plan``.
CANDIDATE_BB = (4, 8, 16)
CANDIDATE_BW = (32, 64, 128, 256)
CANDIDATE_BS = (512, 1024, 4096, 8192)


def cache_path(path: str | os.PathLike | None = None) -> Path:
    """Resolve the cache file: explicit arg > env override > default."""
    if path is not None:
        return Path(path)
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    return Path(env) if env else DEFAULT_CACHE.expanduser()


def cache_key(b: int, w: int, s: int, dim: int,
              device: jax.Device | None = None) -> str:
    """Cache key: (platform, device kind, B, W, S, dim)."""
    device = device or jax.devices()[0]
    kind = getattr(device, "device_kind", device.platform)
    return f"{device.platform}|{kind}|B{b}|W{w}|S{s}|D{dim}"


def load_cache(path: str | os.PathLike | None = None) -> dict:
    """Read the cache; missing or corrupt files are an empty cache."""
    try:
        return json.loads(cache_path(path).read_text())
    except (OSError, ValueError):
        return {}


def save_cache(cache: dict, path: str | os.PathLike | None = None) -> Path:
    """Atomically write the cache (temp file + rename, crash-safe)."""
    p = cache_path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=p.parent, prefix=p.name + ".")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=2, sort_keys=True)
        os.replace(tmp, p)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return p


def candidate_plans(b: int, s: int, w: int, *, read_len: int, n: int,
                    alphabet: int = 4) -> list[dict[str, int]]:
    """Normalized, deduplicated tile plans for the candidate sweep."""
    seen: set[tuple[int, int, int]] = set()
    plans = []
    for bb in CANDIDATE_BB:
        for bw in CANDIDATE_BW:
            for bs in CANDIDATE_BS:
                plan = ops.fused_tile_plan(b, s, w, read_len=read_len, n=n,
                                           alphabet=alphabet, bb=bb, bw=bw,
                                           bs=bs)
                key = (plan["bb"], plan["bw"], plan["bs"])
                if key not in seen:
                    seen.add(key)
                    plans.append(plan)
    return plans


def _synthetic_inputs(space: HDSpace, batch: int, num_prototypes: int,
                      read_len: int, seed: int = 0):
    """Deterministic measurement inputs at the live shape."""
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(
        0, space.alphabet_size, (batch, read_len), dtype=np.int32))
    lengths = jnp.full((batch,), read_len, jnp.int32)
    im = item_memory.make_item_memory(space)
    tie = item_memory.make_tie_break(space)
    protos = jnp.asarray(rng.integers(
        0, 2 ** 32, (num_prototypes, space.num_words),
        dtype=np.uint32))
    return tokens, lengths, im, tie, protos


def _time_plan(plan: dict[str, int], args, space: HDSpace,
               trials: int) -> float:
    """Best-of-``trials`` wall time (s); first call compiles and warms."""
    tokens, lengths, im, tie, protos = args

    def run():
        return ops.fused_agreement(
            tokens, lengths, im, tie, protos, space,
            bb=plan["bb"], bw=plan["bw"], bs=plan["bs"])

    run().block_until_ready()
    best = float("inf")
    for _ in range(max(1, trials)):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def tune(space: HDSpace, *, batch: int, num_prototypes: int, read_len: int,
         path: str | os.PathLike | None = None, force: bool = False,
         trials: int = 2, budget: int = ops.VMEM_BUDGET,
         seed: int = 0) -> tuple[dict[str, int], bool]:
    """Pick (and cache) the fastest feasible tiles for the live shape.

    Returns ``(tiles, cached)`` where ``tiles`` is ``{"bb","bw","bs"}``
    and ``cached`` is True when the result came straight from the cache
    (no measurement ran — same key always yields the same tiles).
    """
    key = cache_key(batch, space.num_words, num_prototypes, space.dim)
    cache = load_cache(path)
    entry = cache.get(key)
    if entry is not None and not force:
        return {k: int(entry["tiles"][k]) for k in ("bb", "bw", "bs")}, True

    cost = dict(read_len=read_len, n=space.ngram,
                alphabet=space.alphabet_size)
    plans = candidate_plans(batch, num_prototypes, space.num_words, **cost)

    def held(p):  # VMEM a call holds: tile buffers + encoded-batch cache
        return ops.vmem_bytes(p, **cost) + p["cache_bytes"]

    ceiling = ops.vmem_ceiling()
    feasible = [p for p in plans
                if ops.vmem_bytes(p, **cost) <= budget and held(p) <= ceiling]
    if not feasible:  # degenerate budget: keep the leanest candidate
        feasible = [min(plans, key=held)]

    args = _synthetic_inputs(space, batch, num_prototypes, read_len, seed)
    timed = [(_time_plan(p, args, space, trials), p) for p in feasible]
    best_t, best = min(timed, key=lambda tp: tp[0])
    tiles = {k: best[k] for k in ("bb", "bw", "bs")}
    cache[key] = {
        "tiles": tiles,
        "time_s": best_t,
        "swept": len(feasible),
        "vmem_bytes": held(best),
    }
    save_cache(cache, path)
    return tiles, False


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Sweep fused-kernel tile shapes and cache the winner.")
    ap.add_argument("--smoke", action="store_true",
                    help="tune the CI smoke shape (dim=512, B=64, tiny "
                         "sweep) instead of a custom shape")
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--ngram", type=int, default=8)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--prototypes", type=int, default=128)
    ap.add_argument("--read-len", type=int, default=1024)
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--force", action="store_true",
                    help="re-measure even on a cache hit")
    ap.add_argument("--out", default=None,
                    help="cache file (default: REPRO_AUTOTUNE_CACHE or "
                         f"{DEFAULT_CACHE})")
    args = ap.parse_args(argv)

    if args.smoke:
        # Matches benchmarks/smoke.py: SMOKE_SPACE + window/batch shape.
        space = HDSpace(dim=512, ngram=8, z_threshold=3.0)
        batch, protos, read_len = 64, 44, 1024
    else:
        space = HDSpace(dim=args.dim, ngram=args.ngram, z_threshold=3.0)
        batch, protos, read_len = args.batch, args.prototypes, args.read_len

    tiles, cached = tune(space, batch=batch, num_prototypes=protos,
                         read_len=read_len, path=args.out,
                         force=args.force, trials=args.trials)
    print(json.dumps({
        "key": cache_key(batch, space.num_words, protos, space.dim),
        "tiles": tiles,
        "cached": cached,
        "cache": str(cache_path(args.out)),
    }, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
