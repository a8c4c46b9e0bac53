"""Time the fused kernel alone at the service's cohort shapes, by read-length mix.

    python benchmarks/fused_trip_timing.py [--root CHECKOUT] [--reps 12]

One ``ops.fused_agreement`` call at AFS20's widths (D=40960, n=16,
S=1,480 random prototypes) per mix of read lengths:

* ``full256``: 4,096 rows of 256 bases, so every batch tile runs the
  bucket's whole gram count (241);
* ``short150``: 4,096 rows of 150 bases in the 256-token bucket;
* ``open36``: 150-base reads on the first 36% of 4,096 rows, padding
  after (an open-loop cohort);
* ``full4096``: 256 rows of 4,096 bases (4,081 grams each).

Each mix is compiled and run once, then timed over ``--reps`` calls that
each end in ``block_until_ready``.  One JSON line per mix gives the
median, least and greatest seconds per call, ``ns_per_shape_gram`` (the
median over the ``B x (L - n + 1)`` row-grams of the padded shape) and a
digest of the agreement matrix.  The script uses nothing but
``ops.fused_agreement``, so it runs on any checkout of the repository:
``--root`` names the checkout whose ``src/`` is imported.  Two
checkouts are compared by running it from each on one chip, in turn;
equal digests mean equal agreements.  It exits non-zero unless JAX's
first device is a TPU; its last line is one JSON object of every mix.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]

S = 1480                     # AFS20 at 600 kbp: the cells' prototype count
COHORT = 4096                # rows of a short-read cohort


def mixes(np):
    """``name -> (B, L, lengths)`` of each cohort shape."""
    live = round(0.36 * COHORT)
    return {
        "full256": (COHORT, 256, np.full(COHORT, 256)),
        "short150": (COHORT, 256, np.full(COHORT, 150)),
        "open36": (COHORT, 256,
                   np.r_[np.full(live, 150), np.zeros(COHORT - live)]),
        "full4096": (256, 4096, np.full(256, 4096)),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", type=pathlib.Path, default=ROOT,
                    help="checkout whose src/ is timed (default: this one)")
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(args.root.resolve() / "src")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import item_memory
    from repro.core.hd_space import HDSpace
    from repro.kernels import ops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"fused_trip_timing: needs a TPU, JAX reports "
                 f"{dev.platform!r}")
    space = HDSpace(dim=40960, ngram=16)
    im = item_memory.make_item_memory(space)
    tie = item_memory.make_tie_break(space)
    rng = np.random.default_rng(args.seed)
    protos = jnp.asarray(rng.integers(0, 2 ** 32, (S, space.num_words),
                                      dtype=np.uint32))
    out = {"root": str(args.root), "device": dev.device_kind}
    for name, (b, length, lens) in mixes(np).items():
        toks = jnp.asarray(rng.integers(0, 4, (b, length), dtype=np.int32))
        lens = jnp.asarray(lens.astype(np.int32))

        def call():
            return ops.fused_agreement(toks, lens, im, tie, protos, space)

        agree = call().block_until_ready()
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            call().block_until_ready()
            times.append(time.perf_counter() - t)
        med = statistics.median(times)
        out[name] = {
            "median_s": med, "min_s": min(times), "max_s": max(times),
            "ns_per_shape_gram": 1e9 * med / (b * (length - space.ngram + 1)),
            "digest": hashlib.sha256(np.asarray(agree).tobytes()
                                     ).hexdigest()[:16]}
        print(name, json.dumps(out[name]), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
