"""Fig. 6 analogue: working data-structure size per profiler.

Paper claim: Demeter's HD-RefDB is ~33-36x smaller than Kraken2/MetaCache
structures on food-scale databases; the reduction is what makes the
in-memory accelerator feasible (the whole AM fits in PCM arrays / VMEM).

The sharded deployment extends the claim: splitting the prototype axis
over N devices leaves ``memory.demeter.bytes_per_device.sN`` resident
per device (padded shard of prototypes + species tags, replicated genome
lengths), so ``memory.reduction_vs_*`` is reported both for the total
structure and against the per-device footprint at each shard count —
the number that decides whether a database fits one accelerator's HBM.

``memory.proto_stream.*`` reports the same structure as *traffic*: the
prototype-stream HBM bytes each kernel organization moves per profiled
read (the AM bytes Acc-Demeter never moves at all, PAPER.md §5) — the
±1 bf16 matmul operand, the bit-packed fused tile re-fetched per batch
tile (pre-PR-9), and the chunk-amortized fused slab — so the packing
and batch-amortization factors are visible side by side.
"""

from __future__ import annotations

from benchmarks import common
from repro.pipeline import per_device_bytes

#: Shard counts to report per-device footprints for (analytical — the
#: layout math of repro.pipeline.sharded, no mesh needed).
SHARD_COUNTS = (1, 2, 4, 8)


def run(community=None, emit=common.emit) -> dict:
    community = community or common.afs_small()
    sizes = {}
    demeter_db = None
    for pname, prof in common.make_profilers().items():
        if pname == "kraken2+bracken":
            continue
        if pname == "demeter":
            demeter_db = prof.build_refdb(community.genomes)
            sizes[pname] = demeter_db.memory_bytes()
        else:
            prof.build(community.genomes)
            sizes[pname] = prof.memory_bytes()
        emit(f"memory.{pname}.bytes", 0.0, str(sizes[pname]))
    for n in SHARD_COUNTS:
        bpd = per_device_bytes(demeter_db, n)
        sizes[f"demeter/device@{n}"] = bpd
        emit(f"memory.demeter.bytes_per_device.s{n}", 0.0, str(bpd))
    sizes["proto_stream"] = prototype_stream(demeter_db, emit=emit)
    for base in ("kraken2", "metacache", "clark"):
        ratio = sizes[base] / sizes["demeter"]
        emit(f"memory.reduction_vs_{base}", 0.0, f"{ratio:.1f}x")
        # the per-device extension of the paper's Fig. 6 ratio: how much
        # smaller one *shard* is than the (unsharded) baseline structure
        for n in SHARD_COUNTS[1:]:
            r = sizes[base] / sizes[f"demeter/device@{n}"]
            emit(f"memory.reduction_vs_{base}.per_device.s{n}", 0.0,
                 f"{r:.1f}x")
    return sizes


def prototype_stream(db, *, batch: int = 64, bb: int = 8,
                     emit=common.emit) -> dict:
    """Prototype-stream HBM bytes per read, per kernel organization.

    Three rows for the same database and batch:

      matmul_pm1_bf16         the AM streamed as its ±1 bf16 expansion
                              (2 bytes per HD bit), once per batch;
      fused_packed_per_tile   bit-packed uint32 tiles, but re-fetched
                              for every ``bb``-row batch tile — the
                              fused kernel's dataflow before the
                              chunk-axis grid (bytes ~ S*W*4/bb);
      fused_packed_amortized  the chunk-axis megakernel: each packed
                              ``(bs, W)`` slab fetched once per batch
                              (``fused_tile_plan`` padded shapes).

    The ratio of row 1 to row 2 is the packing factor; row 2 to row 3
    the batch-tile amortization factor.
    """
    from repro.kernels.ops import fused_tile_plan
    s, w = (int(x) for x in db.prototypes.shape)
    dim = w * 32
    plan = fused_tile_plan(batch, s, w, read_len=common.READ_LEN,
                           n=common.BENCH_SPACE.ngram, bb=bb)
    rows = {
        "matmul_pm1_bf16": s * dim * 2 / batch,
        "fused_packed_per_tile":
            plan["s_pad"] * plan["w_pad"] * 4 / plan["bb"],
        "fused_packed_amortized": plan["proto_bytes_per_call"] / batch,
    }
    for name, val in rows.items():
        emit(f"memory.proto_stream.{name}.bytes_per_read", 0.0,
             f"{val:.1f}")
    # The two factors, each isolated at a fixed cadence: bytes per
    # prototype row (±1 bf16 vs bit-packed), and slab fetches per batch
    # (once per bb-row tile vs once per batch).
    emit("memory.proto_stream.packing_factor", 0.0,
         f"{dim * 2 / (w * 4):.1f}x")
    emit("memory.proto_stream.amortization_factor", 0.0,
         f"{rows['fused_packed_per_tile'] / rows['fused_packed_amortized']:.1f}x")
    return rows


if __name__ == "__main__":
    run()
