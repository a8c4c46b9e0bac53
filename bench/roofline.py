"""Peaks of the chip and the least work Demeter profiling needs.

Peaks are Google Cloud's published figures for one TPU v5e chip (Cloud
TPU documentation, "TPU v5e", system architecture table): 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.  They are keyed by the
``device_kind`` JAX reports; a device that is not listed is an error.

The work is counted from the definition of profiling, whatever
implements it, for the live reads only (padding rows and padded
positions are waste, and lower the share):

- encode: ``3 * D * g`` operations for a read of ``g = L - n + 1`` grams,
  an incremental bind of two XORs plus one bundle add per gram and bit;
- search: ``2 * D * S`` operations per read, one compare and one add per
  bit and prototype;
- bytes: one per base read, ``S * D / 8`` of packed prototypes once per
  call, and ``4 * species`` of result per read.

No VPU peak is published, so the operations are charged against the
int8 peak, the highest integer rate the chip publishes.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"int_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def call_work(lengths, *, dim: int, ngram: int, prototypes: int,
              species: int) -> tuple[float, float]:
    """``(ops, bytes)`` one fused call needs for its live reads."""
    live = [int(x) for x in lengths if x > 0]
    grams = sum(max(x - ngram + 1, 0) for x in live)
    ops = 3.0 * dim * grams + 2.0 * dim * prototypes * len(live)
    nbytes = (float(sum(live)) + prototypes * dim / 8
              + 4.0 * species * len(live))
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, device_kind: str
                  ) -> tuple[float, str]:
    """The larger of compute and memory time, and which one binds."""
    p = peaks(device_kind)
    t_ops = ops / p["int_ops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
