"""Ahead-of-time compiles of the profiler's kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at the production width
(``PROD_CONFIG``: D=40960, n=16, window 8192, batch 4096) with the tile
plan the ``pallas_fused`` backend picks, and compiles it with the TPU
compiler for a v5e that is described, not attached.  This catches what
interpret mode cannot — lowering gaps, unaligned slices, VMEM overruns.
The topology is described inside a fixture, so collection never loads
the TPU library and a machine that cannot describe it skips.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from benchmarks.common import PROD_CONFIG
from repro.kernels import (am_matmul, fused_profile, hamming_am, hdc_encoder,
                           ops)

SPACE = PROD_CONFIG.space
W = SPACE.num_words
BATCH = PROD_CONFIG.batch_size
#: AFS20 at window 8192: 20 genomes of 600 kbp, 74 windows each.
PROTOTYPES = 1480
GENOME_WINDOWS = 74
READ_LEN = 150


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tiles():
    from repro.pipeline.fused import PallasFusedBackend
    return PallasFusedBackend(PROD_CONFIG).tiles


def _compile(fn, *shapes):
    """Lower + compile ``fn`` for the described chip; returns the program."""
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("rows,length", [
    (GENOME_WINDOWS, PROD_CONFIG.window),   # RefDB build: one genome
    (BATCH, READ_LEN),                      # a read batch
], ids=["window", "read"])
def test_hdc_encoder_compiles(one_chip, rows, length):
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    rows = -(-rows // 8) * 8                # ops.hdc_encode pads to 8
    compiled = _compile(
        functools.partial(hdc_encoder.hdc_encode, n=SPACE.ngram,
                          bw=min(128, W), interpret=False),
        sds((rows, length), jnp.int32), sds((rows, 1), jnp.int32),
        sds((SPACE.ngram, 4, W), jnp.uint32), sds((1, W), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()


def _compile_fused(one_chip, plan, read_len, double_buffer):
    """Compile one fused call of ``plan`` for the described chip.

    The tile buffers stay within ``VMEM_BUDGET``; with the encoded-batch
    cache they stay within the scoped limit the call passes, and that
    limit within the chip's VMEM.
    """
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    tiles = ops.vmem_bytes(plan, read_len=read_len, n=SPACE.ngram)
    assert tiles <= ops.VMEM_BUDGET
    limit = plan["vmem_limit_bytes"] or ops.SCOPED_VMEM_DEFAULT
    assert tiles + plan["cache_bytes"] + ops.VMEM_HEADROOM <= limit \
        <= ops.V5E_VMEM_CAPACITY
    compiled = _compile(
        functools.partial(fused_profile.fused_profile, n=SPACE.ngram,
                          dim=SPACE.dim, bb=plan["bb"], bw=plan["bw"],
                          bs=plan["bs"], interpret=False,
                          double_buffer=double_buffer,
                          vmem_limit_bytes=plan["vmem_limit_bytes"]),
        sds((plan["b_call"], read_len), jnp.int32),
        sds((plan["b_call"], 1), jnp.int32),
        sds((SPACE.ngram, 4, plan["w_pad"]), jnp.uint32),
        sds((1, plan["w_pad"]), jnp.uint32),
        sds((plan["s_pad"], plan["w_pad"]), jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("double_buffer", [True, False],
                         ids=["dma", "pipeline"])
def test_fused_profile_compiles(one_chip, tiles, double_buffer):
    plan = ops.fused_tile_plan(BATCH, PROTOTYPES, W, read_len=READ_LEN,
                               n=SPACE.ngram, **tiles)
    assert plan["n_chunks"] == 2 and plan["cache_bytes"] > 0
    _compile_fused(one_chip, plan, READ_LEN, double_buffer)


@pytest.mark.parametrize("double_buffer", [True, False],
                         ids=["dma", "pipeline"])
@pytest.mark.parametrize("rows,bucket", [(BATCH, 256), (256, 4096)],
                         ids=["short", "ont"])
def test_fused_profile_compiles_at_cell_shapes(one_chip, tiles, rows, bucket,
                                               double_buffer):
    """The benchmark's cohorts: 4096 short reads in the 256 bucket (a
    20-MiB encoded-batch cache under a raised scoped limit) and 256 long
    reads in the 4096 bucket (a 1.25-MiB cache under the default)."""
    plan = ops.fused_tile_plan(rows, PROTOTYPES, W, read_len=bucket,
                               n=SPACE.ngram, **tiles)
    assert (plan["n_chunks"], plan["s_pad"], plan["n_calls"]) == (2, 1536, 1)
    assert plan["cache_bytes"] == rows * W * 4
    assert (plan["vmem_limit_bytes"] is None) == (rows == 256)
    _compile_fused(one_chip, plan, bucket, double_buffer)


@pytest.mark.parametrize("formulation", ["matmul", "packed"])
def test_am_kernels_compile(one_chip, formulation):
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    s_pad = -(-PROTOTYPES // 128) * 128     # ops.am_agreement's row pad
    if formulation == "matmul":
        fn = functools.partial(am_matmul.am_matmul, dim=SPACE.dim, bk=512,
                               interpret=False)
        shapes = (sds((BATCH, SPACE.dim), jnp.bfloat16),
                  sds((s_pad, SPACE.dim), jnp.bfloat16))
    else:
        fn = functools.partial(hamming_am.hamming_am, dim=SPACE.dim,
                               bw=min(256, W), interpret=False)
        shapes = (sds((BATCH, W), jnp.uint32), sds((s_pad, W), jnp.uint32))
    assert "tpu_custom_call" in _compile(fn, *shapes).as_text()


def test_sharded_fused_compiles_on_four_chips(topo, monkeypatch):
    """``sharded`` over ``pallas_fused`` on a 2x2 mesh: one program with
    the fused kernel per shard and one all-reduce (the species pmax)."""
    import dataclasses

    from repro.pipeline import resolve_backend

    # Code that asks jax.default_backend() sees the CPU here; steer the
    # kernel to native lowering and the backend onto the described mesh.
    monkeypatch.setattr(fused_profile, "interpret_default", lambda i: False)
    be = resolve_backend("sharded", dataclasses.replace(
        PROD_CONFIG, backend="sharded",
        backend_options={"base": "pallas_fused", "shards": 1}))
    mesh = Mesh(np.asarray(topo.devices), ("shard",))
    monkeypatch.setattr(be, "mesh", mesh)
    replicated = NamedSharding(mesh, P())
    compiled = jax.jit(be._tokens_scores_impl,
                       static_argnames=("num_species",)).lower(
        jax.ShapeDtypeStruct((BATCH, READ_LEN), jnp.int32,
                             sharding=replicated),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=replicated),
        jax.ShapeDtypeStruct((PROTOTYPES, W), jnp.uint32,
                             sharding=NamedSharding(mesh, P("shard", None))),
        jax.ShapeDtypeStruct((PROTOTYPES,), jnp.int32,
                             sharding=NamedSharding(mesh, P("shard"))),
        num_species=20).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
