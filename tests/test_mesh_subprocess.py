"""Multi-device mesh tests, each in a subprocess with its own device count.

The main pytest process stays at 1 CPU device (per assignment: smoke tests
see 1 device); these scenarios need 8 host devices, so they run via
``python -c`` with XLA_FLAGS set only in the child environment.
"""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

# ``jax.make_mesh`` builds Explicit axes by default; the GSPMD-style code
# under test (logical-axis rules + sharding propagation) targets Auto ones.
_PRELUDE = "from jax.sharding import AxisType\nAUTO = AxisType.Auto\n"


def _run(snippet: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", _PRELUDE + snippet], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_pjit_train_step_matches_single_device():
    _run("""
import dataclasses, jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed import param_specs, sharding
from repro.train import train_step as ts
cfg = dataclasses.replace(get_config('stablelm_3b', smoke=True), param_dtype='float32')
tc = ts.TrainConfig(loss_chunk=8, q_chunk=8, kv_chunk=8)
rng = np.random.default_rng(0)
batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (8,16)), jnp.int32),
         'labels': jnp.asarray(rng.integers(0, cfg.vocab, (8,16)), jnp.int32)}
state = ts.init_train_state(jax.random.key(0), cfg, tc)
step = ts.make_train_step(cfg, tc)
_, m1 = jax.jit(step)(jax.tree.map(lambda x: x, state), batch)
mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AUTO,) * 2)
rules = sharding.TRAIN_RULES
with sharding.use_rules(mesh, rules):
    st_sh = param_specs.state_shardings(state, mesh, rules)
    b_sh = param_specs.batch_shardings(batch, mesh, rules)
    st = jax.device_put(state, st_sh); bt = jax.device_put(batch, b_sh)
    _, m2 = jax.jit(step, in_shardings=(st_sh, b_sh))(st, bt)
d = abs(float(m1['loss']) - float(m2['loss'])) / abs(float(m1['loss']))
assert d < 1e-3, (float(m1['loss']), float(m2['loss']))
print('pjit parity OK', d)
""")


def test_decode_step_under_decode_rules():
    """Seq-sharded KV cache decode lowers, runs, and matches 1-device."""
    _run("""
import dataclasses, jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed import param_specs, sharding
from repro.models import lm
cfg = dataclasses.replace(get_config('deepseek_67b', smoke=True), param_dtype='float32')
params = lm.init_lm(jax.random.key(0), cfg)
rng = np.random.default_rng(0)
tok = jnp.asarray(rng.integers(0, cfg.vocab, (4,)), jnp.int32)
caches = lm.init_cache(cfg, 4, 32, dtype=jnp.float32)
logits1, _ = lm.decode_step(params, tok, caches, jnp.int32(0), cfg)
mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AUTO,) * 2)
rules = sharding.DECODE_RULES
with sharding.use_rules(mesh, rules):
    p_sh = param_specs.param_shardings(params, mesh, rules)
    c_sh = param_specs.cache_shardings(caches, mesh, rules)
    f = jax.jit(lambda p, t, c, pos: lm.decode_step(p, t, c, pos, cfg),
                in_shardings=(p_sh, None, c_sh, None))
    logits2, _ = f(jax.device_put(params, p_sh), tok,
                   jax.device_put(caches, c_sh), jnp.int32(0))
np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits2),
                           rtol=2e-3, atol=2e-3)
print('decode parity OK')
""")


def test_pipeline_and_compressed_psum():
    _run("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed import pipeline as pp
from repro.train import compression as comp
mesh = jax.make_mesh((4, 2), ('pod', 'data'))
rng = np.random.default_rng(0)
params = jnp.asarray(rng.normal(size=(4, 16, 16)) * 0.1, jnp.float32)
x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
got = pp.pipelined_apply(params, x, lambda w, xb: jnp.tanh(xb @ w),
                         mesh=mesh, axis='pod', num_microbatches=4)
want = x
for s in range(4):
    want = jnp.tanh(want @ params[s])
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

mesh2 = jax.make_mesh((8,), ('data',))
g = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
est = comp.init_state({'w': jnp.zeros((16,))})
def f(gl):
    out, _ = comp.compressed_psum({'w': gl[0]}, est, 'data')
    return out['w']
got = jax.jit(jax.shard_map(f, mesh=mesh2, in_specs=P('data'),
                            out_specs=P(), check_vma=False))(g)
np.testing.assert_allclose(np.asarray(got), np.asarray(g.mean(0)), atol=0.02)
print('pipeline + compressed psum OK')
""")


def test_hdc_profiler_sharded():
    """Demeter classification under pjit: reads over data, D over model."""
    _run("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import HDSpace, bitops
from repro.pipeline import ProfilerConfig, ProfilingSession
sp = HDSpace(dim=2048, ngram=8, z_threshold=3.0)
dm = ProfilingSession(ProfilerConfig(space=sp, window=1024, batch_size=32))
rng = np.random.default_rng(0)
genomes = {f's{i}': rng.integers(0, 4, 8000).astype(np.int32) for i in range(4)}
db = dm.build_refdb(genomes)
toks = jnp.asarray(rng.integers(0, 4, (32, 64)), jnp.int32)
lens = jnp.full((32,), 64, jnp.int32)
q = dm.encode_reads(toks, lens)
res1 = dm.classify_queries(q, db)
mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(AUTO,) * 2)
qs = jax.device_put(q, NamedSharding(mesh, P('data', 'model')))
res2 = dm.classify_queries(qs, db)
np.testing.assert_array_equal(np.asarray(res1.scores), np.asarray(res2.scores))
print('sharded HDC classify OK')
""")
