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
# under test (sharding propagation) targets Auto ones.
_PRELUDE = "from jax.sharding import AxisType\nAUTO = AxisType.Auto\n"


def _run(snippet: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", _PRELUDE + snippet], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


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
