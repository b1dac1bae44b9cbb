"""``correct`` across four virtual CPU devices: a sound run of a four-chip
cell passes, and one with the exchange between chips left out fails."""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

SCRIPT = r"""
import json, sys, tempfile
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import cbtiny
cbtiny.use_harness()
import jax
from repro.core import exchanger

bench = cbtiny.make(Path(tempfile.mkdtemp()), chips=4)
sound = cbtiny.run(bench)


def local_reduce_scatter(self, grads, axis, *, sum_fn=None, bucket_bytes=0,
                         plan=None, raw=False):
    # each chip keeps its own gradient's shard: nothing crosses chips
    plan = plan or self.plan_for(grads, axis, bucket_bytes)
    flats, smalls, _ = self.pack(grads, plan)
    idx = jax.lax.axis_index(axis)
    shards = [jax.lax.dynamic_slice_in_dim(f, idx * b.shard_len, b.shard_len)
              for f, b in zip(flats, plan.buckets)]
    return {"shards": shards, "full": [s.astype("float32") for s in smalls]}, plan


exchanger.Exchanger.reduce_scatter = local_reduce_scatter
broken = cbtiny.run(bench)
print(json.dumps({"sound": sound["correct"], "sound_checks": sound["checks"],
                  "broken": broken["correct"],
                  "broken_checks": broken["checks"]}))
"""


def test_exchange_left_out_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(HERE)],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"], out["sound_checks"]
    assert not out["broken"], out["broken_checks"]
