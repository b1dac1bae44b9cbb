"""Dry-run machinery at reduced scale: sharding rules, spec sanitizer,
collective-bytes parser, and a subprocess mini dry-run on an 8-device mesh
(mirrors launch/dryrun.py without locking the main process to 512 devices).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.roofline.analysis import (Roofline, model_flops_6nd,
                                     parse_collectives)


def test_parse_collectives_known_hlo():
    hlo = """
  %ag = bf16[16,128,4096]{2,1,0} all-gather(%x), replica_groups={{0,1}}
  %ar = f32[1024]{0} all-reduce(%y), to_apply=%add
  %rs = f32[64,32]{1,0} reduce-scatter(%z), dimensions={0}
  %a2a = (f32[8,16]{1,0}) all-to-all(%w)
  %cp = bf16[256]{0} collective-permute(%v), source_target_pairs={{0,1}}
  %ags = bf16[4,4]{1,0} all-gather-start(%q)
"""
    st = parse_collectives(hlo)
    assert st.counts["all-gather"] == 2
    assert st.counts["all-reduce"] == 1
    assert st.counts["reduce-scatter"] == 1
    assert st.counts["all-to-all"] == 1
    assert st.counts["collective-permute"] == 1
    assert st.bytes_by_kind["all-gather"] == 16 * 128 * 4096 * 2 + 4 * 4 * 2
    assert st.bytes_by_kind["all-reduce"] == 1024 * 4 * 2  # 2x for AR
    assert st.bytes_by_kind["collective-permute"] == 256 * 2


def test_roofline_terms_and_dominance():
    rl = Roofline(flops=197e12, hbm_bytes=819e9 * 2, coll_bytes=50e9 * 0.5,
                  model_flops=100e12)
    assert rl.t_compute == pytest.approx(1.0)
    assert rl.t_memory == pytest.approx(2.0)
    assert rl.t_collective == pytest.approx(0.5)
    assert rl.dominant == "memory"
    assert rl.useful_ratio == pytest.approx(100 / 197, rel=1e-3)
    assert model_flops_6nd(10, 5, "train") == 300
    assert model_flops_6nd(10, 5, "infer") == 100


def test_sanitize_spec_relocation():
    import numpy as np
    os.environ.setdefault("XLA_FLAGS", "")
    from repro.dist.sharding import sanitize_spec
    mesh = make_mesh((1,), ("model",))

    class M:  # fake mesh with model=16 for divisibility logic
        axis_names = ("model",)
        shape = {"model": 16}
    # 20 heads not divisible by 16 -> moved to hd=128
    spec = sanitize_spec(P(None, None, "model", None), (40, 2560, 20, 128), M)
    assert tuple(spec) == (None, None, None, "model")
    # nothing divisible -> dropped
    spec = sanitize_spec(P("model"), (20,), M)
    assert tuple(spec) == ()
    # divisible stays
    spec = sanitize_spec(P(None, "model"), (5, 32), M)
    assert tuple(spec) == (None, "model")


_MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config, get_shape
from repro.configs.base import InputShape
from repro.core.bsp import make_bsp_step
from repro.core.exchanger import get_exchanger
from repro.core.gspmd import make_gspmd_step, fsdp_state_shardings
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 state_shardings)
from repro.launch.mesh import make_mesh
from repro.launch.specs import (abstract_cache, abstract_state,
                                decode_batch_specs, train_batch_specs, sds)
from repro.models import build_model
from repro.optim import sgd_momentum, constant
from repro.roofline.analysis import analyze

mesh = make_mesh((4, 2), ("data", "model"))
jax.set_mesh(mesh)
out = {}
for arch in ["llama3.2-1b", "mamba2-1.3b", "deepseek-v2-lite-16b"]:
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    opt = sgd_momentum(weight_decay=0.0)
    shape = InputShape("tiny_train", 64, 8, "train")
    state = abstract_state(model, opt)
    batch = train_batch_specs(cfg, shape)
    for mode in ["bsp", "zero1"]:
        if mode == "bsp":
            step = make_bsp_step(model, opt, get_exchanger("asa"),
                                 constant(0.01), mesh, data_axes=("data",))
            sh = state_shardings(mesh, state)
        else:
            step = make_gspmd_step(model, opt, constant(0.01), mesh)
            sh = fsdp_state_shardings(mesh, state)
        def fn(s, b, seed, _step=step):
            return _step(s, b, jax.random.wrap_key_data(seed))
        lowered = jax.jit(fn, in_shardings=(
            sh, batch_shardings(mesh, batch),
            NamedSharding(mesh, P()))).lower(state, batch,
                                             sds((2,), jnp.uint32))
        compiled = lowered.compile()
        res = analyze(compiled)
        out[f"{arch}:{mode}"] = {
            "ok": True,
            "colls": res["collectives"]["counts"],
            "coll_bytes": res["roofline"]["coll_bytes"],
        }
    # decode
    dshape = InputShape("tiny_decode", 64, 8, "decode")
    cache = abstract_cache(model, cfg, dshape)
    dbatch = decode_batch_specs(cfg, dshape)
    def dfn(params, cache, b, pos):
        lg, nc = model.decode_step(params, cache, b, pos, seq_len=64)
        return jnp.argmax(lg[:, -1], -1), nc
    from repro.dist.sharding import param_shardings
    params = state["params"]
    lowered = jax.jit(dfn, in_shardings=(
        param_shardings(mesh, params),
        cache_shardings(mesh, cache, 8),
        batch_shardings(mesh, dbatch),
        NamedSharding(mesh, P()))).lower(params, cache, dbatch,
                                         sds((), jnp.int32))
    compiled = lowered.compile()
    out[f"{arch}:decode"] = {"ok": True}
print("RESULTS_JSON:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mini_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _MINI_DRYRUN], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON:"):
            return json.loads(line[len("RESULTS_JSON:"):])
    raise AssertionError(proc.stdout[-2000:])


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-1.3b",
                                  "deepseek-v2-lite-16b"])
def test_mini_dryrun_lowers(mini_results, arch):
    assert mini_results[f"{arch}:bsp"]["ok"]
    assert mini_results[f"{arch}:zero1"]["ok"]
    assert mini_results[f"{arch}:decode"]["ok"]


def test_bsp_path_emits_asa_collectives(mini_results):
    """The ASA exchanger must appear as all-to-all + all-gather in HLO."""
    colls = mini_results["llama3.2-1b:bsp"]["colls"]
    assert colls.get("all-to-all", 0) >= 1, colls
    assert colls.get("all-gather", 0) >= 1, colls
