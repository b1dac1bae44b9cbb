"""Exchanger equivalence, and the bytes each strategy puts on the wire,
on an 8-device host mesh.

Needs >1 device, so runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (keeps the main pytest
process at 1 device per the dry-run contract).
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch.mesh import make_mesh

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.exchanger import EXCHANGERS, get_exchanger
from repro.kernels import ops
from repro.launch.mesh import make_mesh

results = {}

def run_mesh(mesh, axes, tag):
    jax.set_mesh(mesh)
    k = int(np.prod([mesh.shape[a] for a in axes]))
    key = jax.random.key(0)
    grads = {
        "big": jax.random.normal(key, (k, 1000, 3)) * 2,          # stacked
        "mat": jax.random.normal(jax.random.fold_in(key, 1), (k, 33, 7)),
        "small": jax.random.normal(jax.random.fold_in(key, 2), (k, 5)),
        "odd": jax.random.normal(jax.random.fold_in(key, 3), (k, 1237)),
    }
    # reference: mean over the worker axis
    want = {n: np.asarray(v.mean(0)) for n, v in grads.items()}
    ax = axes[0] if len(axes) == 1 else tuple(axes)

    for name in ["ar", "asa", "asabf16", "asa16", "asa8", "ring", "ring16",
                 "hier", "hier16"]:
        ex = get_exchanger(name)
        def f(gs):
            per = {n: v[0] for n, v in gs.items()}
            out = ex.exchange(per, ax)
            return {n: v[None] for n, v in out.items()}
        got = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
            axis_names=frozenset(axes), check_vma=False))(grads)
        tol = {"ar": 1e-6, "asa": 1e-6, "ring": 1e-6, "hier": 1e-6,
               "asabf16": 2e-2, "asa16": 2e-3, "ring16": 5e-3,
               "hier16": 2e-3, "asa8": 5e-2}[name]
        errs = {}
        for n in grads:
            g0 = np.asarray(got[n][0])
            scale = np.abs(want[n]).max() + 1e-9
            errs[n] = float(np.abs(g0 - want[n]).max() / scale)
        results[f"{tag}:{name}"] = {"errs": errs, "tol": tol,
                                    "ok": all(e <= tol for e in errs.values())}

    # pallas chunk_sum plugged into ASA
    ex = get_exchanger("asa")
    def f2(gs):
        per = {n: v[0] for n, v in gs.items()}
        out = ex.exchange(per, ax, sum_fn=ops.chunk_sum)
        return {n: v[None] for n, v in out.items()}
    got = jax.jit(jax.shard_map(
        f2, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
        axis_names=frozenset(axes), check_vma=False))(grads)
    err = max(float(np.abs(np.asarray(got[n][0]) - want[n]).max()
                    / (np.abs(want[n]).max() + 1e-9)) for n in grads)
    results[f"{tag}:asa+pallas_chunk_sum"] = {"errs": {"max": err},
                                              "tol": 1e-6,
                                              "ok": err <= 1e-6}

run_mesh(make_mesh((8,), ("data",)), ("data",), "dp8")
run_mesh(make_mesh((2, 4), ("pod", "data")), ("pod", "data"), "pod2x4")

# what each strategy puts on the wire, read from the LOWERED module: the
# CPU compiler widens bf16 collectives to f32, so the CPU-compiled text
# would report asabf16 at asa's bytes
from repro.core.exchanger import make_rs_plan, wire_summary
from repro.roofline.analysis import parse_collectives
mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
per_rank = {"w1": (256, 512), "w2": (512, 128), "b": (128,)}
grads = {n: jax.ShapeDtypeStruct((8, *s), jnp.float32)
         for n, s in per_rank.items()}
plan = make_rs_plan({n: jax.ShapeDtypeStruct(s, jnp.float32)
                     for n, s in per_rank.items()}, 8)
for name in ["ar", "asa", "asa16", "asabf16", "asa8", "ring", "ring16",
             "none"]:
    ex = get_exchanger(name)
    def f(gs):
        per = {n: v[0] for n, v in gs.items()}
        return {n: v[None] for n, v in ex.exchange(per, "data").items()}
    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"),
                               axis_names=frozenset({"data"}),
                               check_vma=False))
    st = parse_collectives(fn.lower(grads).as_text(dialect="hlo"))
    results[f"wire:{name}"] = {
        "counts": st.counts, "hlo_bytes": st.total_bytes,
        "model_bytes": wire_summary(ex, plan)["bytes_per_exchange"],
        "buckets": plan.num_buckets, "small": len(plan.small)}
print("RESULTS_JSON:" + json.dumps(results))
"""


def _run_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON:"):
            return json.loads(line[len("RESULTS_JSON:"):])
    raise AssertionError(f"no results in output: {proc.stdout[-2000:]}")


_results_cache = {}


@pytest.fixture(scope="module")
def results():
    if not _results_cache:
        _results_cache.update(_run_subprocess())
    return _results_cache


@pytest.mark.parametrize("strategy", [
    "ar", "asa", "asabf16", "asa16", "asa8", "ring", "ring16", "hier",
    "hier16", "asa+pallas_chunk_sum"])
def test_strategy_matches_mean_dp8(results, strategy):
    r = results[f"dp8:{strategy}"]
    assert r["ok"], f"{strategy}: errors {r['errs']} > tol {r['tol']}"


@pytest.mark.parametrize("strategy", ["ar", "asa", "hier", "hier16"])
def test_strategy_matches_mean_multipod(results, strategy):
    r = results[f"pod2x4:{strategy}"]
    assert r["ok"], f"{strategy}: errors {r['errs']} > tol {r['tol']}"


_ASA_KINDS = {"all-to-all", "all-gather", "all-reduce"}
_RING_KINDS = {"collective-permute", "all-reduce"}
_WIRE_KINDS = {"ar": {"all-reduce"}, "asa": _ASA_KINDS, "asa16": _ASA_KINDS,
               "asabf16": _ASA_KINDS, "asa8": _ASA_KINDS,
               "ring": _RING_KINDS, "ring16": _RING_KINDS, "none": set()}
# narrow-wire strategies: (the fp32 strategy of their family, bytes ratio)
_NARROWING = {"asa16": ("asa", 2), "asabf16": ("asa", 2), "asa8": ("asa", 4),
              "ring16": ("ring", 2)}


@pytest.mark.parametrize("strategy", list(_WIRE_KINDS))
def test_lowered_exchange_moves_what_wire_summary_counts(results, strategy):
    """``wire_summary`` (the ``exchange/bytes_wire`` counter's source)
    against the bytes the lowered program sends, over two bucketed leaves
    and one small one on 8 ranks. ``parse_collectives`` counts whole
    result shapes where a rank sends (k-1)/k of them, so the module may
    exceed the model by at most k/(k-1)."""
    r = results[f"wire:{strategy}"]
    assert (r["buckets"], r["small"]) == (2, 1)
    assert set(r["counts"]) == _WIRE_KINDS[strategy], r["counts"]
    if strategy not in ("ar", "none"):
        # the small leaf is psum'd whole; every bucket takes the wire path
        assert r["counts"]["all-reduce"] == 1, r["counts"]
    k = 8
    model, hlo = r["model_bytes"], r["hlo_bytes"]
    assert model <= hlo <= model * k / (k - 1) * 1.001, (model, hlo)
    if strategy in _NARROWING:
        # the wire dtype really narrows what is sent (the bound above holds
        # for any dtype, since the model reads the same transfer_dtype)
        wide, ratio = _NARROWING[strategy]
        assert results[f"wire:{wide}"]["hlo_bytes"] / hlo == pytest.approx(
            ratio, rel=0.01), (wide, hlo)


def test_bucketed_exchange_single_device():
    """Bucketing packs/unpacks losslessly (k=1 host: exchange == identity
    mean over a single worker)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.exchanger import get_exchanger

    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    grads = {"a": jnp.arange(100.0), "b": jnp.ones((7, 3)),
             "c": jnp.full((2049,), 2.0)}
    ex = get_exchanger("asa")

    def f(gs):
        return ex.exchange(gs, "data", bucket_bytes=1 << 10)

    got = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                                axis_names=frozenset({"data"}),
                                check_vma=False))(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(grads[k]),
                                   rtol=1e-6)
