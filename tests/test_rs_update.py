"""RS->update->AG (sharded fused update) equivalence.

Property: training with ``sharded_update=True`` (and with
``overlap="buckets"``) is bitwise/tolerance-equivalent to the existing
exchange-then-update path for every strategy on an 8-way host mesh — with
deliberately non-divisible leaf sizes so the pad/shard/unpad plumbing is
exercised. Lossy-wire strategies (fp16/int8) differ only by where the
rounding lands (reduced gradient vs gathered parameters), so they get
per-strategy tolerances. The overlapped step's compiled schedule must
put each microbatch's reduce-scatter beside the next one's backward dots.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(keeps the main pytest process at 1 device per the dry-run contract).
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import (get_exchanger, init_sharded_train_state,
                        init_train_state, make_bsp_step)
from repro.launch.mesh import make_mesh
from repro.models.registry import Model
from repro.optim import adamw, constant, sgd_momentum

# leaf sizes chosen to be non-divisible by k=8 and to cover all plan
# classes: bucketed 2-D (2541, 3080), bucketed 1-D (1237), small (5, 17)
def init(key):
    r = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s) * 0.05
    return {"w1": r(0, (33, 77)), "w2": r(1, (77, 40)), "b1": r(2, (1237,)),
            "small": r(3, (5,)), "norm": r(4, (17,))}

def loss_fn(params, batch, rng=None, unroll=False):
    h = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
    loss = (0.5 * jnp.mean(jnp.square(h))
            + 1e-3 * jnp.sum(jnp.square(params["b1"]))
            + 1e-3 * jnp.sum(jnp.square(params["norm"]))
            + jnp.sum(jnp.square(params["small"])))
    return loss, {"loss": loss, "aux": jnp.zeros(())}

model = Model(cfg=None, init=init, loss_fn=loss_fn, forward=None)
mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
batch = {"x": np.random.default_rng(0).normal(0, 1, (32, 33)).astype(
    np.float32)}
STEPS = 3
results = {}


def run(opt, strat, **kw):
    sharded = kw.get("sharded_update") or kw.get("overlap")
    if sharded:
        state = init_sharded_train_state(
            model, opt, jax.random.key(0), mesh,
            bucket_bytes=kw.get("bucket_bytes", 0))
    else:
        state = init_train_state(model, opt, jax.random.key(0))
    step = jax.jit(make_bsp_step(model, opt, get_exchanger(strat),
                                 constant(0.05), mesh, **kw))
    for i in range(STEPS):
        state = one_step(step, state, batch, jax.random.key(100 + i))
    return state


def one_step(step, state, batch, key):
    # one program in flight at a time: the CPU backend runs all 8 virtual
    # devices' collectives on one shared thread pool (8 threads here), and
    # two programs in flight at once, even two steps in a row, can each
    # hold part of it while waiting for the rest (a rendezvous timeout
    # under load)
    return jax.block_until_ready(step(state, batch, key)[0])


def rel_err(a, b):
    errs = {}
    for k in a["params"]:
        x = np.asarray(a["params"][k], np.float32)
        y = np.asarray(b["params"][k], np.float32)
        errs[k] = float(np.abs(x - y).max() / (np.abs(y).max() + 1e-9))
    return errs


sgd = sgd_momentum(momentum=0.9, weight_decay=5e-4)
for strat in ["ar", "asa", "asa16", "asa8", "ring", "hier"]:
    base = run(sgd, strat)
    for tag, kw in [
        ("sharded", dict(sharded_update=True)),
        ("sharded+buckets", dict(sharded_update=True, bucket_bytes=4096)),
        ("overlap", dict(overlap="buckets", microbatches=4)),
    ]:
        if tag == "overlap":
            base_cmp = run(sgd, strat, microbatches=4)
        else:
            base_cmp = base
        got = run(sgd, strat, **kw)
        errs = rel_err(got, base_cmp)
        fin = all(bool(jnp.isfinite(l).all())
                  for l in jax.tree.leaves(got["opt"]))
        results[f"{strat}:{tag}"] = {"errs": errs, "finite_opt": fin}

# sharded path must also shard the momentum: global bucket state is
# (k * shard_len,) and the per-bucket shards reassemble the replicated
# momentum of the baseline path (fp32 strategy => tight tolerance)
st = run(sgd, "asa", sharded_update=True)
m0 = np.asarray(st["opt"]["buckets"][0]["m"])
results["momentum_shape"] = {"shape": list(m0.shape)}

# adamw flat path
ad = adamw(weight_decay=0.0)
base = run(ad, "asa")
got = run(ad, "asa", sharded_update=True)
results["adamw:sharded"] = {"errs": rel_err(got, base),
                            "finite_opt": True}

# sub-ulp updates must accumulate in the fp32 master shard: with lr*grad
# ~2% of the fp16 ulp at w=1.0, a path that fed the fp16 param gather back
# into the update would never move the weights at all
def init2(key):
    return {"w": jnp.ones((2000,), jnp.float32)}

def loss2(params, batch, rng=None, unroll=False):
    loss = 0.1 * jnp.mean(params["w"]) + 0.0 * jnp.mean(batch["x"])
    return loss, {"loss": loss, "aux": jnp.zeros(())}

m2 = Model(cfg=None, init=init2, loss_fn=loss2, forward=None)
opt2 = sgd_momentum(momentum=0.0, weight_decay=0.0)
st2 = init_sharded_train_state(m2, opt2, jax.random.key(0), mesh)
step2 = jax.jit(make_bsp_step(m2, opt2, get_exchanger("asa16"),
                              constant(0.2), mesh, sharded_update=True))
for i in range(100):
    st2 = one_step(step2, st2, batch, jax.random.key(i))
results["master_accum"] = {
    "delta": float(1.0 - np.asarray(st2["params"]["w"]).mean())}

# nesterov + fused kernel path agree with the unfused flat update
# (fuse forced on: auto mode keeps it off in Pallas interpreter mode)
sgd_n = sgd_momentum(momentum=0.9, weight_decay=5e-4, nesterov=True)
a = run(sgd_n, "asa16", sharded_update=True, fuse_rs_update=True)
b = run(sgd_n, "asa16", sharded_update=True, fuse_rs_update=False)
results["fused_vs_flat"] = {"errs": rel_err(a, b)}

# the compiled step's schedule (asa16, 4 microbatches): only the overlapped
# body holds a reduce-scatter that no backward dot of its iteration feeds.
# Every gradient here passes through a dot: a leaf whose gradient does not
# (b1 above) is loop-invariant, XLA hoists its reduce-scatter out of the
# scan, and that collective reads as independent whatever the schedule.
from repro.roofline.analysis import overlap_evidence

def init3(key):
    return {k: v for k, v in init(key).items() if k in ("w1", "w2")}

def loss3(params, batch, rng=None, unroll=False):
    loss = 0.5 * jnp.mean(jnp.square(
        jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]))
    return loss, {"loss": loss, "aux": jnp.zeros(())}

m3 = Model(cfg=None, init=init3, loss_fn=loss3, forward=None)
for bb in (0, 1 << 20):
    st = init_sharded_train_state(m3, sgd, jax.random.key(0), mesh,
                                  bucket_bytes=bb)
    for mode, kw in (("serial", dict(sharded_update=True)),
                     ("overlap", dict(overlap="buckets"))):
        step = jax.jit(make_bsp_step(
            m3, sgd, get_exchanger("asa16"), constant(0.05), mesh,
            microbatches=4, bucket_bytes=bb, **kw))
        txt = step.lower(st, batch, jax.random.key(0)).compile().as_text()
        results[f"evidence:{mode}:{bb}"] = overlap_evidence(txt)

# Shaped buckets: a one-leaf bucket whose leading dim k divides is
# exchanged in its leaf's own shape. Leaves: "a" (64, 48) and "d" (48, 33)
# divide by 1 and 4, "c" (33, 40) only by 1, so at k = 4 it stays flat.
from repro import telemetry
from repro.core.exchanger import Exchanger, default_chunk_sum, make_rs_plan

telemetry.set_enabled(True)


def init5(key):
    r = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s) * 0.1
    return {"a": r(0, (64, 48)), "c": r(1, (33, 40)), "d": r(2, (48, 33))}


def loss5(params, batch, rng=None, unroll=False):
    h = jnp.tanh(jnp.tanh(batch["x"] @ params["a"]) @ params["d"])
    loss = 0.5 * jnp.mean(jnp.square(h @ params["c"]))
    return loss, {"loss": loss, "aux": jnp.zeros(())}


m5 = Model(cfg=None, init=init5, loss_fn=loss5, forward=None)
batch5 = {"x": np.random.default_rng(1).normal(0, 1, (32, 64)).astype(
    np.float32)}


def flat_receives(ex, tree, plan, raw, ax):
    # the flat receive of each bucket, by the path each strategy took
    # before buckets kept their leaf's shape: the reference
    inv_k = 1.0 / plan.k
    out = []
    for f in Exchanger.pack(tree, plan)[0]:
        c = f.reshape(plan.k, -1)
        if ex.kind == "ar":
            out.append(jax.lax.psum_scatter(f, ax, scatter_dimension=0,
                                            tiled=True) * inv_k)
            continue
        if ex.transfer_dtype == jnp.int8:
            scale = jnp.max(jnp.abs(c), axis=1, keepdims=True) / 127.0 + 1e-12
            q = jnp.clip(jnp.round(c / scale), -127, 127).astype(jnp.int8)
            r = jax.lax.all_to_all(q, ax, 0, 0)
            rs = jax.lax.all_to_all(scale, ax, 0, 0)
            out.append((r, rs) if raw else
                       jnp.sum(r.astype(jnp.float32) * rs, axis=0) * inv_k)
            continue
        if ex.transfer_dtype is not None:
            c = c.astype(ex.transfer_dtype)
        r = jax.lax.all_to_all(c, ax, 0, 0)
        out.append(r if raw else default_chunk_sum(r) * inv_k)
    return out


def flat_reduce_scatter(self, grads, axis, *, sum_fn=default_chunk_sum,
                        bucket_bytes=0, plan=None, raw=False):
    # Exchanger.reduce_scatter on flat buckets (float wires only)
    plan = plan or self.plan_for(grads, axis, bucket_bytes)
    full = [jax.lax.psum(s.astype(jnp.float32), axis) * (1.0 / plan.k)
            for s in self.pack(grads, plan)[1]]
    recv = flat_receives(self, grads, plan, raw, axis)
    if raw:
        return {"chunks": recv, "scales": [], "full": full}, plan
    return {"shards": recv, "full": full}, plan


def bits_equal(x, y):
    x, y = np.asarray(x), np.asarray(y)
    return x.shape == y.shape and x.dtype == y.dtype and x.tobytes() == y.tobytes()


from jax.sharding import PartitionSpec as P
grads5 = jax.tree.map(lambda l: np.stack([np.asarray(l) * (1.0 + 0.37 * i)
                                          for i in range(8)]),
                      init5(jax.random.key(3)))
for k in (1, 4):
    mesh_k = make_mesh((k,), ("data",), devices=jax.devices()[:k])
    # rank i's gradient is grads5[...][i]
    g_k = jax.tree.map(lambda l: l[:k].reshape(k * l.shape[1], *l.shape[2:]),
                       grads5)
    plan5 = make_rs_plan(init5(jax.random.key(0)), k)
    results[f"shaped_plan:{k}"] = [b.shaped for b in plan5.buckets]
    for strat, raw in (("asa", False), ("asa16", False), ("asa16", True),
                       ("asa8", False), ("asa8", True), ("ar", False)):
        ex = get_exchanger(strat)

        def both(g, ex=ex, raw=raw):
            res, _ = ex.reduce_scatter(g, "data", plan=plan5, raw=raw)
            if raw:
                new = [c.reshape(k, -1) for c in res["chunks"]]
                new = (list(zip(new, [s.reshape(k, 1)
                                      for s in res["scales"]]))
                       if res["scales"] else new)
            else:
                new = [s.reshape(-1) for s in res["shards"]]
            return new, flat_receives(ex, g, plan5, raw, "data")

        with jax.set_mesh(mesh_k):
            new, old = jax.jit(jax.shard_map(
                both, mesh=mesh_k, in_specs=P("data"), out_specs=P("data"),
                check_vma=False))(g_k)
        results[f"shaped_rs:{k}:{strat}:{'raw' if raw else 'sum'}"] = all(
            bits_equal(x, y) for x, y in zip(jax.tree.leaves(new),
                                             jax.tree.leaves(old)))

    # three overlapped steps on shaped buckets against the flat reference
    for mode, raw, opt in (("raw", True, sgd), ("flat_update", False, sgd),
                           ("flat_update_nowd", False,
                            sgd_momentum(momentum=0.9, weight_decay=0.0))):
        states = []
        for rs in (Exchanger.reduce_scatter, flat_reduce_scatter):
            orig = Exchanger.reduce_scatter
            Exchanger.reduce_scatter = rs
            try:
                with jax.set_mesh(mesh_k):
                    st = init_sharded_train_state(m5, opt, jax.random.key(0),
                                                  mesh_k)
                    step = jax.jit(make_bsp_step(
                        m5, opt, get_exchanger("asa16"), constant(0.05),
                        mesh_k, microbatches=4, overlap="buckets",
                        fuse_rs_update=raw))
                    for i in range(STEPS):
                        st = one_step(step, st, batch5,
                                      jax.random.key(100 + i))
                    states.append(st["opt"])
            finally:
                Exchanger.reduce_scatter = orig
        new, old = states
        pairs = list(zip(new["master"], old["master"])) + [
            (x["m"], y["m"]) for x, y in zip(new["buckets"], old["buckets"])]
        results[f"shaped_steps:{k}:{mode}"] = {
            "bitwise": all(bits_equal(x, y) for x, y in pairs),
            # largest gap in float32 ulps of the array's largest magnitude
            "ulps": max(float(np.abs(np.asarray(x) - np.asarray(y)).max()
                              / np.spacing(np.abs(np.asarray(y)).max()))
                        for x, y in pairs),
            "moved": bool(np.abs(np.asarray(new["buckets"][0]["m"])).max()
                          > 0)}

    # the gauges count the plan's shaped and flat buckets when a step is built
    for bb in (0, 1 << 20):
        make_bsp_step(m5, sgd, get_exchanger("asa16"), constant(0.05), mesh_k,
                      microbatches=4, overlap="buckets", bucket_bytes=bb)
        results[f"gauges:{k}:{bb}"] = [
            telemetry.metrics.get(f"exchange/{n}_buckets").value
            for n in ("shaped", "flat")]
print("RESULTS_JSON:" + json.dumps(results))
"""

_TOL = {"ar": 2e-6, "asa": 2e-6, "ring": 2e-6, "hier": 2e-6,
        "asa16": 3e-3, "asa8": 3e-2}


def _run_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
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


@pytest.mark.parametrize("strategy",
                         ["ar", "asa", "asa16", "asa8", "ring", "hier"])
@pytest.mark.parametrize("mode", ["sharded", "sharded+buckets", "overlap"])
def test_sharded_update_matches_exchange_then_update(results, strategy,
                                                     mode):
    r = results[f"{strategy}:{mode}"]
    tol = _TOL[strategy]
    bad = {k: e for k, e in r["errs"].items() if e > tol}
    assert not bad, f"{strategy}:{mode} errors {bad} > tol {tol}"
    assert r["finite_opt"]


def test_momentum_state_is_sharded(results):
    # leaves flatten alphabetically: the first bucket packs b1 (1237):
    # shard_len = ceil(1237/8) = 155, global extent 155 * 8
    assert results["momentum_shape"]["shape"] == [155 * 8]


def test_sub_ulp_updates_accumulate_in_master(results):
    # 100 steps x 1e-5/step = 1e-3 expected drop; without fp32 master
    # weights the fp16 gather would round every step away (delta == 0)
    assert results["master_accum"]["delta"] > 5e-4


def test_adamw_sharded_matches(results):
    errs = results["adamw:sharded"]["errs"]
    assert max(errs.values()) <= 2e-6, errs


def test_fused_kernel_matches_flat_update(results):
    errs = results["fused_vs_flat"]["errs"]
    assert max(errs.values()) <= 1e-6, errs


@pytest.mark.parametrize("bucket_bytes", [0, 1 << 20])
@pytest.mark.parametrize("mode", ["serial", "overlap"])
def test_overlap_puts_reduce_scatter_beside_backward_dots(results, mode,
                                                          bucket_bytes):
    """``overlap="buckets"`` reduce-scatters microbatch i-1's gradients in
    the scan body, beside microbatch i's backward dots; the serial sharded
    update issues every reduce-scatter after the loop."""
    ev = results[f"evidence:{mode}:{bucket_bytes}"]
    assert ev["comm_independent_of_dots"] == (mode == "overlap"), ev


def test_plan_marks_shaped_buckets(results):
    """A one-leaf bucket is shaped when k divides its leading dim: at
    k = 1 every leaf, at k = 4 "a" (64, ...) and "d" (48, ...) but not
    "c" (33, ...). Leaves flatten alphabetically: a, c, d."""
    assert results["shaped_plan:1"] == [True, True, True]
    assert results["shaped_plan:4"] == [True, False, True]


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("strategy,mode", [
    ("asa", "sum"), ("asa16", "sum"), ("asa16", "raw"), ("asa8", "sum"),
    ("asa8", "raw"), ("ar", "sum")])
def test_shaped_receive_flattens_to_the_flat_receive(results, k, strategy,
                                                     mode):
    """The shaped shard reshaped to (-1,), and the shaped raw chunks to
    (k, -1), equal the flat bucket's receive bit for bit: row i of the
    leaf split on its leading dim is row i of the flat bucket."""
    assert results[f"shaped_rs:{k}:{strategy}:{mode}"]


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("mode", ["raw", "flat_update", "flat_update_nowd"])
def test_overlap_on_shaped_buckets_matches_flat_accumulation(results, k,
                                                             mode):
    """Three overlapped steps (asa16, 4 microbatches) that accumulate the
    receives in their leaf's shape leave the same master shards and
    momenta as accumulating every bucket flat: bit for bit through the
    fused kernel and through the jnp update without weight decay. With
    it, the CPU backend may contract ``g + wd * mask * p`` into one
    fused multiply-add for one layout and not the other, a rounding of at
    most one float32 ulp per step."""
    r = results[f"shaped_steps:{k}:{mode}"]
    assert r["moved"], r
    if mode == "flat_update":
        assert r["ulps"] <= 3.0, r
    else:
        assert r["bitwise"], r


@pytest.mark.parametrize("k,bucket_bytes,want", [
    (1, 0, [3, 0]), (4, 0, [2, 1]), (1, 1 << 20, [0, 1]),
    (4, 1 << 20, [0, 1])])
def test_gauges_count_shaped_and_flat_buckets(results, k, bucket_bytes,
                                              want):
    """``exchange/shaped_buckets`` / ``exchange/flat_buckets`` are set
    when the step is built; a multi-leaf bucket is flat."""
    assert results[f"gauges:{k}:{bucket_bytes}"] == want


def test_rs_plan_invariants():
    """Every leaf lands in exactly one bucket or the small set; shards
    cover the bucket; plan is deterministic for shapes."""
    import jax
    import jax.numpy as jnp
    from repro.core.exchanger import make_rs_plan

    tree = {"a": jnp.zeros((33, 77)), "b": jnp.zeros((1237,)),
            "c": jnp.zeros((5,)), "d": jnp.zeros((2048, 3))}
    for bb in [0, 4096, 1 << 20]:
        plan = make_rs_plan(tree, 8, bucket_bytes=bb)
        seen = sorted(i for b in plan.buckets for i in b.leaves)
        seen += sorted(plan.small)
        assert sorted(seen) == list(range(4))
        for b in plan.buckets:
            assert b.padded == b.shard_len * 8
            assert b.padded >= sum(b.sizes)
        abs_tree = jax.eval_shape(lambda: tree)
        plan2 = make_rs_plan(abs_tree, 8, bucket_bytes=bb)
        assert plan2.buckets == plan.buckets and plan2.small == plan.small


def test_pack_unpack_roundtrip():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.exchanger import Exchanger, make_rs_plan

    key = jax.random.key(0)
    tree = {"a": jax.random.normal(key, (33, 77)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (1237,)),
            "c": jax.random.normal(jax.random.fold_in(key, 2), (5,)).astype(
                jnp.float16)}
    plan = make_rs_plan(tree, 8, bucket_bytes=1 << 20)
    flats, smalls, _ = Exchanger.pack(tree, plan)
    back = Exchanger.unpack(flats, smalls, plan)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_allclose(np.asarray(back[k], np.float32),
                                   np.asarray(tree[k], np.float32),
                                   rtol=1e-6)
