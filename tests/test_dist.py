"""Property-style checks for the repro.dist sharding subsystem:

- ``sanitize_spec`` output is always divisible-or-empty, never duplicates a
  mesh axis, and handles axes absent from the mesh;
- ``param_spec`` returns a rank-compatible spec for every leaf of every
  smoke config in the registry, sanitizable against every production mesh;
- ``act.constrain`` is the identity outside ``activation_spec`` and a shape-
  preserving constraint inside;
- the ``*_shardings`` builders produce valid NamedShardings end-to-end.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, get_smoke_config
from repro.configs.base import InputShape
from repro.dist import act
from repro.dist.sharding import (MODEL_AXIS, batch_shardings, cache_shardings,
                                 dp_axes_of, dp_size_of, param_shardings,
                                 param_spec, sanitize_spec,
                                 set_replicate_attn, state_shardings)
from repro.launch.mesh import make_mesh
from repro.launch.specs import (abstract_cache, abstract_state,
                                train_batch_specs)
from repro.models import build_model
from repro.optim import sgd_momentum
from repro.testing import FakeMesh

SINGLE = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})
TINY = FakeMesh({"data": 4, "model": 2})
MESHES = [SINGLE, MULTI, TINY]
_IDS = ["16x16", "2x16x16", "4x2"]


def _extent(mesh, e):
    if isinstance(e, (tuple, list)):
        k = 1
        for a in e:
            k *= mesh.shape[a]
        return k
    return mesh.shape[e]


def _assert_valid(spec, shape, mesh):
    assert len(spec) <= len(shape)
    used = []
    for i, e in enumerate(spec):
        if e is None:
            continue
        assert shape[i] % _extent(mesh, e) == 0, (spec, shape)
        used += list(e) if isinstance(e, (tuple, list)) else [e]
    assert len(used) == len(set(used)), f"duplicated axis in {spec}"


# ---------------------------------------------------------------------------
# sanitize_spec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", MESHES, ids=_IDS)
def test_sanitize_always_divisible_or_dropped(mesh):
    shapes = [(20,), (16,), (1,), (7, 13), (16, 16), (40, 2560, 20, 128),
              (3, 5, 7, 11), (48, 64, 64), (2, 8, 4, 2, 64)]
    for shape in shapes:
        for pos in range(len(shape)):
            entries = [None] * len(shape)
            entries[pos] = MODEL_AXIS
            _assert_valid(sanitize_spec(P(*entries), shape, mesh),
                          shape, mesh)


def test_sanitize_relocation_prefers_right():
    # the pinned dryrun case: 20 heads on model=16 move right to head_dim
    spec = sanitize_spec(P(None, None, "model", None),
                         (40, 2560, 20, 128), SINGLE)
    assert tuple(spec) == (None, None, None, "model")
    # nothing divisible on the right: falls back to the nearest left dim
    spec = sanitize_spec(P(None, "model", None), (32, 20, 7), SINGLE)
    assert tuple(spec) == ("model",)


def test_sanitize_drops_when_nothing_divides():
    assert tuple(sanitize_spec(P("model"), (20,), SINGLE)) == ()
    assert tuple(sanitize_spec(P("model", "data"), (6, 10), SINGLE)) == ()


def test_sanitize_tuple_and_missing_axes():
    # tuple (pod,data) entry: extent is the product
    spec = sanitize_spec(P(("pod", "data"), None), (64, 3), MULTI)
    assert tuple(spec) == (("pod", "data"),)
    assert tuple(sanitize_spec(P(("pod", "data")), (4,), MULTI)) == ()
    # axes absent from the mesh are dropped, present ones kept
    pure_dp = FakeMesh({"data": 4})
    assert tuple(sanitize_spec(P(None, "model"), (4, 32), pure_dp)) == ()
    spec = sanitize_spec(P(("pod", "data"), "model"), (8, 32), pure_dp)
    assert tuple(spec) == ("data",)


def test_sanitize_never_widens_rank():
    spec = sanitize_spec(P("model", None, None, None), (32,), SINGLE)
    assert len(spec) <= 1


# ---------------------------------------------------------------------------
# dp axes
# ---------------------------------------------------------------------------

def test_dp_axes_and_size():
    assert dp_axes_of(SINGLE) == ("data",)
    assert dp_size_of(SINGLE) == 16
    assert dp_axes_of(MULTI) == ("pod", "data")
    assert dp_size_of(MULTI) == 32
    assert dp_axes_of(FakeMesh({"model": 8})) == ()
    assert dp_size_of(FakeMesh({"model": 8})) == 1


# ---------------------------------------------------------------------------
# param_spec over every smoke config in the registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ASSIGNED_ARCHS + PAPER_ARCHS)
def test_param_spec_rank_compatible_every_leaf(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    n_sharded = 0

    def check(path, leaf):
        nonlocal n_sharded
        spec = param_spec(path, leaf)
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        for mesh in MESHES:
            _assert_valid(sanitize_spec(spec, leaf.shape, mesh),
                          leaf.shape, mesh)
        if any(e is not None for e in spec):
            n_sharded += 1

    jax.tree_util.tree_map_with_path(check, params)
    # the rule engine must actually shard things, not replicate everything
    assert n_sharded >= 3, f"{arch}: only {n_sharded} sharded leaves"


def test_replicate_attn_toggle():
    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    wq = [(p, l) for p, l in leaves
          if jax.tree_util.keystr(p).endswith("['wq']")]
    assert wq
    path, leaf = wq[0]
    assert MODEL_AXIS in tuple(param_spec(path, leaf))
    try:
        set_replicate_attn(True)
        assert tuple(param_spec(path, leaf)) == ()
        # FFN TP is unaffected by the toggle
        wi = [(p, l) for p, l in leaves
              if "mlp" in jax.tree_util.keystr(p)
              and jax.tree_util.keystr(p).endswith("['wi']")][0]
        assert MODEL_AXIS in tuple(param_spec(*wi))
    finally:
        set_replicate_attn(False)
    assert MODEL_AXIS in tuple(param_spec(path, leaf))


# ---------------------------------------------------------------------------
# act.constrain
# ---------------------------------------------------------------------------

def test_act_constrain_identity_outside_context():
    x = jnp.ones((2, 8, 16))
    assert act.constrain(x) is x
    with act.activation_spec(None):   # explicit None is also a no-op
        assert act.constrain(x) is x
    assert act.current_spec() is None


def test_act_constrain_inside_context_preserves_shape_and_values():
    mesh = make_mesh((1,), ("model",))
    jax.set_mesh(mesh)
    x = jnp.arange(2 * 8 * 16, dtype=jnp.float32).reshape(2, 8, 16)
    with act.activation_spec(P(None, None, "model")):
        assert act.current_spec() == P(None, None, "model")
        y = jax.jit(act.constrain)(x)
    assert y.shape == x.shape
    assert bool(jnp.all(y == x))
    assert act.current_spec() is None


def test_act_constrain_rank_pads():
    mesh = make_mesh((1,), ("model",))
    jax.set_mesh(mesh)
    with act.activation_spec(P(None, None, "model")):
        y2 = jax.jit(act.constrain)(jnp.ones((4, 16)))      # rank < spec
        y4 = jax.jit(act.constrain)(jnp.ones((2, 2, 4, 16)))  # rank > spec
    assert y2.shape == (4, 16) and y4.shape == (2, 2, 4, 16)


def test_act_contexts_nest():
    a, b = P("model"), P(None, "model")
    with act.activation_spec(a):
        with act.activation_spec(b):
            assert act.current_spec() is b
        assert act.current_spec() is a
    assert act.current_spec() is None


# ---------------------------------------------------------------------------
# builders end-to-end on a real (1-device) mesh
# ---------------------------------------------------------------------------

def _real_mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_param_and_state_shardings_build():
    mesh = _real_mesh()
    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    state = abstract_state(model, sgd_momentum(weight_decay=0.0))
    psh = param_shardings(mesh, state["params"])
    for leaf, sh in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(psh)):
        assert isinstance(sh, NamedSharding)
        assert len(sh.spec) <= leaf.ndim
    ssh = state_shardings(mesh, state)
    assert set(ssh) == {"params", "opt", "step"}
    # BSP state is replicated over the whole mesh (paper-faithful DP)
    for sh in jax.tree.leaves(ssh,
                              is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert tuple(sh.spec) == ()


def test_batch_and_cache_shardings_build():
    mesh = _real_mesh()
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    model = build_model(cfg)
    shape = InputShape("tiny_train", 32, 8, "train")
    bsh = batch_shardings(mesh, train_batch_specs(cfg, shape))
    for sh in jax.tree.leaves(bsh):
        assert isinstance(sh, NamedSharding)
    cache = abstract_cache(model, cfg, InputShape("tiny_dec", 32, 8, "decode"))
    csh = cache_shardings(mesh, cache, 8)
    for leaf, sh in zip(jax.tree.leaves(cache), jax.tree.leaves(csh)):
        assert isinstance(sh, NamedSharding)
        assert len(sh.spec) <= leaf.ndim


def test_cache_shardings_shard_heads_on_fake_mesh():
    """On the production mesh shape the KV cache is model-sharded on a
    head-like dim and data-sharded on batch (validated via specs only)."""
    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    cache = abstract_cache(model, cfg, InputShape("d", 64, 16, "decode"))
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    k_leaves = [(p, l) for p, l in leaves
                if jax.tree_util.keystr(p).endswith("['k']")]
    assert k_leaves
    for path, leaf in k_leaves:
        entries = [None] * leaf.ndim
        bi = next(i for i, s in enumerate(leaf.shape) if s == 16)
        entries[bi] = "data"
        entries[leaf.ndim - 2] = MODEL_AXIS
        _assert_valid(sanitize_spec(P(*entries), leaf.shape, TINY),
                      leaf.shape, TINY)
