"""dist spec-construction micro-bench: ``param_spec``+``sanitize_spec`` and
the ``param_shardings``/``state_shardings`` builders over the LARGEST config
(mistral-large-123b, 88 stacked layers) on the production mesh shapes.

Spec construction runs once per compile, but the dry-run sweeps hundreds of
(arch x shape x mesh x mode) programs — it must stay off the hot path.
Derived: leaf count and per-leaf cost.
"""
import time


def _time(fn, reps=5):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    import jax
    from repro.configs import get_config
    from repro.dist.sharding import (param_spec, param_shardings,
                                     sanitize_spec, state_shardings)
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import abstract_state
    from repro.models import build_model
    from repro.optim import sgd_momentum
    from repro.testing import FakeMesh

    cfg = get_config("mistral-large-123b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})

    rows = []

    def specs_all():
        for path, leaf in leaves:
            sanitize_spec(param_spec(path, leaf), leaf.shape, mesh)

    us = _time(specs_all)
    rows.append(("dist/param_spec+sanitize_123b", us,
                 f"leaves={len(leaves)};us_per_leaf={us / len(leaves):.1f}"))

    # full builders need a real (1-device) mesh for NamedSharding
    rmesh = make_mesh((1, 1), ("data", "model"))
    us = _time(lambda: param_shardings(rmesh, params))
    rows.append(("dist/param_shardings_123b", us, f"leaves={len(leaves)}"))

    state = abstract_state(model, sgd_momentum(weight_decay=0.0))
    n_state = len(jax.tree.leaves(state))
    us = _time(lambda: state_shardings(rmesh, state))
    rows.append(("dist/state_shardings_123b", us, f"leaves={n_state}"))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
