"""Activation sharding constraints (sequence-parallel residual stream).

The model executors call ``act.constrain(x)`` on the residual stream between
layers (``models/transformer.py``, ``models/encdec.py``). Outside an
``activation_spec`` context that is an identity — smoke tests and eager
training pay nothing. Inside (the dry-run compiles with
``P(None, None, 'model')``: the residual feature dim sharded over the TP
axis) it becomes a rank-padded ``with_sharding_constraint``, pinning the
between-layer activation layout so XLA keeps the residual stream distributed
instead of all-gathering it after every layer — the activation-memory side
of tensor parallelism.

The spec is sanitized against the ambient mesh (the one installed by
``jax.set_mesh``) so a non-divisible feature dim degrades to replicated
rather than failing to compile.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import sanitize_spec

_STATE = threading.local()


def current_spec():
    """The active activation PartitionSpec, or None outside any context."""
    return getattr(_STATE, "spec", None)


@contextlib.contextmanager
def activation_spec(spec):
    """Make ``spec`` the activation constraint for the enclosed trace/compile.

    ``spec`` may be None (explicit no-op, e.g. decode shapes where the
    single-token residual is too small to shard). Contexts nest; the previous
    spec is restored on exit."""
    prev = getattr(_STATE, "spec", None)
    _STATE.spec = spec
    try:
        yield
    finally:
        _STATE.spec = prev


def _bound_axes():
    """Mesh axes currently bound as *manual* (shard_map) axes at trace time.

    A with_sharding_constraint may only reference auto axes; entries naming
    manual axes must drop. The BSP shard_map binds only the data axes, so
    'model' stays auto and its constraints are kept."""
    try:
        from jax._src import core as jcore
        return frozenset(jcore.get_axis_env().axis_names())
    except Exception:  # noqa: BLE001 - introspection is best-effort
        return frozenset()


def _ambient_mesh():
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        if m is not None and len(m.axis_names) > 0:
            return m
    except Exception:  # noqa: BLE001 - mesh introspection is best-effort
        pass
    return None


def constrain(x):
    """Apply the active activation constraint to ``x`` (identity if none).

    The spec is right-aligned to ``x``'s rank: leading dims are padded with
    None (batch/seq stay unconstrained), an over-long spec is trimmed from
    the left. With an ambient mesh available the padded spec is sanitized so
    non-divisible dims fall back to replicated instead of erroring."""
    spec = current_spec()
    if spec is None:
        return x
    entries = list(spec)
    nd = x.ndim
    if len(entries) > nd:
        entries = entries[len(entries) - nd:]
    entries = [None] * (nd - len(entries)) + entries
    bound = _bound_axes()
    if bound:
        def free(e):
            if isinstance(e, (tuple, list)):
                e = tuple(a for a in e if a not in bound)
                return e[0] if len(e) == 1 else (e or None)
            return None if e in bound else e
        entries = [free(e) for e in entries]
    if all(e is None for e in entries):
        return x
    mesh = _ambient_mesh()
    if mesh is not None:
        p = sanitize_spec(P(*entries), x.shape, mesh)
    else:
        p = P(*entries)
    return jax.lax.with_sharding_constraint(x, p)
