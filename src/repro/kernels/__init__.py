# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Pallas kernels and their execution-mode policy.

Every kernel takes ``interpret: bool | None``; ``None`` (the default)
resolves via :func:`default_interpret` — compiled on TPU, the Pallas
interpreter elsewhere — overridable per-process with
``REPRO_PALLAS_INTERPRET=0|1``.
"""
from __future__ import annotations

import os


def default_interpret() -> bool:
    """Whether Pallas kernels should run in interpreter mode.

    Priority: ``REPRO_PALLAS_INTERPRET`` env (0/1) > backend autodetect
    (compiled only on TPU — the interpreter is the only Pallas path on CPU
    hosts)."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)
