"""Mesh-aware sharding rules — the single authority for parameter, optimizer
state, batch, and cache placement on the ``(pod, data, model)`` meshes.

Every train/serve path asks this module where things live:

- BSP (``core/bsp.py``):     ``state_shardings`` / ``batch_shardings`` give
  the jit ``in_shardings``; parameters are model-sharded only, replicated
  over the data/pod axes so the exchangers' shard_map manual axes stay
  untouched.
- GSPMD/ZeRO-1 (``core/gspmd.py``): ``fsdp_param_spec`` extends
  ``param_spec`` with the data axis on a free dimension.
- async plans (``core/easgd.py``): per-worker replica stacks put the
  leading worker dim over the data axes; the engine
  (``repro.train.engine``) composes these placements per TrainPlan and
  ``batch_shardings`` splits gspmd batches.
- dry-run (``launch/dryrun.py``):   all builders, on 16x16 and 2x16x16.
- decode (``build_decode``):        ``param_shardings`` + ``cache_shardings``.

Placement policy (tensor parallelism over ``MODEL_AXIS``):

===============================  ==========================================
leaf                             spec (for the unstacked trailing dims)
===============================  ==========================================
attention q/k/v, MLA up-proj     heads dim on ``model``
attention out (wo)               contracting (heads*hd) dim on ``model``
MLA latent down-proj (wdkv)      latent dim on ``model``
MoE experts (wi/wu/wd)           expert dim on ``model`` (expert parallel)
dense/shared FFN wi/wu           ffn dim on ``model``
dense/shared FFN wd              ffn (contracting) dim on ``model``
SSM in-proj wz/wx                d_inner dim on ``model``
SSM out_proj                     d_inner (contracting) dim on ``model``
embeddings / lm head             vocab dim on ``model``
conv kernels, norms, biases,     replicated
router, SSM scalars, rope keys
===============================  ==========================================

Leaves inside stacked layer segments carry a leading layer dim; specs are
right-aligned to the leaf rank, so the same rule covers stacked and
unstacked layouts.  ``sanitize_spec`` then repairs any axis whose dim is not
divisible by the mesh extent — relocating it to the nearest free divisible
dim (preferring dims to the right: 20 heads on model=16 move to head_dim)
or dropping it to replicated when nothing divides.

``set_replicate_attn(True)`` (dry-run ``--replicate-attn``) turns off tensor
parallelism for attention/SSM mixer parameters, leaving only FFN/embedding
TP — the ablation knob for attention-collective cost.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"

# attention/SSM mixer leaves affected by set_replicate_attn
_ATTN_KEYS = frozenset({"wq", "wk", "wv", "wo", "bq", "bk", "bv",
                        "wuk", "wuv", "wdkv", "wkr"})
_SSM_KEYS = frozenset({"wz", "wx", "wbc", "wdt", "out_proj",
                       "conv_w", "conv_b", "A_log", "dt_bias", "D", "norm"})

_REPLICATE_ATTN = False


def set_replicate_attn(flag: bool) -> None:
    """Globally replicate attention/SSM mixer params (no TP on them)."""
    global _REPLICATE_ATTN
    _REPLICATE_ATTN = bool(flag)


# ---------------------------------------------------------------------------
# mesh topology
# ---------------------------------------------------------------------------

def dp_axes_of(mesh) -> tuple:
    """Data-parallel axes (everything but ``model``): ('data',) single-pod,
    ('pod', 'data') multi-pod — mesh order, as the exchangers expect."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def dp_size_of(mesh) -> int:
    """Total data-parallel world size (product over data+pod axes)."""
    k = 1
    for a in dp_axes_of(mesh):
        k *= mesh.shape[a]
    return k


def _extent(mesh, entry) -> int:
    """Mesh extent of one PartitionSpec entry (axis name or tuple of them)."""
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        k = 1
        for a in entry:
            k *= mesh.shape[a]
        return k
    return mesh.shape[entry]


def _dp_entry(mesh):
    """The spec entry sharding one dim over all data axes (None if pure-TP)."""
    dp = dp_axes_of(mesh)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


# ---------------------------------------------------------------------------
# spec sanitizer
# ---------------------------------------------------------------------------

def sanitize_spec(spec, shape, mesh) -> P:
    """Repair ``spec`` for ``shape`` on ``mesh``: every surviving mesh axis
    divides its dim, or it is gone.

    For each entry whose dim is NOT divisible by the entry's mesh extent,
    relocate it to the nearest *free* divisible dim — scanning right first
    (20 heads on model=16 move to head_dim), then left — and drop it
    entirely when nothing divides. Trailing ``None``s are stripped, so a
    fully-dropped 1-D spec comes back as ``P()``.

    Only needs ``mesh.axis_names``/``mesh.shape``, so tests may pass a fake
    mesh without allocating devices.
    """
    entries = list(spec)
    if len(entries) > len(shape):
        entries = entries[:len(shape)]
    entries += [None] * (len(shape) - len(entries))
    for i, e in enumerate(entries):
        if e is None:
            continue
        # axes absent from this mesh (e.g. 'model' on a pure-DP mesh) drop
        if isinstance(e, (tuple, list)):
            e = tuple(a for a in e if a in mesh.shape)
            e = e[0] if len(e) == 1 else (e or None)
        elif e not in mesh.shape:
            e = None
        entries[i] = e
        if e is None:
            continue
        k = _extent(mesh, e)
        if k <= 1 or shape[i] % k == 0:
            continue
        cands = [j for j in range(i + 1, len(entries))
                 if entries[j] is None and shape[j] % k == 0]
        cands += [j for j in range(i - 1, -1, -1)
                  if entries[j] is None and shape[j] % k == 0]
        entries[i] = None
        if cands:
            entries[cands[0]] = e
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


# ---------------------------------------------------------------------------
# parameter rule engine
# ---------------------------------------------------------------------------

def _path_names(path) -> list:
    """Key names along a jax tree path (DictKey/SequenceKey/GetAttrKey)."""
    names = []
    for e in path:
        if hasattr(e, "key"):
            names.append(str(e.key))
        elif hasattr(e, "name"):
            names.append(str(e.name))
        elif hasattr(e, "idx"):
            names.append(str(e.idx))
        else:
            names.append(str(e))
    return names


def _base_rule(names: list, key: str, leaf) -> tuple:
    """Spec for the trailing (unstacked) dims; () means fully replicated."""
    M = MODEL_AXIS
    if _REPLICATE_ATTN and (key in _ATTN_KEYS or key in _SSM_KEYS):
        return ()
    if key in ("wq", "wk", "wv", "wuk", "wuv"):
        return (None, M, None)          # (d|R, heads, head_dim): shard heads
    if key == "wo":
        return (M, None)                # (heads*hd, d): shard contracting dim
    if key in ("bq", "bk", "bv"):
        return (M, None)                # (heads, head_dim)
    if key == "wdkv":
        return (None, M)                # (d, kv_lora_rank): shard the latent
    if key == "wkr":
        return ()                       # shared rope key: small, replicated
    if key in ("wi", "wu", "wd") and "moe" in names and "shared" not in names:
        return (M, None, None)          # (E, ., .): expert parallelism
    if key in ("wi", "wu", "wz", "wx"):
        return (None, M)                # (d, ffn|d_inner): shard hidden dim
    if key in ("wd", "out_proj"):
        return (M, None)                # (ffn|d_inner, d): shard hidden dim
    if key == "embed":
        return (M, None)                # (vocab, d): shard vocab
    if key == "head":
        return (None, M)                # (d, vocab): shard vocab
    if key == "w":
        # vision: 2-D fc sharded on out-features, 4-D conv kernels replicated
        return (None, M) if getattr(leaf, "ndim", 0) == 2 else ()
    return ()   # norms, biases, router, conv, meta tokens, scalars


def param_spec(path, leaf) -> P:
    """PartitionSpec for one parameter leaf, right-aligned to its rank.

    ``path`` is a ``jax.tree_util`` key path (as produced by
    ``tree_map_with_path``); the rule keys off the leaf's dict-key name and
    its ancestors, so stacked-layer leading dims are transparently skipped.
    The result is *not* divisibility-checked — compose with
    ``sanitize_spec`` (the ``*_shardings`` builders do)."""
    names = _path_names(path)
    key = names[-1] if names else ""
    nd = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
    base = list(_base_rule(names, key, leaf))
    if not base:
        return P()
    if len(base) > nd:
        base = base[len(base) - nd:]
    return P(*([None] * (nd - len(base)) + base))


# ---------------------------------------------------------------------------
# sharding builders (NamedSharding trees for jit in_shardings)
# ---------------------------------------------------------------------------

def param_shardings(mesh, params):
    """Model-sharded, data-replicated NamedShardings for a parameter tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, sanitize_spec(param_spec(path, leaf), leaf.shape, mesh)),
        params)


def state_shardings(mesh, state):
    """BSP train-state shardings: the paper's replicated data parallelism.

    Parameters and optimizer state are replicated over the WHOLE mesh (the
    exchangers own the data axes as shard_map manual axes; the model axis
    contributes through activation constraints only). Architectures too
    big to replicate take the GSPMD/ZeRO-1 path (``fsdp_state_shardings``),
    selected by the FSDP threshold in ``launch/dryrun.py``."""
    rep = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: rep, state)


def batch_shardings(mesh, batch):
    """Batch leaves sharded over the data(+pod) axes on dim 0."""
    dpe = _dp_entry(mesh)

    def leaf(l):
        spec = P(dpe) if dpe is not None else P()
        return NamedSharding(mesh, sanitize_spec(spec, l.shape, mesh))

    return jax.tree.map(leaf, batch)


def cache_shardings(mesh, cache, global_batch: int,
                    page_batch: int | None = None):
    """Decode-cache shardings: batch dim over data axes, head-like dims over
    ``model`` (KV heads for GQA k/v, the latent for MLA ckv, SSM heads for
    recurrent state); conv windows and rope keys replicated.

    ``page_batch``: page count of a paged serve pool — attention leaves
    there carry (layers, num_pages, ...) pages instead of a slot
    batch dim, and the page dim shards over the data axes exactly like the
    slot dim does (pages are the unit of cache parallelism)."""
    dpe = _dp_entry(mesh)

    def leaf(path, l):
        names = _path_names(path)
        key = names[-1] if names else ""
        entries = [None] * l.ndim
        if dpe is not None:
            for i, s in enumerate(l.shape):
                if s == global_batch or (page_batch is not None
                                         and s == page_batch):
                    entries[i] = dpe
                    break
        if not _REPLICATE_ATTN:
            mi = None
            if key in ("k", "v") and page_batch is not None:
                mi = l.ndim - 3          # (..., P, KV, ps, hd): KV heads
            elif key in ("k", "v") and l.ndim >= 2:
                mi = l.ndim - 2          # (..., S, KV, hd): KV heads
            elif key == "ckv":
                mi = l.ndim - 1          # (..., S, R): MLA latent
            elif key == "state" and l.ndim >= 3:
                mi = l.ndim - 3          # (..., nh, N, P): SSM heads
            if mi is not None and entries[mi] is None:
                entries[mi] = MODEL_AXIS
        return NamedSharding(mesh, sanitize_spec(P(*entries), l.shape, mesh))

    return jax.tree_util.tree_map_with_path(leaf, cache)
