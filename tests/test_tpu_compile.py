"""Compile the main-path Pallas kernels for a described TPU v5e, at real
widths, with no chip attached: the TPU compiler refuses here what it would
refuse on the chip (tile-illegal block shapes, unsupported lowerings,
VMEM overflow). Each test asserts the kernel survived as a
``tpu_custom_call`` in the compiled HLO.

The topology is described only inside the fixtures below — never while a
module is imported — because one process at a time may load the TPU
library; pytest-xdist workers that do not get this file never touch it.
Widths: llama3.2-1b attention (H=32, KV=8, head_dim=64, S=2048, vocab
128256), a 4-worker gradient exchange (k=4) of a 4M-element bucket, and
AlexNet's largest bucket (fc6's weights) as one chip updates it."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels.chunk_sum import chunk_sum
from repro.kernels.flash_attention import (flash_attention, flash_decode,
                                           flash_decode_paged)
from repro.kernels.fused_rs_update import fused_rs_update
from repro.kernels.slot_gather import slot_gather_sample

B, S, H, KV, D = 1, 2048, 32, 8, 64
VOCAB = 128256
K_WORKERS, BUCKET = 4, 4 * 2 ** 20
F6_W = 9216 * 4096                    # AlexNet fc6 weights: 37,748,736


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # a context mesh of CPU devices, left by an earlier test in this
    # process, would clash with arguments placed on the described chip
    ctx = jax.set_mesh(Mesh(np.asarray(desc.devices[:1]), ("chip",)))
    yield desc
    ctx.__exit__(None, None, None)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _qkv_shapes():
    return [((B, S, H, D), jnp.bfloat16), ((B, S, KV, D), jnp.bfloat16),
            ((B, S, KV, D), jnp.bfloat16)]


def test_flash_attention_forward_compiles(one_chip):
    txt = _compile_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        one_chip, *_qkv_shapes())
    assert "tpu_custom_call" in txt


def test_flash_attention_forward_backward_compiles(one_chip):
    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: flash_attention(
            q, k, v, interpret=False).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = _compile_text(fwd_bwd, one_chip, *_qkv_shapes())
    # forward + dq + dkv kernels
    assert txt.count("tpu_custom_call") >= 3


def test_flash_attention_prefill_chunk_compiles(one_chip):
    """The serve engine's prefill: a 32-row q chunk at per-slot offsets
    against a 256-row cache lane."""
    chunk, lane = 32, 256
    txt = _compile_text(
        lambda q, k, v, off: flash_attention(q, k, v, q_off=off,
                                             interpret=False),
        one_chip, ((B, chunk, H, D), jnp.bfloat16),
        ((B, lane, KV, D), jnp.bfloat16), ((B, lane, KV, D), jnp.bfloat16),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_flash_decode_paged_compiles(one_chip):
    slots, ps = 8, 16
    pages_per_slot = S // ps
    num_pages = slots * pages_per_slot + 1          # + the null page
    txt = _compile_text(
        lambda q, kp, vp, tbl, pos: flash_decode_paged(
            q, kp, vp, tbl, pos, page_size=ps, interpret=False),
        one_chip, ((slots, 1, H, D), jnp.bfloat16),
        ((num_pages, KV, ps, D), jnp.bfloat16),
        ((num_pages, KV, ps, D), jnp.bfloat16),
        ((slots, pages_per_slot), jnp.int32), ((slots,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_flash_decode_contiguous_compiles(one_chip):
    slots = 8
    txt = _compile_text(
        lambda q, k, v, pos: flash_decode(q, k, v, pos, interpret=False),
        one_chip, ((slots, 1, H, D), jnp.bfloat16),
        ((slots, S, KV, D), jnp.bfloat16), ((slots, S, KV, D), jnp.bfloat16),
        ((slots,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_slot_gather_sample_compiles(one_chip):
    slots = 8
    txt = _compile_text(
        lambda lg, oh, t, nz: slot_gather_sample(lg, oh, t, nz,
                                                 interpret=False),
        one_chip, ((slots, 1, VOCAB), jnp.float32), ((slots, 1), jnp.float32),
        ((slots,), jnp.float32), ((slots, VOCAB), jnp.float32))
    assert "tpu_custom_call" in txt


def test_fused_rs_update_compiles(one_chip):
    txt = _compile_text(
        lambda recv, p, m, mask: fused_rs_update(recv, p, m, mask, 0.01,
                                                 interpret=False),
        one_chip, ((K_WORKERS, BUCKET), jnp.float32),
        ((BUCKET,), jnp.float32), ((BUCKET,), jnp.float32),
        ((BUCKET,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_chunk_sum_compiles(one_chip):
    txt = _compile_text(lambda x: chunk_sum(x, interpret=False), one_chip,
                        ((K_WORKERS, BUCKET), jnp.float32))
    assert "tpu_custom_call" in txt


def _operand_producers(txt, call):
    """Opcode of the instruction behind each operand of the custom call
    named ``call`` in compiled HLO text, looking through bitcasts (which
    move no data)."""
    insts = {}
    for name, opcode, args in re.findall(
            r"^\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\((.*?)\)", txt, re.M):
        insts[name] = (opcode, re.findall(r"%([\w.-]+)", args))
    calls = [n for n in insts if n.startswith(call + ".")
             and insts[n][0] == "custom-call"]
    assert len(calls) == 1, calls
    out = []
    for name in insts[calls[0]][1]:
        while insts[name][0] == "bitcast":
            name = insts[name][1][0]
        out.append(insts[name][0])
    return out


def test_fused_rs_update_one_chip_f6_width_views_without_copies(one_chip):
    """The one-chip cell's largest call: a k = 1 float32 receive over fc6's
    weights. The lane-dense (rows, 128) view of every operand must be a
    bitcast of its 1-D (or (1, n)) array, never a copy or transpose."""
    txt = _compile_text(
        lambda recv, p, m, mask: fused_rs_update(
            recv, p, m, mask, 0.01, weight_decay=5e-4, interpret=False),
        one_chip, ((1, F6_W), jnp.float32), ((F6_W,), jnp.float32),
        ((F6_W,), jnp.float32), ((F6_W,), jnp.float32))
    assert "tpu_custom_call" in txt
    producers = _operand_producers(txt, "fused_rs_update")
    # recv, p, m, mask, then lr
    assert producers == ["parameter"] * 4 + ["constant"]


def test_fused_rs_update_int8_compiles(one_chip):
    """The asa8 wire: int8 chunks from four workers with one scale each."""
    txt = _compile_text(
        lambda recv, s, p, m, mask: fused_rs_update(
            recv, p, m, mask, 0.01, scale=1.0 / K_WORKERS, scales=s,
            interpret=False),
        one_chip, ((K_WORKERS, BUCKET), jnp.int8), ((K_WORKERS,), jnp.float32),
        ((BUCKET,), jnp.float32), ((BUCKET,), jnp.float32),
        ((BUCKET,), jnp.float32))
    assert "tpu_custom_call" in txt
    assert not {"copy", "transpose"} & set(
        _operand_producers(txt, "fused_rs_update")[2:])   # p, m, mask, lr


# two dense layers, one weight gradient a 2M-element (1024, 2048) leaf;
# every leaf is a bucket (each > 1024 elements), all shaped at k = 1
_MLP = {"w1": (1024, 2048), "b1": (2048,), "w2": (2048, 256)}
_RELAYOUT = re.compile(r"(copy|reshape|convert_\w*fusion)(\.\d+)?$")


def _computations(txt):
    """Compiled HLO text -> {computation name: [(instruction name, opcode,
    element count of its array output or None)]}."""
    comps, cur = {}, None
    for line in txt.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = re.match(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
            if m:
                arr = re.fullmatch(r"\w+\[([\d,]*)\]\S*", m.group(2))
                size = arr and int(np.prod(
                    [int(d) for d in arr.group(1).split(",") if d]))
                cur.append((m.group(1), m.group(3), size))
    return comps


def test_bsp_step_accumulates_buckets_in_their_leaf_shape(topo, monkeypatch):
    """The overlapped BSP step (asa16, 4 microbatches, one described v5e)
    adds each microbatch's receive in its leaf's own layout: the scan's
    body holds no top-level ``copy``, ``reshape`` or ``convert_*_fusion``
    of a gradient-sized array. The flat view the fused update reads is made
    after the scan, at most once per bucket on the way from the
    accumulator to the kernel."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import (get_exchanger, init_sharded_train_state,
                            make_bsp_step)
    from repro.launch.mesh import make_mesh
    from repro.models.registry import Model
    from repro.optim import constant, sgd_momentum

    def init(key):
        return {n: jax.random.normal(jax.random.fold_in(key, i), s) * 0.02
                for i, (n, s) in enumerate(_MLP.items())}

    def loss_fn(params, batch, rng=None, unroll=False):
        bf = jnp.bfloat16
        h = jax.nn.relu(batch["x"].astype(bf) @ params["w1"].astype(bf)
                        + params["b1"].astype(bf))
        loss = jnp.mean(jnp.square((h @ params["w2"].astype(bf)).astype(
            jnp.float32)))
        return loss, {"loss": loss, "aux": jnp.zeros(())}

    model = Model(cfg=None, init=init, loss_fn=loss_fn, forward=None)
    opt = sgd_momentum(momentum=0.9, weight_decay=5e-4)
    mesh = make_mesh((1,), ("data",), devices=topo.devices[:1])
    host_mesh = make_mesh((1,), ("data",))
    with jax.set_mesh(host_mesh):   # the plan and shapes come from the host
        state = jax.eval_shape(lambda: init_sharded_train_state(
            model, opt, jax.random.key(0), host_mesh))
        step = make_bsp_step(model, opt, get_exchanger("asa16"),
                             constant(0.01), mesh, microbatches=4,
                             overlap="buckets", fuse_rs_update=True)
    rep = NamedSharding(mesh, P())
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        (state, {"x": jax.ShapeDtypeStruct((128, 1024), jnp.float32)},
         jax.eval_shape(lambda: jax.random.key(0))))
    with jax.set_mesh(mesh):
        txt = jax.jit(step).lower(*args).compile().as_text()

    # a 1-D leaf's flat and shaped views are one layout: only the dense
    # leaves can be relaid out
    sizes = {int(np.prod(s)) for s in _MLP.values() if len(s) > 1}
    comps = _computations(txt)
    bodies = re.findall(r" while\(.*?body=%([\w.-]+)", txt)
    assert len(bodies) == 1, bodies
    in_body = [(n, op) for n, op, size in comps[bodies[0]]
               if size in sizes and (op in ("copy", "reshape")
                                     or _RELAYOUT.match(n))]
    assert not in_body, in_body

    # from each fused_rs_update's receive back to the accumulator's add
    entry = re.search(r"^ENTRY %(\S+) ", txt, re.M).group(1)
    insts = {}
    for name, opcode, args_ in re.findall(
            r"^\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\((.*?)\)", txt, re.M):
        insts[name] = (opcode, re.findall(r"%([\w.-]+)", args_))
    calls = [n for n, _, _ in comps[entry]
             if n.startswith("fused_rs_update") and
             insts[n][0] == "custom-call"]
    assert len(calls) == len(_MLP), calls
    for call in calls:
        name, relayouts = insts[call][1][0], 0
        while (insts[name][0] in ("bitcast", "copy", "reshape")
               or _RELAYOUT.match(name)):
            relayouts += insts[name][0] != "bitcast"
            name = insts[name][1][0]
        assert relayouts <= 1, (call, relayouts)
