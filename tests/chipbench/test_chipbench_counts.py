"""The benchmark's yardsticks: FLOP and byte counts, and the peaks table."""
import json

import jax
import jax.numpy as jnp
import pytest

import cbtiny

cbtiny.use_harness()

import kernelcost  # noqa: E402
import spec  # noqa: E402


def _reference(name):
    return spec._load_module(cbtiny.CHIPBENCH / "configs" / f"{name}.py",
                             f"counts_ref_{name}")


def test_alexnet_flops_match_xla_cost_analysis():
    """The configuration's count from its layer shapes against XLA's own
    count of the program's forward at batch 1: they differ only by the
    elementwise operations, which the FLOP function leaves out."""
    from repro.configs import get_config
    from repro.models import build_model
    conf = json.loads((cbtiny.CHIPBENCH / "configs" / "alexnet.json")
                      .read_text())
    model = build_model(get_config("alexnet"))
    params = jax.eval_shape(model.init, jax.random.key(0))
    side = conf["image_size"]
    images = jax.ShapeDtypeStruct((1, side, side, 3), jnp.float32)
    xla = jax.jit(model.forward).lower(params, {"images": images}) \
        .cost_analysis()["flops"]
    ours = _reference("alexnet").forward_flops_per_image(conf)
    assert ours <= xla <= 1.01 * ours
    assert _reference("alexnet").train_flops_per_image(conf) == 3 * ours


@pytest.mark.parametrize("k,n,wire", [(1, 2048 * 37, jnp.float16),
                                      (4, 2048 * 5, jnp.float16),
                                      (4, 2048 * 3, jnp.float32)])
def test_fused_rs_update_bytes_match_operand_shapes(k, n, wire):
    from repro.kernels.fused_rs_update import fused_rs_update
    args = (jax.ShapeDtypeStruct((k, n), wire),
            *[jax.ShapeDtypeStruct((n,), jnp.float32)] * 3)
    outs = jax.eval_shape(lambda r, p, m, mask: fused_rs_update(
        r, p, m, mask, 0.01, interpret=True), *args)
    moved = sum(a.size * a.dtype.itemsize for a in args) + 4 + sum(
        o.size * o.dtype.itemsize for o in outs)
    want, flops = kernelcost.fused_rs_update_cost(
        k, n, jnp.dtype(wire).itemsize)
    assert moved == want
    # the kernel is bound by bytes: its operations take far less time
    assert flops / 197e12 < 0.05 * want / 819e9


def test_shape_bytes_reads_every_shape():
    text = "(f32[4096]{0}, f32[4096]{0:T(1024)}) f16[4,4096]{1,0} bf16[2,8] s32[]"
    assert kernelcost.shape_bytes(text) == 2 * 4096 * 4 + 4 * 4096 * 2 + 32 + 4


def test_peaks_table_is_keyed_by_device_kind():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")
