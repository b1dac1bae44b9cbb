"""The fused reduce-scatter update kernel's share of its roofline: the
least time its calls in the traced stretch could take, bytes moved (from
their operand and result shapes) over peak HBM bandwidth, over the device
time they took. Its few operations per byte never make compute the bound."""
import kernelcost

KERNEL = "fused_rs_update"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    calls = trace.kernel_ops(KERNEL)
    moved = sum(kernelcost.hlo_bytes(op) for op in calls)
    spent = sum(op.end - op.start for op in calls) * 1e-9
    if not moved or not spent:
        return None
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / spent
