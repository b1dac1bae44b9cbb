"""The training loop's own host cost per step, to set beside the device's
step time: the time of its ``train`` step spans in the traced stretch,
less the time it is blocked on the device in them, per traced step
(``loopspans`` says how the stretch's edges count)."""
import loopspans


def read(ctx):
    return loopspans.per_step_ms(ctx, loopspans.host_work)
