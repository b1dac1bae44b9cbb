"""The host blocked on the device: the time of the training loop's
``train/flush`` spans (losses copied to the host) in the traced stretch,
per traced step; 0 where the stretch holds steps and no flush."""
import loopspans


def read(ctx):
    return loopspans.per_step_ms(
        ctx, lambda trace: loopspans.spans(trace, loopspans.SYNC))
