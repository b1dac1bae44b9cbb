"""The host's enqueue of a step: the time of the training loop's
``train/step`` spans (the step's programs and its key dispatched) in the
traced stretch, per traced step."""
import loopspans


def read(ctx):
    return loopspans.per_step_ms(
        ctx, lambda trace: loopspans.spans(trace, loopspans.DISPATCH))
