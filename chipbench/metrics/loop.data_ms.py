"""How long a step waits for its batch: the time of the training loop's
``train/data`` spans (its ``next()`` on the batches) in the traced stretch,
per traced step."""
import loopspans


def read(ctx):
    return loopspans.per_step_ms(
        ctx, lambda trace: loopspans.spans(trace, loopspans.DATA))
