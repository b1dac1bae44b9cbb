"""The whole training step's share of the chip's peak bfloat16 rate: the
configuration's model FLOPs per image (forward from the layer shapes,
backward twice the forward) times the images each chip trained in the
traced stretch, over the stretch's length."""


def read(ctx):
    trace, steps = ctx["trace"], ctx["traced_steps"]
    if trace is None or not steps:
        return None
    flops = ctx["ref"].train_flops_per_image(ctx["conf"])
    images = steps * ctx["global_batch"] / ctx["cell"]["chips"]
    return (100.0 * flops * images / trace.window_s
            / ctx["peaks"]["bf16_flops_per_s"])
