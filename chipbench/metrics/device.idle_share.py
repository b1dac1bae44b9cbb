"""Share of the traced stretch in which no operation ran on the device,
averaged over the cell's chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * trace.idle_share()
