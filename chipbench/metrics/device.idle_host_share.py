"""The idle that the host's own work causes: per chip, the share of the
traced stretch in which no operation ran while the training loop was in a
step and not blocked on the device, averaged over the cell's chips. The
rest of ``device.idle_share`` is idle with the host waiting or ahead."""
import loopspans
import tracereduce as tr


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not loopspans.marked(trace):
        return None
    work = loopspans.host_work(trace)
    idle = sum(tr.length(tr.intersect(
        loopspans.complement(d.busy, trace.window), work))
        for d in trace.devices) / len(trace.devices)
    return 100.0 * idle * 1e-9 / trace.window_s
