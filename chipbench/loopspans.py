"""The training loop's host spans in a traced stretch, for the ``loop.*``
metrics and ``device.idle_host_share``.

``repro.train.loop`` marks each iteration with a ``train`` step span
(the profiler's ``StepTraceAnnotation``) holding its phases: ``train/data``
(the ``next()`` on the batches), ``train/step`` (the host enqueue of the
step's programs), ``train/flush`` (the host blocked on losses) and, where
they happen, ``train/checkpoint``; ``train/compile_block`` and
``train/final_block`` block on the device too. The parts of an iteration
outside its phases are the loop's bookkeeping.

Every span is clipped to the stretch. The feed starts and stops the
profiler inside ``next()``, so inside ``train/data``: a span open when the
session starts or stops is not in the trace. The iteration cut by the
stretch's start thus shows its ``train/step`` (and any flush) without its
``train`` and ``train/data`` spans; the one cut by its end shows nothing.
The loop's host work is the union of the ``train`` spans and the working
phases, less the spans where the host waits for the device: the cut first
iteration's dispatch counts, its bookkeeping does not. Per-step numbers
divide by the stretch's steps.
"""
import tracereduce as tr

STEP = "train"
DATA = "train/data"
DISPATCH = "train/step"
SYNC = "train/flush"
WORK = (STEP, DATA, DISPATCH, "train/checkpoint")
WAITS = (SYNC, "train/compile_block", "train/final_block")


def spans(trace, *names) -> list:
    """Merged intervals of the host events so named, clipped to the
    stretch."""
    return tr.union(tr.clip([(s, e) for n, s, e, _ in trace.host
                             if n in names], trace.window))


def complement(intervals, window) -> list:
    """The parts of ``window`` that merged ``intervals`` leave uncovered."""
    edges = ([window[0]] + [x for iv in intervals for x in iv]
             + [window[1]])
    return [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def marked(trace) -> bool:
    """Whether the program marked its steps in the stretch at all."""
    return bool(spans(trace, STEP))


def host_work(trace) -> list:
    """The loop's own host time in the stretch, as merged intervals."""
    return tr.intersect(spans(trace, *WORK),
                        complement(spans(trace, *WAITS), trace.window))


def per_step_ms(ctx, intervals) -> float | None:
    """Milliseconds of ``intervals(trace)`` per traced step; None where the
    stretch or its steps are missing, or the program marks no steps."""
    trace, steps = ctx["trace"], ctx["traced_steps"]
    if trace is None or not steps or not marked(trace):
        return None
    return 1e3 * tr.length(intervals(trace)) * 1e-9 / steps
