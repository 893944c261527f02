"""The program's own spans (``repro.spans``) on the device trace's clock.

While the traced window's profiler runs, the program logs its spans in
memory on ``time.perf_counter_ns``; the trace's events lie on the
profiler's clock.  One offset maps the first onto the second: the median,
over the window's steps, of a ``bench.batch`` event's start in the trace
less the benchmark's host clock reading taken as that fetch began
(``run.stats["fetches"]``), two readings of one instant.

A program without ``repro.spans``, or a run with no fetch to pair, gives
nothing to map: ``window_spans`` and ``idle`` then return None.
"""
import statistics

import tracing


def window_spans(run):
    """[(name, start_ns, end_ns, attrs)] of the program's spans that closed
    inside the traced window, on the trace's clock, oldest first; None
    where the log cannot be mapped."""
    try:
        from repro import spans
    except ImportError:
        return None
    starts = sorted(s for n, s, _ in run.trace.trace["host"]
                    if n == "bench.batch")
    fetches = run.stats["fetches"]
    if not starts or len(starts) != len(fetches):
        return None
    shift = statistics.median(s - round(f * 1e9)
                              for s, f in zip(starts, fetches))
    lo, hi = run.trace.lo, run.trace.hi
    return [(name, s + shift, e + shift, attrs)
            for name, _, s, e, attrs in spans.log()
            if e is not None and lo <= s + shift and e + shift <= hi]


def named(spans, name):
    """[[start_ns, end_ns]] of the spans called ``name``."""
    return [[s, e] for n, s, e, _ in spans if n == name]


def idle(run):
    """Merged [[start_ns, end_ns]] of the window in which the first device
    ran nothing; None for a trace without a device."""
    red = run.trace
    if not red.devices:
        return None
    ops = red.trace["devices"][red.devices[0]]["ops"]
    busy = tracing.union(tracing.clip([[s, s + d] for _, s, d in ops],
                                      red.lo, red.hi))
    return tracing.subtract([[red.lo, red.hi]], busy)


def overlap_s(a, b) -> float:
    """Seconds in both of the merged interval lists ``a`` and ``b``."""
    return (tracing.total(a) - tracing.total(tracing.subtract(a, b))) / 1e9
