"""Seconds in which the first device ran nothing while the program grew
the model: a ``train.expand`` span, or the first ``train.dispatch`` after
it (which traces the deep step and compiles or loads it), was open
(``program_spans``).  Nothing to read without an expansion in the window."""
import program_spans
import tracing


def read(run):
    spans, idle = program_spans.window_spans(run), program_spans.idle(run)
    if spans is None or idle is None:
        return None
    dispatch = sorted(program_spans.named(spans, "train.dispatch"))
    during = []
    for s, e in program_spans.named(spans, "train.expand"):
        during.append([s, e])
        during += [d for d in dispatch if d[0] >= e][:1]
    if not during:
        return None
    return program_spans.overlap_s(idle, tracing.union(during))
