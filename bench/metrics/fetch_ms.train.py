"""Host milliseconds per ``train.fetch`` span in the window: the trainer's
call for a step's batch and the ``device_put`` that places it, from the
program's own spans (``program_spans``)."""
import statistics

import program_spans


def read(run):
    spans = program_spans.window_spans(run)
    fetch = program_spans.named(spans or [], "train.fetch")
    return 1e-6 * statistics.fmean(e - s for s, e in fetch) if fetch \
        else None
