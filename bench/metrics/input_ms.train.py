"""Host milliseconds per ``BinCorpus.batch`` call in the window, from the
benchmark's span around each call."""
import statistics


def read(run):
    spans = run.stats.get("batch_s") or []
    return 1e3 * statistics.fmean(spans) if spans else None
