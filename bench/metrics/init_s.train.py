"""Seconds of the program's ``train.init`` span: the creation of the
sharded weights and the optimizer state before the first step, in the
run's last trainer (``repro.spans.last``; set-up, not the traced window).
Nothing to read from a program whose spans do not keep it."""


def read(run):
    try:
        from repro import spans
    except ImportError:
        return None
    last = getattr(spans, "last", None)
    return None if last is None else last("train.init")
