"""Seconds of the window in which JAX traced, lowered, compiled or loaded
from its persistent cache some program: the union of the program's
``jax.trace``, ``jax.lower`` and ``jax.compile`` spans (``program_spans``).
An inner jit is traced inside its outer one, so the spans overlap and are
not summed."""
import program_spans
import tracing


def read(run):
    spans = program_spans.window_spans(run)
    if spans is None:
        return None
    return tracing.total(tracing.union(
        [[s, e] for n, s, e, _ in spans if n.startswith("jax.")])) / 1e9
