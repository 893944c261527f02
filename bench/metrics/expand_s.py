"""The host's stall at the depth expansion: the gap between the batch
fetches of the last shallow step and the first deep step, less the median
gap between shallow steps.  Nothing to read without an expansion."""
import statistics


def read(run):
    depths, fetches = run.stats["depths"], run.stats["fetches"]
    for i in range(1, len(depths)):
        if depths[i] > depths[i - 1]:
            shallow = [b - a for a, b in zip(fetches[:i - 1], fetches[1:i])]
            base = statistics.median(shallow) if shallow else 0.0
            return fetches[i] - fetches[i - 1] - base
    return None
