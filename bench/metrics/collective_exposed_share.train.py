"""Share of the traced training window in which a collective ran on a
device and no other op did (``Reduction.exposed_collective_s``), averaged
over the devices."""


def read(run):
    red = run.trace
    if not red.devices or red.window_s <= 0:
        return None
    return 100.0 * red.exposed_collective_s() / red.window_s
