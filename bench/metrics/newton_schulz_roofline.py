"""Newton–Schulz's share of its roofline: the quintic iterations' FLOPs at
every Muon matrix shape of every step in the window
(``counts.newton_schulz_step``; every chip orthogonalizes whole matrices),
over the summed device time of the kernels named ``newton_schulz_*`` (the
fused kernel, or the tiled matmuls of the large-matrix path).
Compute-bound at these shapes."""
import counts

KERNELS = ("newton_schulz_fused", "newton_schulz_matmul")


def read(run):
    t = run.trace.op_s(lambda n: any(k in n for k in KERNELS))
    if t <= 0:
        return None
    flops = nbytes = 0.0
    for layers in run.stats["depths"]:
        f, b = counts.newton_schulz_step(run.cell.model, layers)
        flops, nbytes = flops + f, nbytes + b
    need, _ = counts.roofline_s(flops, nbytes, run.peak)
    return 100.0 * need / t
