"""Flash attention's share of its roofline in training: the causal FLOPs
and least bytes its forward and backward kernels need over the window's
steps (``counts.flash_attention_train``, per chip), over the summed device
time of the kernels named ``flash_attention_*``.  Compute-bound at these
shapes."""
import counts

KERNELS = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")


def read(run):
    t = run.trace.op_s(lambda n: any(k in n for k in KERNELS))
    if t <= 0:
        return None
    m, mix = run.cell.model, run.cell.traffic
    flops = nbytes = 0.0
    for layers in run.stats["depths"]:
        f, b = counts.flash_attention_train(m, layers, mix["batch"],
                                            mix["seq_len"])
        flops, nbytes = flops + f, nbytes + b
    need, _ = counts.roofline_s(flops / run.chips, nbytes / run.chips,
                                run.peak)
    return 100.0 * need / t
