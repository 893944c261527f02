"""Model FLOP utilization of training: the forward and backward FLOPs of
every step in the window at its depth (causal attention at the pairs it
needs; no recompute, no optimizer) over the traced window, chips and peak."""
import counts


def read(run):
    m, mix = run.cell.model, run.cell.traffic
    flops = sum(counts.train_step_flops(m, L, mix["batch"], mix["seq_len"])
                for L in run.stats["depths"])
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peak["flops_per_s"])
