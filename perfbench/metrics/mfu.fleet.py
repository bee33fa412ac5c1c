"""Model FLOP/s utilisation of the client model step, in %: training
samples per second of the traced window x the forward-and-backward FLOPs
one LeNet5 sample requires, over chips x the chip's bf16 peak. Eval, the
upload forward pass and recomputation are not counted."""


def read(run):
    t = run.trace
    if t is None or run.cell.unit != "samples" or t.window_s <= 0:
        return None
    rate = run.work / t.window_s
    return 100.0 * rate * run.cell.flops_per_unit / (
        run.chips * run.peaks["bf16_flops"])
