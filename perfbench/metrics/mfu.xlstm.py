"""Model FLOP/s utilisation of the whole LM client step, in %: training
sequences per second of the traced window x the forward-and-backward FLOPs
one sequence requires (the model's matmuls, the mLSTM chunk terms, the LM
head and its share of the CoRS discriminator; not recomputation, the
embedding lookup or the one-hot prototype matmul), over chips x the chip's
bf16 peak."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    rate = run.work / t.window_s
    return 100.0 * rate * run.cell.flops_per_unit / (
        run.chips * run.peaks["bf16_flops"])
