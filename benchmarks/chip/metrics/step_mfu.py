"""Model FLOPs of the samples finished in the window, over the window and
the chips' bf16 peak. Counts 6 x N x T per sample (``chipbench.flops``);
recomputed work is not counted."""


def read(run):
    if run.flops_per_sample is None or run.chip is None:
        return None
    chips = run.cell.chips
    return 100.0 * run.flops_per_sample * run.samples / run.window_s / (
        chips * run.chip.bf16_flops)
