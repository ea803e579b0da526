"""Model FLOPs of the tokens the window computed (the benchmark's own count:
projections, LoRA, MLP, attention over each token's real context, the LM
head once per emitted token) over the traced window times the bf16 peak."""

from perfkit import readers


def read(run):
    return readers.step_mfu(run)
