"""Model FLOPs of the decode rows actually kept (dense, LoRA, head and
attention over each token's live context) over the device time of the
decode programs times the chip's peak."""
from harness.readings import program_s, share


def read(run):
    t = program_s(run, "decode")
    return share(run.decode_flops(), t * run.peaks["bf16_flops_per_s"])
