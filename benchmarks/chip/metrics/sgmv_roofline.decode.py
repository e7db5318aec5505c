"""Least time of the SGMV kernel's calls in decode (per layer, step and
LoRA site: the running rows in and out and the A/B factors of their
distinct adapters) over its time in the trace's decode programs."""
from harness.readings import kernel_s, share


def read(run):
    return share(run.sgmv_decode_min_s(), kernel_s(run, "decode", "sgmv"))
