"""Whole serving step: model FLOPs of every prefill and decode step
that ran in the traced window over the window's length at the bf16
peak, in %.  It bounds every kernel's gain: a kernel taken off the path
leaves its own roofline silent, never this."""
import statistics

from chipbench import cost
from chipbench.readers import program_runs


def read(data):
    cfg = data.cell.cfg
    pre = [cost.prefill_flops(cfg, c["rows"], c["prefix"], c["suffix"])
           for c in data.spans.prefill]
    dec = [cost.decode_flops(cfg, c["rows"], c["pos"] + t)
           for c in data.spans.decode for t in range(c["steps"])]
    if not pre or not dec:
        return None
    flops = (program_runs(data, "prefill")[0] * statistics.fmean(pre)
             + program_runs(data, "decode")[0] * statistics.fmean(dec))
    return 100.0 * flops / (data.trace["window_s"] * data.peaks["bf16_flops"])
