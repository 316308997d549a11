"""Model step: model FLOPs of the prefill tokens actually computed
(layers, attention over prefix and suffix, the head for the last token)
over the prefill programs' device time at the bf16 peak, in %."""
from chipbench import cost
from chipbench.readers import share


def read(data):
    flops = [cost.prefill_flops(data.cell.cfg, c["rows"], c["prefix"],
                                c["suffix"]) for c in data.spans.prefill]
    return share(data, "prefill", flops, data.peaks["bf16_flops"])
