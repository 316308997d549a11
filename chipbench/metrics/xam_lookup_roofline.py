"""Index lookup kernel: least time for the multiset XAM search's int8
ops at the int8 peak or its bytes at HBM bandwidth, whichever is larger,
over its device time in the trace, in %."""
from chipbench import cost
from chipbench.readers import share


def read(data):
    least = []
    for c in data.spans.lookup:
        ops, nbytes = cost.xam_lookup(c["queries"], c["key_bits"], c["ways"],
                                      c["sets"], c["set_bytes"])
        least.append(max(ops / data.peaks["int8_ops"],
                         nbytes / data.peaks["hbm_bytes_per_s"]))
    return share(data, "lookup", least, 1.0)
