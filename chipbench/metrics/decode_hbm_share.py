"""Model step: bytes a decode step must read (weights, each row's cache
up to its position) over the decode program's device time a step at
HBM bandwidth, in %."""
from chipbench import cost
from chipbench.readers import share


def read(data):
    nbytes = [cost.decode_bytes(data.cell.cfg, c["rows"], c["pos"] + t)
              for c in data.spans.decode for t in range(c["steps"])]
    return share(data, "decode", nbytes, data.peaks["hbm_bytes_per_s"])
