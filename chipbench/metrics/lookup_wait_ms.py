"""Index lookup: 95th percentile, over the ``monarch.lookup`` spans wholly
inside the traced window, of a lookup's time in its read-your-writes
flush (``monarch.lookup.flush``) and waiting for the index lock, which
admission holds (``monarch.lookup.wait``), in ms."""
import collections

from chipbench import program_spans
from chipbench.harness import quantile

WAITS = ("monarch.lookup.flush", "monarch.lookup.wait")


def read(data):
    ps = program_spans.load(data)
    lookups = ps.named("monarch.lookup") if ps else []
    if not lookups:
        return None
    waits = collections.defaultdict(list)
    for s in ps.spans:
        if s.name in WAITS:
            waits[s.line].append(s)
    return quantile([1e3 * sum(w.end - w.start for w in waits[lk.line]
                               if lk.start <= w.start and w.end <= lk.end)
                     for lk in lookups], 0.95)
