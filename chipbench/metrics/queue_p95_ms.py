"""Router: 95th percentile of the time an answered request waited in
the router's queue (its ``queued_ms`` field)."""
from chipbench.harness import quantile


def read(data):
    q = [r.doc["queued_ms"] for r in data.window
         if r.status == 200 and r.doc]
    return quantile(q, 0.95) if q else None
