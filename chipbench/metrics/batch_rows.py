"""Router: mean rows of a micro-batch.  Every answered request reports
the rows of the batch that served it (``batched_rows``); a batch of r
rows is reported r times, so the batches number the sum of 1/r."""


def read(data):
    rows = [r.doc["batched_rows"] for r in data.window
            if r.status == 200 and r.doc]
    return len(rows) / sum(1.0 / r for r in rows) if rows else None
