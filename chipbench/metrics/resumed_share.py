"""Index lookup: share of answered prompt chunks restored from KV slabs
instead of prefilled (``resumed_chunks`` over ``chunks``), in %."""


def read(data):
    done = [r.doc for r in data.window if r.status == 200 and r.doc]
    chunks = sum(d["chunks"] for d in done)
    return 100.0 * sum(d["resumed_chunks"] for d in done) / chunks \
        if chunks else None
