"""Resume engine: share of the index-hit prompt chunks that the engine
restored instead of recomputing (``resumed_chunks`` over ``hit_chunks``
of the answered requests), in %.  A recurrent model resumes only up to
its deepest state snapshot, so the snapshot rule (or a missing slab)
shows here as hit chunks computed again."""


def read(data):
    done = [r.doc for r in data.window if r.status == 200 and r.doc]
    hit = sum(d["hit_chunks"] for d in done)
    return 100.0 * sum(d["resumed_chunks"] for d in done) / hit \
        if hit else None
