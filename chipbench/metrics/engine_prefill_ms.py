"""Resume engine: median host span of one ``prefill_fn`` call (slab
fetch, concat and upload, the prefill program, slab slicing), in ms."""
import statistics


def read(data):
    spans = [(c["end"] - c["t"]) * 1e3 for c in data.spans.prefill]
    return statistics.median(spans) if spans else None
