"""Resume engine: bytes of KV slabs restored (fetched, concatenated on the
host and uploaded) over the time of the ``monarch.resume.restore`` spans
wholly inside the traced window, in GB/s."""
from chipbench import program_spans


def read(data):
    ps = program_spans.load(data)
    got = ps.named("monarch.resume.restore") if ps else []
    secs = sum(s.end - s.start for s in got)
    return sum(s.args["nbytes"] for s in got) / secs / 1e9 if secs else None
