"""Resume engine: share of the KV slab bytes restored (``nbytes`` of the
``monarch.resume.restore`` spans wholly inside the traced window) that
came from slabs resident on the device (their ``device_nbytes``), in %.
A program whose restore spans carry no ``device_nbytes`` reports
nothing."""
from chipbench import program_spans


def read(data):
    ps = program_spans.load(data)
    got = ps.named("monarch.resume.restore") if ps else []
    if not got or any("device_nbytes" not in s.args for s in got):
        return None
    total = sum(s.args["nbytes"] for s in got)
    device = sum(s.args["device_nbytes"] for s in got)
    return 100.0 * device / total if total else None
