"""Resume engine: device idle time named by the ``monarch.resume.restore``
span (the innermost program span open at the gap's midpoint) over the
traced window, in %."""
from chipbench import program_spans

SPAN = "monarch.resume.restore"


def read(data):
    ps = program_spans.load(data)
    if not ps or not ps.has(SPAN):
        return None
    return 100.0 * ps.idle.get(SPAN, 0.0) / ps.window_s
