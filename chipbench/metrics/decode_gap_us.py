"""Model step: device idle time named by the resume engine's decode spans
(``monarch.decode`` and its per-token ``.sync`` and ``.dispatch``) over
the decode program's runs wholly inside the traced window, in us a step:
the host's turn-around between one step and the next."""
from chipbench import program_spans
from chipbench.readers import program_runs


def read(data):
    ps = program_spans.load(data)
    if not ps or not ps.has("monarch.decode"):
        return None
    runs = program_runs(data, "decode")[0]
    if not runs:
        return None
    idle = sum(v for k, v in ps.idle.items()
               if k == "monarch.decode" or k.startswith("monarch.decode."))
    return 1e6 * idle / runs
