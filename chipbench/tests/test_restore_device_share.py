"""``restore_device_share`` on made-up program spans: the device-resident
share of the restored bytes, over the restore spans inside the window,
and nothing where the restore spans carry no ``device_nbytes``."""
import types

import pytest

from chipbench import harness, program_spans

NAMES = ("restore_device_share.online", "restore_device_share.batch")


def _restore(start, nbytes, **args):
    return program_spans.Span("monarch.resume.restore", 0, start,
                              start + 0.01, dict(rows=2, nbytes=nbytes,
                                                 **args))


def _data(spans):
    data = types.SimpleNamespace(cell=types.SimpleNamespace(name="x"))
    data._program_spans = (None if spans is None else
                           program_spans.ProgramSpans(
                               window_s=1.0, idle={}, spans=spans))
    return data


@pytest.mark.parametrize("name", NAMES)
def test_share_of_restored_bytes_on_the_device(name):
    d = _data([_restore(0.1, 600, device_nbytes=600),
               _restore(0.4, 400, device_nbytes=100),
               program_spans.Span("monarch.resume.slice", 0, 0.5, 0.6,
                                  {"nbytes": 50})])
    assert harness._reader(name)(d) == pytest.approx(70.0)


@pytest.mark.parametrize("spans", [
    None,                                             # no trace
    [],                                               # no restore
    [_restore(0.1, 600)],                             # parent's spans
    [_restore(0.1, 0, device_nbytes=0)]])             # nothing restored
def test_nothing_to_read(spans):
    assert all(harness._reader(n)(_data(spans)) is None for n in NAMES)
