"""Operations and bytes the served work needs, computed from shapes.

These are the yardstick for the roofline and utilization shares: a
program's share is what these functions count, at the chip's peak, over
the device time the trace gives it.  They count what the algorithm
needs, never what a particular program happens to do beyond it (masked
key positions, bucket padding), so a share can only understate.

A model step's counts belong to its architecture: ``prefill_flops``,
``decode_flops`` and ``decode_bytes`` call the functions of the same
names in the reference module the configuration names (``reference``
package), so a configuration of a new architecture brings its counts
with its reference.  The peaks and the index's search are counted here.
"""
from __future__ import annotations

import json
import pathlib

from chipbench import reference

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak row for a ``jax.Device.device_kind``; unknown kinds are
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def prefill_flops(cfg: dict, rows: int, prefix: int, suffix: int) -> float:
    """Model FLOPs of a prefill that computes ``suffix`` tokens per row
    after a restored ``prefix``."""
    return reference.module(cfg).prefill_flops(cfg, rows, prefix, suffix)


def decode_flops(cfg: dict, rows: int, pos: int) -> float:
    """Model FLOPs of one decode step writing position ``pos``."""
    return reference.module(cfg).decode_flops(cfg, rows, pos)


def decode_bytes(cfg: dict, rows: int, pos: int) -> float:
    """Bytes one decode step writing position ``pos`` must move."""
    return reference.module(cfg).decode_bytes(cfg, rows, pos)


def xam_lookup(queries: int, key_bits: int, ways: int, sets: int,
               set_bytes: int) -> tuple[float, float]:
    """(int8 ops, bytes) of one multiset XAM search: each query scores
    its set's ``key_bits x ways`` plane (two ops a multiply-add); the
    bytes are the queries' key bits (one byte each), the stored plane
    and validity of each set a query touches (``set_bytes`` a set),
    read once, and an int32 result a query.  Padding to blocks is not
    counted."""
    ops = 2.0 * queries * key_bits * ways
    nbytes = queries * key_bits + sets * set_bytes + 4 * queries
    return ops, float(nbytes)
