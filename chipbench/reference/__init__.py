"""The plain references, one module a model family, found by name.

A configuration file names its reference with the key ``reference``:
the module ``chipbench/reference/<reference>.py`` of the checkout the
configuration is loaded from (``harness.load_config``, which records
that file under ``reference_path``).  A module imports nothing of the
program under test and provides

``logits(cfg, tokens, first, quant=None)``
    float32 logits of ``tokens`` (B, L) at positions ``first..L-1``, a
    (B, L - first, vocab) host array, its weights drawn from the
    configuration's own init recipe; ``quant`` (``"int8"``, ``"fp8"``)
    is the control, the same forward with its weights rounded below
    the precision served;
``prefill_flops(cfg, rows, prefix, suffix)``
    model FLOPs of a prefill computing ``suffix`` tokens a row after a
    restored ``prefix``;
``decode_flops(cfg, rows, pos)``, ``decode_bytes(cfg, rows, pos)``
    FLOPs and bytes of one decode step writing position ``pos``.

A new architecture joins the benchmark as a new module here, its
configuration file and its cells: ``harness`` and ``cost`` dispatch to
the module a configuration names.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib


@functools.lru_cache(maxsize=None)
def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_reference_{pathlib.Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module(cfg: dict):
    """The reference module of a configuration loaded by
    ``harness.load_config``."""
    return _load(cfg["reference_path"])
