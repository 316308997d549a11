"""Jit'd wrappers for the string-match kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.string_match.kernel import string_match_pallas
from repro.kernels.string_match.ref import string_match_ref


def string_match(text, pattern, *, use_kernel: bool = True,
                 tile: int = 4096, interpret: bool | None = None):
    """Exact-match start positions of ``pattern`` in ``text``.

    Parameters
    ----------
    text : (N,) uint8
        Haystack bytes.
    pattern : (P,) uint8
        Needle bytes (``P`` becomes a static kernel parameter).
    use_kernel : bool
        False = numpy-style reference path.
    tile : int
        Text bytes per kernel grid step (compared in int32 lanes).
    interpret : bool, optional
        Pallas interpret-mode flag (None: compiled on a TPU,
        interpreted on the CPU).

    Returns
    -------
    jnp.ndarray, shape (N,), int8
        1 at every position where ``text[i : i + P] == pattern``.
    """
    text = jnp.asarray(text, jnp.uint8)
    pattern = jnp.asarray(pattern, jnp.uint8)
    if not use_kernel:
        return string_match_ref(text, pattern)
    return string_match_pallas(
        text, pattern, pattern_len=int(pattern.shape[0]), tile=tile,
        interpret=interpret)


def count_matches(text, pattern, **kw) -> jnp.ndarray:
    return jnp.sum(string_match(text, pattern, **kw).astype(jnp.int32))
