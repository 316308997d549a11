"""Host spans of the serving path, on the JAX profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``monarch.<name>``: it records only while a profiler session runs (a
``jax.profiler.start_trace`` call, or a capture through the server that
``launch/httpd.py --profiler-port`` opens), and costs about a
microsecond otherwise.  Its events sit on the same timeline as the
device's programs, so an idle gap on the device can be named by the
host work that held it.  Args are plain ints read from host shapes and
sizes; an arg known only at the span's end is added with
``set_metadata`` on the object the ``with`` yields.  The span table is
in docs/SERVING.md ("Tracing").
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "monarch."


def span(name: str, **args) -> TraceAnnotation:
    return TraceAnnotation(PREFIX + name, **args)
