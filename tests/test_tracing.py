"""The serving path's ``monarch.*`` spans and wait counters.

A reduced yi-9b ``PrefixResumeEngine`` behind a ``ServeRouter`` and the
HTTP edge serves one miss, then the same prompts again as a hit, under a
CPU profiler session; a twin stack serves the same requests with the
profiler off.  The profile must hold every span of the serving path
(docs/SERVING.md, "Tracing"), nested as documented, with args equal to
the shapes served, and the decoded tokens must not depend on whether a
profiler runs.  Every test that records a profile lives in this file: a
profiler session is per process.
"""
from __future__ import annotations

import collections
import http.client
import json
import pathlib
import socket
import threading
import time

import numpy as np
import pytest

import jax

from repro import configs
from repro.models import transformer
from repro.serve.admit_queue import AdmitQueue
from repro.serve.http_frontend import HttpFrontend, ServeRouter
from repro.serve.kv_index import (CHUNK_TOKENS, KVIndexConfig, KVSlabStore,
                                  MonarchKVIndex)
from repro.serve.resume import PrefixResumeEngine

ROWS, PROMPT, DECODE = 2, 64, 4
RUN = (PROMPT - 1) // CHUNK_TOKENS          # chunks a hit resumes


def _index():
    return MonarchKVIndex(KVIndexConfig(
        n_sets=8, set_ways=16, admit_after_reads=0, rotate_every=1 << 30,
        fingerprint="prefix"), slab_store=KVSlabStore())


def _post(fe, toks):
    conn = http.client.HTTPConnection(*fe.address, timeout=120)
    conn.request("POST", "/v1/generate",
                 body=json.dumps({"tokens": toks.tolist()}))
    resp = conn.getresponse()
    doc = json.loads(resp.read())
    conn.close()
    assert resp.status == 200, doc
    return doc


def _serve(params, cfg, toks, trace_dir=None):
    """Miss, then hit, of ``toks`` through a fresh stack; returns the two
    answers, the queue's stats, the slabs a hit restores, the chunks'
    sets and the index."""
    idx = _index()
    q = AdmitQueue(idx)
    engine = PrefixResumeEngine(params, cfg, max_seq=PROMPT + DECODE,
                                index=idx, decode_tokens=DECODE)
    prefill_fn, decode_fn = engine.request_fns()
    fe = HttpFrontend(ServeRouter(q, prefill_fn=prefill_fn,
                                  decode_fn=decode_fn, n_workers=1,
                                  batch_window_s=0.0)).start()
    try:
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        try:
            docs = [_post(fe, toks), _post(fe, toks)]
            q.flush()                    # the hit's admission ends inside
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
        fps = idx.fingerprints(toks)
        restored = [idx.slab_store.get(int(fps[r, k]))
                    for r in range(ROWS) for k in range(RUN)]
        set_ids = idx._set_of(fps.reshape(-1))
    finally:
        fe.shutdown()
        q.close()
    return docs, q.stats, restored, set_ids, idx


Span = collections.namedtuple("Span", "name line start end args")


def _spans(trace_dir) -> list:
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out, n = [], 0
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("monarch."):
                    out.append(Span(ev.name, n, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
            n += 1
    return sorted(out, key=lambda s: s.start)


def _inside(child, parent) -> bool:
    return (child.line == parent.line and parent.start <= child.start
            and child.end <= parent.end)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = configs.get_arch("yi-9b").reduced()
    params = transformer.init_params(jax.random.PRNGKey(3), cfg)
    toks = np.random.default_rng(5).integers(
        1, cfg.vocab_size, (ROWS, PROMPT)).astype(np.int32)
    trace_dir = tmp_path_factory.mktemp("trace")
    on = _serve(params, cfg, toks, trace_dir)
    off = _serve(params, cfg, toks)
    return {"on": on, "off": off, "spans": _spans(trace_dir)}


def _named(served, name):
    return [s for s in served["spans"] if s.name == name]


@pytest.mark.parametrize("name", [
    "monarch.edge", "monarch.router.batch", "monarch.router.serve",
    "monarch.lookup", "monarch.lookup.wait", "monarch.lookup.search",
    "monarch.resume.prefill", "monarch.resume.match",
    "monarch.resume.restore", "monarch.resume.step", "monarch.resume.slice",
    "monarch.decode", "monarch.decode.sync", "monarch.decode.dispatch",
    "monarch.admit", "monarch.admit.wait"])
def test_every_span_is_recorded(served, name):
    assert _named(served, name)


@pytest.mark.parametrize("child,parent", [
    ("monarch.resume.match", "monarch.resume.prefill"),
    ("monarch.resume.restore", "monarch.resume.prefill"),
    ("monarch.resume.step", "monarch.resume.prefill"),
    ("monarch.resume.slice", "monarch.resume.prefill"),
    ("monarch.decode.sync", "monarch.decode"),
    ("monarch.decode.dispatch", "monarch.decode"),
    ("monarch.lookup.wait", "monarch.lookup"),
    ("monarch.lookup.search", "monarch.lookup"),
    ("monarch.admit.wait", "monarch.admit"),
    ("monarch.lookup", "monarch.router.serve"),
    ("monarch.resume.prefill", "monarch.router.serve"),
    ("monarch.decode", "monarch.router.serve")])
def test_spans_nest(served, child, parent):
    parents = _named(served, parent)
    for c in _named(served, child):
        assert any(_inside(c, p) for p in parents), (c, parents)


def test_prefill_args_are_the_shapes(served):
    miss, hit = _named(served, "monarch.resume.prefill")
    assert miss.args == {"rows": ROWS, "prefix": 0, "suffix": PROMPT}
    p_len = RUN * CHUNK_TOKENS
    assert hit.args == {"rows": ROWS, "prefix": p_len,
                        "suffix": PROMPT - p_len}
    # Only the hit restores; the miss stages every chunk of both rows.
    (restore,) = _named(served, "monarch.resume.restore")
    assert _inside(restore, hit)
    restored = served["on"][2]
    nbytes = sum(a.nbytes for slab in restored
                 for a in jax.tree.leaves(slab))
    # The slabs live on the device (unbounded tier on the CPU backend);
    # an attention-only model restores no recurrent state.
    assert restore.args == {"rows": ROWS, "nbytes": nbytes,
                            "device_nbytes": nbytes, "state_nbytes": 0}
    first_slice = _named(served, "monarch.resume.slice")[0]
    assert first_slice.args["nbytes"] == ROWS * PROMPT // CHUNK_TOKENS \
        * sum(a.nbytes for a in jax.tree.leaves(restored[0]))


def test_lookup_and_admit_args(served):
    idx = served["on"][4]
    set_ids = served["on"][3]
    queries = ROWS * PROMPT // CHUNK_TOKENS
    for lk in _named(served, "monarch.lookup"):
        assert lk.args == {"queries": queries}
    set_bytes = sum(int(np.prod(a.shape[1:])) * np.dtype(a.dtype).itemsize
                    for a in (idx.bits, idx.valid))
    for s in _named(served, "monarch.lookup.search"):
        assert s.args == {"queries": queries, "key_bits": idx.cfg.key_bits,
                          "ways": idx.cfg.set_ways,
                          "sets": np.unique(set_ids).size,
                          "set_bytes": set_bytes}
    admits = _named(served, "monarch.admit")
    assert sum(a.args["fps"] for a in admits) >= queries
    assert all(a.args["batches"] >= 1 for a in admits)


def test_router_and_edge_args(served):
    for name in ("monarch.router.batch", "monarch.router.serve"):
        assert [s.args["rows"] for s in _named(served, name)] == [ROWS] * 2
    assert [s.args["requests"]
            for s in _named(served, "monarch.router.serve")] == [1, 1]
    assert [s.args for s in _named(served, "monarch.edge")] == \
        [{"rows": ROWS, "tokens": ROWS * PROMPT}] * 2


def test_one_sync_per_decoded_token(served):
    """One read a token, one dispatch a step: the last token is read
    with no step after it, so n tokens take n-1 steps."""
    decodes = _named(served, "monarch.decode")
    assert len(decodes) == 2
    for d in decodes:
        assert d.args == {"rows": ROWS, "pos": PROMPT, "steps": DECODE,
                          "dispatched": DECODE - 1}
        for child, count in (("monarch.decode.sync", DECODE),
                             ("monarch.decode.dispatch", DECODE - 1)):
            assert sum(_inside(s, d) for s in _named(served, child)) \
                == count


def test_tokens_do_not_depend_on_the_profiler(served):
    on, off = served["on"][0], served["off"][0]
    assert [d["tokens"] for d in on] == [d["tokens"] for d in off]
    assert on[0]["tokens"] == on[1]["tokens"]          # hit == miss
    assert [d["resumed_chunks"] for d in on] == [0, ROWS * RUN]


def test_first_token_ms(served):
    for doc in served["on"][0] + served["off"][0]:
        assert 0 < doc["first_token_ms"] <= \
            doc["queued_ms"] + doc["service_ms"]


def test_lookup_counters(served):
    for stats in (served["on"][1], served["off"][1]):
        assert stats.lookups == 2
        assert stats.lookup_wait_s >= 0


def test_lookup_wait_covers_the_flush_and_the_lock(tmp_path):
    """A lookup of a fingerprint still queued for admission waits for the
    read-your-writes flush; admission itself waits for the index lock,
    held here for 50 ms.  Both waits are timed and spanned."""
    q = AdmitQueue(_index())
    toks = np.arange(1, 1 + 2 * CHUNK_TOKENS, dtype=np.int32)[None]
    try:
        q.lookup(toks)                       # warm the search
        jax.profiler.start_trace(str(tmp_path))
        try:
            q._idx_lock.acquire()
            q.submit_tokens(toks)            # the worker waits for the lock
            threading.Timer(0.05, q._idx_lock.release).start()
            t = time.perf_counter()
            hits = q.lookup(toks)
            took = time.perf_counter() - t
        finally:
            jax.profiler.stop_trace()
        assert hits.all()
        assert q.stats.lookups == 2 and q.stats.rww_flushes == 1
        assert 0.04 <= q.stats.lookup_wait_s <= took
    finally:
        q.close()
    spans = _spans(tmp_path)
    (lk,) = [s for s in spans if s.name == "monarch.lookup"]
    (flush,) = [s for s in spans if s.name == "monarch.lookup.flush"]
    (admit_wait,) = [s for s in spans if s.name == "monarch.admit.wait"]
    assert _inside(flush, lk)
    assert (flush.end - flush.start) / 1e9 >= 0.04
    assert (admit_wait.end - admit_wait.start) / 1e9 >= 0.04


def test_profiler_port_serves_live_captures(tmp_path):
    """``launch/httpd.py --profiler-port`` opens the profiler's server: a
    capture from outside the serving code records its spans."""
    from jax import collect_profile
    from repro.launch import httpd
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    args = httpd.build_parser().parse_args(
        ["--arch", "yi-9b", "--reduced", "--port", "0", "--prompt-len",
         "32", "--decode-tokens", "2", "--batch-window-ms", "0",
         "--profiler-port", str(port)])
    fe, q = httpd.build_frontend(args)
    fe.start()
    try:
        fresh = lambda i: np.arange(i, i + 32, dtype=np.int32)[None]  # noqa
        _post(fe, fresh(1))                  # compiles the miss path
        capture = threading.Thread(target=collect_profile.collect_profile,
                                   args=(port, 1500, "localhost",
                                         str(tmp_path), True))
        capture.start()
        time.sleep(0.5)
        _post(fe, fresh(100))
        capture.join(timeout=60)
        assert not capture.is_alive()
    finally:
        jax.profiler.stop_server()
        fe.shutdown()
        q.close()
    names = {s.name for s in _spans(tmp_path)}
    assert {"monarch.edge", "monarch.resume.prefill",
            "monarch.decode"} <= names
