"""One run of one cell: boot the program's own serving stack, fill and
warm it, offer the cell's traffic for a window, then check the answers.

Everything a cell needs is found by name in data files: the cell in
``BENCHMARK.json``, its configuration in ``configs/``, its traffic mix in
``traffic/``, its correctness limits in ``limits/<cell>.json``, and each
per-layer metric's reader in ``metrics/``.  Adding a cell is adding such
files and entries.

A configuration names its plain reference with the key ``reference``:
the module ``reference/<reference>.py``, which gives the logits that
``correct`` compares with the served tokens and the counts of a model
step that ``cost`` hands to the readers (the interface is in
``reference/__init__.py``).  A new architecture is a new reference
module beside its configuration; nothing here names one.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import gc
import json
import pathlib
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from chipbench import reference, traffic

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
#: Micro-batch rows the router may coalesce (``ServeRouter`` default).
MAX_ROWS = 8
#: Seconds past the window's close an answer may still come.
DRAIN_S = 60.0
#: Client threads of the open loop (requests in flight at once).
OPEN_LOOP_THREADS = 96
#: Seconds of the window a ``--trace 1`` run profiles (traces are large).
TRACE_S = 10.0
#: Token stream of the warm-up requests: never the window's own.
WARM_STREAM = 5
#: Largest admission unit the queue coalesces (``AdmitQueue``'s documented
#: cap of 8192 fingerprints): the warm-up offers units up to this size.
ADMIT_UNIT_MAX = 8192
#: Fixed stream of the fresh fingerprints that warm the admission grids,
#: so every run warms the same grids whatever its seed.
WARM_FP_SEED = 6


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's files.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    spec: dict            # the BENCHMARK.json workload entry
    cfg: dict             # configs/<config>.json
    mix: dict             # traffic/<traffic>.json
    limits: dict          # limits/<cell>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_config(path: pathlib.Path, root: pathlib.Path = ROOT) -> dict:
    """A configuration file, with the file of the reference module it
    names (``chipbench/reference/<reference>.py`` under ``root``) added
    under ``reference_path``.  The key has no default."""
    cfg = json.loads(path.read_text())
    if "reference" not in cfg:
        raise KeyError(f"{path}: no 'reference' key naming its module in "
                       "chipbench/reference/")
    ref = root / "chipbench" / "reference" / f"{cfg['reference']}.py"
    if not ref.is_file():
        raise FileNotFoundError(f"{path}: reference module {ref} not found")
    return {**cfg, "reference_path": str(ref)}


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    specs = {w["name"]: w for w in bench["workloads"]}
    if name not in specs:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(specs)}")
    spec = specs[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = load_config(root / cfgs[spec["config"]]["file"], root)
    mix = traffic.load(root / "chipbench" / "traffic"
                       / f"{spec['traffic']}.json")
    limits = json.loads(
        (root / "chipbench" / "limits" / f"{name}.json").read_text())
    return Cell(name=name, spec=spec, cfg=cfg, mix=mix, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


# ---------------------------------------------------------------------------
# The system under test.
# ---------------------------------------------------------------------------

def boot(cfg: dict, mix: dict):
    """The launcher's own stack (``repro.launch.httpd.build_frontend``)
    with its defaults; only the model, the lengths and the port are set."""
    from repro.launch.httpd import build_frontend, build_parser
    args = build_parser().parse_args(cfg["launcher_args"] + [
        "--prompt-len", str(traffic.prompt_tokens(mix)),
        "--decode-tokens", str(mix["decode_tokens"]), "--port", "0"])
    with contextlib.redirect_stdout(sys.stderr):
        frontend, admit_q = build_frontend(args)
    frontend.start()
    return frontend, admit_q


def _submit(frontend, toks: np.ndarray) -> dict:
    out = frontend.router.submit(toks, timeout=600.0)
    got = np.asarray(out["tokens"])
    if got.shape[0] != toks.shape[0]:
        raise RuntimeError(f"set-up request answered {got.shape} tokens")
    return out


def fill(frontend, admit_q, plan: traffic.Plan) -> None:
    """Make every shared prefix resident: send prefixes (with fresh
    suffixes) until a lookup finds all their chunks."""
    if not len(plan.prefixes):
        return
    pool = len(plan.prefixes)
    rng = np.random.default_rng([plan.seed, 2])
    for _ in range(4):
        hits = admit_q.lookup(plan.prefixes)
        todo = [i for i in range(pool) if not hits[i].all()]
        if not todo:
            return
        step = plan.mix.get("fill_rows", MAX_ROWS)
        for lo in range(0, len(todo), step):
            rows = todo[lo:lo + step]
            fresh = rng.integers(traffic.LOW_TOKEN, plan.vocab,
                                 (len(rows), plan.mix["suffix_tokens"]))
            _submit(frontend, np.concatenate(
                [plan.prefixes[rows], fresh], axis=1).astype(np.int32))
        admit_q.flush()
    hits = admit_q.lookup(plan.prefixes)
    raise RuntimeError(f"set-up: {int((~hits.all(axis=1)).sum())} of {pool} "
                       "prefixes not resident after four fills")


def admit_unit_sizes() -> list:
    """Sizes of the fresh-fingerprint units that warm the admission
    grids: every size to 16, then steps of a quarter up to the largest
    unit.  A grid's shape follows the largest per-set count of a unit,
    which these sizes reach from 1 to the most a unit can hold."""
    sizes = list(range(1, 17))
    while sizes[-1] < ADMIT_UNIT_MAX:
        sizes.append(min(int(sizes[-1] * 1.25), ADMIT_UNIT_MAX))
    return sizes


def warm(frontend, admit_q, plan: traffic.Plan) -> None:
    """Compile (or load from the cache) every program the window can
    call, through the program's public entry points: the cell's own
    prefill and decode at 1..MAX_ROWS rows through the router, every
    lookup-kernel block count those row counts can reach, and every
    admission grid a coalesced submit can reach."""
    from repro.kernels.xam_search import ops as xam_ops
    for rows in range(1, MAX_ROWS + 1):
        first = rows * (rows - 1) // 2          # every warm request fresh
        toks = np.concatenate([plan.tokens(first + k, stream=WARM_STREAM)
                               for k in range(rows)], axis=0)
        _submit(frontend, toks)
    admit_q.flush()

    idx = admit_q.index
    chunks = traffic.prompt_tokens(plan.mix) // 16
    n_sets = idx.cfg.n_sets
    for rows in range(1, MAX_ROWS + 1):
        q = rows * chunks
        keys = np.zeros((q, idx.cfg.key_bits), np.int8)
        # Queries piled on one set, then k of them moved to sets of their
        # own: every block count from the fewest to the most.
        for k in range(min(n_sets, q)):
            sets = np.zeros(q, np.int32)
            sets[:k] = np.arange(1, k + 1)
            xam_ops.xam_search_multiset(keys, sets, idx.bits, idx.valid)
    # Fresh fingerprints are only first touches: they install nothing.
    rng = np.random.default_rng(WARM_FP_SEED)
    for n in admit_unit_sizes():
        fps = rng.choice(2 ** 32 - 1, n, replace=False).astype(np.uint32)
        admit_q.submit(fps + np.uint32(1))
        admit_q.flush()


# ---------------------------------------------------------------------------
# Load.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    i: int
    due: float                 # scheduled arrival (open) / send (closed)
    sent: float
    done: float = float("nan")
    status: int = 0
    doc: dict | None = None


def _post(port: int, body: bytes, timeout: float):
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate", data=body,
        headers={"Content-Type": "application/json"})
    try:
        with opener.open(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, None
    except (urllib.error.URLError, TimeoutError, ConnectionError):
        return -1, None


def _send(port: int, plan: traffic.Plan, rec: Record, t0: float) -> Record:
    body = json.dumps({"tokens": plan.tokens(rec.i).tolist()}).encode()
    rec.sent = time.perf_counter() - t0
    rec.status, rec.doc = _post(port, body, DRAIN_S + 600)
    rec.done = time.perf_counter() - t0
    return rec


def drive_open(port: int, plan: traffic.Plan, seconds: float, t0: float,
               on_close=None) -> list:
    """Send each request at its scheduled time; latency counts from the
    schedule, so a stalled server is charged for the backlog it causes."""
    recs = []
    with concurrent.futures.ThreadPoolExecutor(OPEN_LOOP_THREADS) as pool:
        futs = []
        for i, due in enumerate(plan.arrivals_s):
            wait = due - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            rec = Record(i=i, due=float(due), sent=float("nan"))
            recs.append(rec)
            futs.append(pool.submit(_send, port, plan, rec, t0))
        rest = seconds - (time.perf_counter() - t0)
        if rest > 0:
            time.sleep(rest)
        if on_close:
            on_close()
        concurrent.futures.wait(futs, timeout=DRAIN_S)
        for f in futs:
            if f.done():
                f.result()
    return recs


def drive_closed(port: int, plan: traffic.Plan, seconds: float, t0: float,
                 on_close=None) -> list:
    """``clients`` callers, each sending its next request as soon as the
    previous one is answered, until the window closes."""
    recs, lock = [], threading.Lock()
    counter = iter(range(len(plan)))

    def client():
        while time.perf_counter() - t0 < seconds:
            with lock:
                i = next(counter, None)
                if i is None:
                    raise RuntimeError("closed-loop plan exhausted: raise "
                                       "closed_max_per_client")
                rec = Record(i=i, due=time.perf_counter() - t0,
                             sent=float("nan"))
                recs.append(rec)
            _send(port, plan, rec, t0)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(plan.mix["clients"])]
    for t in threads:
        t.start()
    rest = seconds - (time.perf_counter() - t0)
    if rest > 0:
        time.sleep(rest)
    if on_close:
        on_close()
    deadline = time.perf_counter() + DRAIN_S
    for t in threads:
        t.join(timeout=max(deadline - time.perf_counter(), 0.0))
    return recs


# ---------------------------------------------------------------------------
# Host spans around the calls into each layer (traced runs only).
# ---------------------------------------------------------------------------

class Spans:
    """Wraps the program's public calls into each layer (the router's
    prefill and decode, the queue's lookup, the index's admission, the
    multiset search) in ``TraceAnnotation`` spans and records, per call,
    what the cost functions need, read from the call's own arrays.
    Calls are kept only while ``on`` is set."""

    def __init__(self, frontend, admit_q):
        import jax
        from repro.kernels.xam_search import ops as xam_ops
        self.on = False
        self.t0 = 0.0
        self.prefill, self.decode, self.lookup = [], [], []
        self._jax = jax
        router = frontend.router
        self._undo = []

        def patch(obj, attr, fn):
            old = getattr(obj, attr)
            self._undo.append((obj, attr, old, attr in vars(obj)))
            setattr(obj, attr, fn(old))

        patch(router, "prefill_fn", self._wrap_prefill)
        patch(router, "decode_fn", self._wrap_decode)
        patch(admit_q, "lookup", self._wrap_span("chipbench.lookup"))
        patch(admit_q.index, "admit_fps", self._wrap_span("chipbench.admit"))
        patch(xam_ops, "xam_search_multiset", self._wrap_search)

    def close(self):
        for obj, attr, old, own in reversed(self._undo):
            if own:
                setattr(obj, attr, old)
            else:
                delattr(obj, attr)
        self._undo = []

    def _now(self):
        return time.perf_counter() - self.t0

    def _wrap_span(self, name):
        def wrap(fn):
            def call(*a, **k):
                with self._jax.profiler.TraceAnnotation(name):
                    return fn(*a, **k)
            return call
        return wrap

    def _wrap_prefill(self, fn):
        def call(toks, hits):
            t = self._now()
            with self._jax.profiler.TraceAnnotation("chipbench.prefill"):
                res = fn(toks, hits)
            if self.on:
                rows, s = toks.shape
                prefix = res.resumed_chunks // rows * 16
                self.prefill.append({"t": t, "end": self._now(),
                                     "rows": rows, "prefix": prefix,
                                     "suffix": s - prefix})
            return res
        return call

    def _wrap_decode(self, fn):
        def call(toks, state):
            t = self._now()
            with self._jax.profiler.TraceAnnotation("chipbench.decode"):
                out = fn(toks, state)
            if self.on:
                self.decode.append({"t": t, "end": self._now(),
                                    "rows": toks.shape[0],
                                    "pos": toks.shape[1],
                                    "steps": out.shape[1]})
            return out
        return call

    def _wrap_search(self, fn):
        """``xam_search_multiset(key_bits, set_ids, planes, valid, ...)``:
        (Q, R) query bits, the set of each, and the (n_sets, ..., ways)
        stored planes and (n_sets, ways) validity."""
        def call(key_bits, set_ids, planes, valid, *a, **kw):
            t = self._now()
            out = fn(key_bits, set_ids, planes, valid, *a, **kw)
            if self.on:
                per_set = lambda x: (int(np.prod(x.shape[1:]))
                                     * np.dtype(x.dtype).itemsize)
                self.lookup.append({
                    "t": t, "queries": int(np.shape(key_bits)[0]),
                    "key_bits": int(np.shape(key_bits)[1]),
                    "ways": int(planes.shape[-1]),
                    "sets": int(np.unique(np.asarray(set_ids)).size),
                    "set_bytes": per_set(planes) + per_set(valid)})
            return out
        return call


class Tracer(threading.Thread):
    """Profiles ``TRACE_S`` seconds from the middle of the window (the
    whole window if it is shorter) inside a ``chipbench.window`` span,
    and has the spans record calls only meanwhile.  One thread starts
    and stops both, so the span opens and closes on one thread."""

    def __init__(self, trace_dir, spans: Spans, t0: float, seconds: float):
        super().__init__(daemon=True)
        self.trace_dir, self.spans = str(trace_dir), spans
        length = min(TRACE_S, seconds)
        self.begin = t0 + (seconds - length) / 2
        self.length = length
        self.start()

    def run(self):
        import jax
        time.sleep(max(self.begin - time.perf_counter(), 0.0))
        jax.profiler.start_trace(self.trace_dir)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            self.spans.t0 = time.perf_counter()
            self.spans.on = True
            time.sleep(self.length)
            self.spans.on = False
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# A run.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    """What the readers of per-layer metrics see."""
    cell: Cell
    window: list                 # Records due inside the window
    seconds: float
    trace: dict | None = None
    spans: Spans | None = None
    peaks: dict | None = None


def _answered(rec: Record) -> bool:
    return rec.status == 200 and rec.doc is not None


def _tokens_ok(rec: Record, mix: dict, vocab: int) -> bool:
    got = np.asarray(rec.doc.get("tokens"))
    return (got.shape == (mix["rows"], mix["decode_tokens"])
            and bool(((got >= 0) & (got < vocab)).all()))


def quantile(values, q: float) -> float:
    """Nearest-rank quantile (every value is a real sample)."""
    v = sorted(values)
    return float(v[max(int(np.ceil(q * len(v))) - 1, 0)])


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, tamper=None, trace_dir=None,
        started: float | None = None) -> dict:
    """One run; returns the result object (without printing it).
    ``started`` is the process's start on the ``perf_counter`` clock."""
    t_start = time.perf_counter() if started is None else started
    import jax
    import jax.monitoring
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.spec["chips"]):
        raise SystemExit(f"needs {cell.spec['chips']} TPU chip(s); JAX "
                         f"found {len(devs)} {devs[0].platform} device(s)")
    cfg, mix = cell.cfg, cell.mix
    plan = traffic.build(mix, cfg["vocab_size"], seed, seconds)

    compiles = {"setup": 0, "window": 0, "drain": 0}
    phase = {"now": "setup"}

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[phase["now"]] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    frontend, admit_q = boot(cfg, mix)
    fill(frontend, admit_q, plan)
    warm(frontend, admit_q, plan)
    if tamper is not None:
        tamper(frontend, admit_q)
    spans = Spans(frontend, admit_q) if trace else None
    errors_before = frontend.router.stats.errors
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s ({compiles['setup']} compiles or cache "
        f"loads); window {seconds} s, {len(plan)} requests planned")

    port = frontend.address[1]
    t0 = time.perf_counter()
    tracer = Tracer(trace_dir, spans, t0, seconds) if trace else None
    phase["now"] = "window"

    def close_window():
        phase["now"] = "drain"
        if tracer is not None:
            tracer.join()

    drive = drive_open if mix["loop"] == "open" else drive_closed
    recs = drive(port, plan, seconds, t0, on_close=close_window)
    log(f"compiles or cache loads inside the window: {compiles['window']}")

    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    frontend.shutdown()
    admit_q.close()
    router_errors = frontend.router.stats.errors - errors_before
    if spans is not None:
        spans.close()

    window = [r for r in recs if r.due < seconds]
    answered = [r for r in window if _answered(r)]
    bad = [r for r in answered if not _tokens_ok(r, mix, cfg["vocab_size"])]
    lost = [r for r in window if not _answered(r)
            and r.status not in (429, 503)]
    failed = len(window) - len(answered) + len(bad)

    # Correctness: the reference, once the program's state is freed.
    del frontend, admit_q
    gc.collect()
    jax.clear_caches()
    stats = gap_stats(check_sample(cell, plan, answered, seed))
    checks = {name: {"value": stats[name], "limit": cell.limits[name]}
              for name in GAP_STATS}
    checks["sampled_tokens"] = {"value": stats["sampled_tokens"],
                                "limit": cell.limits["min_sampled_tokens"]}
    checks["wrong_shape"] = {"value": len(bad), "limit": 0}
    checks["lost"] = {"value": len(lost) + router_errors, "limit": 0}
    correct = (all(stats[n] <= cell.limits[n] for n in GAP_STATS)
               and stats["sampled_tokens"] >= cell.limits["min_sampled_tokens"]
               and not bad and not lost and router_errors == 0)

    data = RunData(cell=cell, window=window, seconds=seconds, spans=spans)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.spec["chips"], "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(window),
              "failed": failed}
    if trace:
        from chipbench import cost
        from chipbench import trace as trace_mod
        data.trace = trace_mod.load_and_reduce(trace_dir)
        data.peaks = cost.peaks(devs[0].device_kind)
        result["metrics"] = per_layer(data)
        device["busy_s"] = data.trace["busy_s"]
        device["window_s"] = data.trace["window_s"]
        result["device"] = device
        result["breakdown"] = breakdown(data)
    else:
        result["metrics"] = end_to_end(data, setup_s)
        result["device"] = device
    result["checks"] = checks
    return result


def sample(cell: Cell, plan: traffic.Plan, answered: list, seed: int):
    """(prompts, served tokens) of the answered requests the reference
    checks: the first ``sample`` of them in an order drawn from the seed
    (a smaller sample is a leading part of a larger one)."""
    rng = np.random.default_rng([seed, 4])
    pick = rng.permutation(len(answered))[:cell.mix["sample"]]
    prompts = np.concatenate([plan.tokens(answered[j].i) for j in pick])
    served = np.concatenate([np.asarray(answered[j].doc["tokens"], np.int32)
                             for j in pick])
    return prompts, served


def reference_logits(cell: Cell, prompts, served, quant=None):
    """Reference logits at every served position: the prompt and the
    served tokens before each one, in blocks of ``reference_rows``, from
    the reference module the configuration names."""
    logits = reference.module(cell.cfg).logits
    seq = np.concatenate([prompts, served[:, :-1]], axis=1)
    rows = cell.limits["reference_rows"]
    return np.concatenate([
        logits(cell.cfg, seq[lo:lo + rows], prompts.shape[1] - 1,
               quant=quant)
        for lo in range(0, len(seq), rows)])


def served_gap(ref: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position: ref (B, T, V), served (B, T)."""
    chosen = np.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    return ref.max(axis=-1) - chosen


#: The numbers compared on the served tokens' gaps below the reference's
#: best logit: the widest gap.  ``gap_stats`` also gives the mean over
#: every sampled token, which grows about with the square of the logit
#: error; it is not compared until readings on the chip set its limit.
GAP_STATS = ("served_gap",)


def gap_stats(gaps: np.ndarray) -> dict:
    """The compared numbers of a (rows, tokens) array of gaps."""
    if gaps.size == 0:
        return {"served_gap": float("inf"), "served_gap_mean": float("inf"),
                "sampled_tokens": 0}
    return {"served_gap": float(gaps.max()),
            "served_gap_mean": float(gaps.mean()),
            "sampled_tokens": int(gaps.size)}


def check_sample(cell: Cell, plan: traffic.Plan, answered: list, seed: int):
    """Gaps between the reference's best logit and each served token's,
    over a sample of answered requests drawn from the seed."""
    if not answered:
        return np.zeros((0, 0), np.float32)
    prompts, served = sample(cell, plan, answered, seed)
    ref = reference_logits(cell, prompts, served)
    return served_gap(ref, served)


# ---------------------------------------------------------------------------
# Metrics.
# ---------------------------------------------------------------------------

def end_to_end(data: RunData, setup_s: float) -> dict:
    """The cell's end-to-end metrics, by name (host clock)."""
    mix = data.cell.mix
    out = {}
    for m in data.cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name in ("req_p95_ms", "req_p50_ms"):
            # A failed request counts as missing every limit: it is
            # charged from its schedule to the end of the drain.
            lat = [(r.done - r.due if _answered(r)
                    else data.seconds + DRAIN_S - r.due) * 1e3
                   for r in data.window]
            v = quantile(lat, 0.95 if name == "req_p95_ms" else 0.5)
        elif name == "tokens_per_s":
            done = [r for r in data.window
                    if _answered(r) and r.done <= data.seconds]
            v = sum(np.asarray(r.doc["tokens"]).size
                    for r in done) / data.seconds
        else:
            raise KeyError(f"no end-to-end measurement named {name!r}")
        out[name] = {"value": float(v), "unit": m["unit"]}
    return out


def _reader(name: str):
    """``metrics/<name>.py``, else ``metrics/<base>.py`` for
    ``<base>.<suffix>``: a reader's ``read(data)`` returns a number or
    None (nothing to read)."""
    import importlib.util
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def per_layer(data: RunData) -> dict:
    out = {}
    for m in data.cell.per_layer:
        v = _reader(m["name"])(data)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(data: RunData) -> dict:
    tr = data.trace
    labels = json.loads((HERE / "programs.json").read_text())
    resumed = any(c["prefix"] > 0 for c in data.spans.prefill)

    def label(name):
        lab = labels.get(name, name)
        if lab == "prefill":
            lab = "prefill.resumed" if resumed else "prefill.full"
        return f"{lab}:{name}"

    progs = sorted(tr["programs"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(tr["idle_by_span"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[label(n), s] for n, s in progs],
            "idle_gaps": [[n, s] for n, s in gaps]}

