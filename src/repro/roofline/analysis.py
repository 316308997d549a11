"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in SECONDS (per step, per device):

    compute    = HLO_FLOPs   / peak_FLOP/s          (197 TFLOP/s bf16, v5e)
    memory     = HLO_bytes   / HBM_bw               (819 GB/s)
    collective = coll_bytes  / ICI_bw               (~50 GB/s/link)

``cost_analysis()`` supplies FLOPs / bytes of the partitioned (per-device)
module.  Collective bytes are NOT in cost_analysis — we parse the compiled
HLO text and sum the output-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import dataclasses
import os
import re

# TPU v5e per-chip peaks.  Source: Google Cloud documentation, "TPU
# v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, and
# 1,600 Gbit/s of chip-to-chip interconnect over four links (50 GB/s
# each).
PEAK_FLOPS = 197e12          # bf16
PEAK_INT8_OPS = 393e12       # int8 x int8 -> int32 (XAM scoring)
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

#: Env knob selecting the machine profile by name (``Machine.name``).
MACHINE_ENV = "REPRO_MACHINE"


@dataclasses.dataclass(frozen=True)
class Machine:
    """Per-chip peak rates a roofline is drawn against.

    ``cpu-interpret`` is a deliberately coarse host profile (one modern
    server core's DRAM stream + vector peak, order-of-magnitude only) so
    interpret-mode bench runs report an achieved-bandwidth *fraction*
    against a ceiling that is at least the right power of ten — it is
    chosen only on the ``cpu`` platform and never compared against a TPU
    roofline.
    """

    name: str
    peak_flops: float        # FLOP/s (bf16)
    peak_int8_ops: float     # OP/s (int8)
    hbm_bw: float            # bytes/s (main-memory stream bandwidth)
    ici_bw: float            # bytes/s/link (interconnect; 0 = none)


#: Accelerator profiles keyed by ``jax.Device.device_kind``.  A TPU kind
#: missing here is an error, never a default.
MACHINES: dict[str, Machine] = {
    "TPU v5 lite": Machine("v5e", PEAK_FLOPS, PEAK_INT8_OPS, HBM_BW, ICI_BW),
}

#: The interpret-mode host profile (``platform == "cpu"`` only).
CPU_INTERPRET = Machine("cpu-interpret", 5e10, 5e10, 2e10, 1e10)

_BY_NAME = {m.name: m for m in (*MACHINES.values(), CPU_INTERPRET)}


def machine_for(device) -> Machine:
    """Peak profile of one ``jax.Device``: ``cpu-interpret`` on the CPU
    platform, else the table entry for its ``device_kind``."""
    if device.platform == "cpu":
        return CPU_INTERPRET
    try:
        return MACHINES[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); known kinds: "
            f"{sorted(MACHINES)}") from None


def current_machine() -> Machine:
    """Active machine profile: ``REPRO_MACHINE`` if set (ValueError on an
    unknown name), else :func:`machine_for` the first device."""
    name = os.environ.get(MACHINE_ENV)
    if name is not None:
        if name not in _BY_NAME:
            raise ValueError(
                f"{MACHINE_ENV}={name!r} is not a known machine profile; "
                f"valid values: {sorted(_BY_NAME)}")
        return _BY_NAME[name]
    import jax
    return machine_for(jax.devices()[0])

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %ag = bf16[16,4096,128]{2,1,0} all-gather(%x), ...
_SHAPE_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\]"
    r"(?:\{[^}]*\})?[\s\S]{0,80}?\b(" + "|".join(_COLLECTIVES) + r")")
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*(" + "|".join(_COLLECTIVES) + r")")
_ELEM_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_COLL_LINE = re.compile(
    r"=\s*(.*?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\(")


def _line_collective(stripped: str):
    """Parse '%x = <shape> all-reduce(...)' lines; returns (kind, bytes).
    The output shape sits between '=' and the op name (instruction names
    also contain the op string, so naive substring matching is wrong)."""
    m = _COLL_LINE.search(stripped)
    if not m:
        return None
    total = 0
    for e in _ELEM_RE.finditer(m.group(1)):
        total += _shape_bytes(e.group(1), e.group(2))
    if total == 0:
        return None
    return m.group(2), total


# NOTE: while-loop bodies have tuple-typed parameters -> NESTED parens in
# the header; the param list must be matched greedily, not with [^)]*.
_COMP_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_CALLED = re.compile(r"(?:calls=|to_apply=|condition=|body=)%?([\w.\-]+)")
_CONST_INT = re.compile(r"constant\((\d+)\)")


def parse_hlo_computations(hlo_text: str):
    """Split optimized HLO text into computations.  Returns
    (comps: name -> list[str] lines, entry_name)."""
    comps, entry = {}, None
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and ("->" in line) and line.rstrip().endswith("{"):
            m = _COMP_HEAD.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.strip().startswith("ENTRY"):
                    entry = cur
                continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line.strip())
    return comps, entry


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective output bytes with WHILE-LOOP TRIP COUNTS applied.

    The optimized module is walked as a call graph from ENTRY; a while op
    multiplies its body's (and transitively called computations')
    contribution by the trip count recovered from the loop condition's
    integer constant.  Collectives outside loops (e.g. the once-per-step
    gradient reduction) count once; FSDP all-gathers inside the scanned
    layer-group body count n_groups times — matching real execution.
    """
    comps, entry = parse_hlo_computations(hlo_text)
    if entry is None:
        # fall back: flat scan, no loop scaling
        comps = {"main": [l.strip() for l in hlo_text.splitlines()]}
        entry = "main"

    def cond_trips(cond_name: str) -> int:
        ints = []
        for line in comps.get(cond_name, []):
            for m in _CONST_INT.finditer(line):
                ints.append(int(m.group(1)))
        return max(ints) if ints else 1

    from functools import lru_cache

    @lru_cache(maxsize=None)
    def walk(name: str) -> tuple:
        """Returns tuple of (kind, bytes) totals dict for one execution."""
        totals = {k: 0 for k in _COLLECTIVES}
        for line in comps.get(name, ()):
            hit = _line_collective(line)
            if hit:
                totals[hit[0]] += hit[1]
            if " while(" in line:
                mb = re.search(r"body=%?([\w.\-]+)", line)
                mc = re.search(r"condition=%?([\w.\-]+)", line)
                if mb:
                    trips = cond_trips(mc.group(1)) if mc else 1
                    sub = dict(walk(mb.group(1)))
                    for k in _COLLECTIVES:
                        totals[k] += sub[k] * trips
            else:
                for m in _CALLED.finditer(line):
                    callee = m.group(1)
                    if callee in comps and callee != name:
                        sub = dict(walk(callee))
                        for k in _COLLECTIVES:
                            totals[k] += sub[k]
        return tuple(totals.items())

    out = dict(walk(entry))
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float            # per-device, loop-corrected
    flops_raw_hlo: float    # per-device, as reported (loop bodies once)
    hbm_bytes: float        # per-device, loop-corrected
    coll_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float
    loop_factor: float      # corrected / raw

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(cost: dict, coll: dict, *, model_flops_per_device: float,
            jaxpr_flops_per_device: float | None = None,
            machine: Machine | None = None) -> Roofline:
    """Derive the three terms.  ``cost_analysis`` counts while/scan bodies
    ONCE (verified; see jaxpr_cost.py), so when a jaxpr-derived count is
    supplied we use it for the compute term and scale the compiled byte
    count by the same body-repeat factor (the scanned layer groups dominate
    both flops and HBM traffic).  ``machine`` defaults to the v5e profile
    (the dry-run artifacts target that part); pass ``current_machine()``
    to roofline against the active backend instead."""
    if machine is None:
        machine = MACHINES["TPU v5 lite"]
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    if jaxpr_flops_per_device and raw_flops > 0:
        factor = max(jaxpr_flops_per_device / raw_flops, 1.0)
    else:
        factor = 1.0
    flops = raw_flops * factor if factor > 1.0 else raw_flops
    if jaxpr_flops_per_device:
        flops = jaxpr_flops_per_device
    hbm = raw_bytes * factor
    cb = float(coll.get("total", 0))
    terms = {
        "compute": flops / machine.peak_flops,
        "memory": hbm / machine.hbm_bw,
        "collective": cb / machine.ici_bw if machine.ici_bw else 0.0,
    }
    bottleneck = max(terms, key=terms.get)
    return Roofline(
        flops=flops, flops_raw_hlo=raw_flops, hbm_bytes=hbm, coll_bytes=cb,
        compute_s=terms["compute"], memory_s=terms["memory"],
        collective_s=terms["collective"], bottleneck=bottleneck,
        model_flops=model_flops_per_device,
        useful_ratio=(model_flops_per_device / flops) if flops else 0.0,
        loop_factor=factor,
    )


def model_flops(cfg, shape, n_devices: int) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) per step, where D =
    tokens processed; decode steps process global_batch tokens."""
    n_params = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        factor = 6.0
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        factor = 2.0
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        factor = 2.0
    return factor * n_params * tokens / n_devices


def active_param_count(cfg) -> float:
    """Parameter count excluding inactive experts (MoE uses top_k of E)."""
    import jax
    from repro.launch import specs as lspecs
    shapes = lspecs.params_shapes(cfg)

    def leaf_count(path, s):
        keys = [getattr(p, "key", "") for p in path]
        n = 1
        for d in s.shape:
            n *= d
        name = keys[-1]
        if (name in ("w_up", "w_gate", "w_down") and len(s.shape) >= 3
                and cfg.n_experts):
            # expert-stacked: count only the top-k active fraction
            n = n * cfg.top_k / cfg.n_experts
        if name == "embed":
            # embedding gathers are not 6ND matmul work; count once (unembed
            # matmul is counted via `unembed`/tied read below).
            n = 0 if not cfg.tie_embeddings else n
        return n

    import jax.tree_util as jtu
    leaves = jtu.tree_leaves_with_path(shapes)
    return float(sum(leaf_count(p, s) for p, s in leaves))
