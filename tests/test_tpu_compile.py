"""Compile rehearsals for a described TPU v5e: the serving path's Pallas
kernels, the set-sharded lookup and admission, and the yi-9b resume
prefill and decode steps, compiled by the installed TPU compiler at real
widths without a chip.

Interpret mode cannot catch tiling, layout or dtype refusals; these
compiles do (each kernel here was once refused).  Nothing runs, so they
say nothing about results or speed — the interpret-mode parity tests in
``test_kernels.py`` guard results.

The topology is described inside a module-scoped fixture (never at
import), because only one process at a time may load the TPU library.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import wear
from repro.kernels.hopscotch.kernel import hopscotch_lookup_pallas
from repro.kernels.string_match.kernel import string_match_pallas
from repro.kernels.xam_search import ops as xam_ops
from repro.kernels.xam_search.kernel import (
    xam_search_multiset_pallas, xam_search_pallas)
from repro.models import transformer
from repro.serve import kv_index
from repro.serve.step import make_decode_step, make_resume_prefill_step

# The launchers' index geometry (launch/httpd.py, launch/serve.py).
N_SETS, SET_WAYS, KEY_BITS = 8, 512, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU compile is written to a persistent cache but cannot be read
    # back without a chip; keep the cache off while these tests run.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def set_mesh(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("sets",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Lower + compile for the described chip; returns the compiled
    program's text (what the TPU compiler accepted)."""
    return jax.jit(fn).lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# Pallas kernels at real widths.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed", [False, True], ids=["int8", "packed8"])
@pytest.mark.parametrize("q,r,c", [(128, KEY_BITS, SET_WAYS),
                                   (128, 64, 1 << 16)])
def test_xam_search_compiles(one_chip, q, r, c, packed):
    data = (_sds((r // 8, c), jnp.uint8, one_chip) if packed
            else _sds((r, c), jnp.int8, one_chip))
    fn = functools.partial(xam_search_pallas, interpret=False)
    txt = _compile(fn, _sds((q, r), jnp.int8, one_chip), data,
                   _sds((q, r), jnp.int8, one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("scoring", ["int8", "f32"])
@pytest.mark.parametrize("packed", [False, True], ids=["int8", "packed8"])
@pytest.mark.parametrize("block_q", [16, 64])      # narrow / wide bucket
def test_xam_multiset_compiles(one_chip, block_q, packed, scoring):
    q = 8 * block_q
    planes = (_sds((N_SETS, KEY_BITS // 8, SET_WAYS), jnp.uint8, one_chip)
              if packed else
              _sds((N_SETS, KEY_BITS, SET_WAYS), jnp.int8, one_chip))
    fn = functools.partial(xam_search_multiset_pallas, block_q=block_q,
                           scoring=scoring, interpret=False)
    txt = _compile(fn, _sds((q, KEY_BITS), jnp.int8, one_chip),
                   _sds((q, KEY_BITS), jnp.int8, one_chip), planes,
                   _sds((N_SETS, SET_WAYS), jnp.int8, one_chip),
                   _sds((q // block_q,), jnp.int32, one_chip),
                   _sds((q // block_q,), jnp.int32, one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("window", [32, 128])
def test_hopscotch_compiles(one_chip, window):
    n_slots = (1 << 20) + 2 * window          # 1M slots + the pad windows
    fn = functools.partial(hopscotch_lookup_pallas, window=window,
                           interpret=False)
    txt = _compile(fn, _sds((n_slots,), jnp.uint32, one_chip),
                   _sds((n_slots,), jnp.uint32, one_chip),
                   _sds((4096,), jnp.int32, one_chip),
                   _sds((4096,), jnp.uint32, one_chip),
                   _sds((4096,), jnp.uint32, one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n,p,tile", [(32 << 20, 8, 4096), (5000, 12, 128)])
def test_string_match_compiles(one_chip, n, p, tile):
    fn = functools.partial(string_match_pallas, pattern_len=p, tile=tile,
                           interpret=False)
    txt = _compile(fn, _sds((n,), jnp.uint8, one_chip),
                   _sds((p,), jnp.uint8, one_chip))
    assert "tpu_custom_call" in txt


# ---------------------------------------------------------------------------
# The set-sharded index over a 4-chip ("sets",) mesh.
# ---------------------------------------------------------------------------

def test_stacked_lookup_compiles_on_four_chips(set_mesh):
    shd = NamedSharding(set_mesh, P("sets"))
    block_q, n_qb = 16, 4
    fn = xam_ops._stacked_shardmap_fn(set_mesh, block_q, "int8", False)
    compiled = fn.lower(
        _sds((4, n_qb * block_q, KEY_BITS), jnp.int8, shd),
        _sds((4, n_qb * block_q, KEY_BITS), jnp.int8, shd),
        _sds((4, n_qb), jnp.int32, shd), _sds((4,), jnp.int32, shd),
        _sds((N_SETS, KEY_BITS, SET_WAYS), jnp.int8, shd),
        _sds((N_SETS, SET_WAYS), jnp.int8, shd)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_admission_scan_compiles_on_four_chips(set_mesh):
    shd = NamedSharding(set_mesh, P("sets"))
    repl = NamedSharding(set_mesh, P())
    n_rounds, width = 2, 8
    grid = (4, n_rounds, width)
    wcfg = wear.WearConfig(n_supersets=N_SETS, blocks_per_superset=SET_WAYS)
    wdyn = jax.tree.map(lambda a: _sds(a.shape, a.dtype, repl),
                        jax.eval_shape(lambda: wear.dyn_of(wcfg)))
    per_set = lambda dt: _sds((N_SETS,), dt, shd)
    fn = kv_index._admit_shardmap_fn(set_mesh)
    fn.lower(
        _sds((N_SETS, KEY_BITS, SET_WAYS), jnp.int8, shd),
        _sds((N_SETS, SET_WAYS), jnp.int8, shd),
        _sds((N_SETS, SET_WAYS), jnp.uint32, shd),
        _sds((N_SETS, SET_WAYS), jnp.int32, shd),
        per_set(jnp.int32), per_set(jnp.int32),
        per_set(jnp.int8), per_set(jnp.int8),
        per_set(jnp.int32), per_set(jnp.int32), per_set(jnp.int32),
        _sds((4,), jnp.int32, shd), _sds((4,), jnp.int32, shd),
        _sds((4,), jnp.int32, shd),
        wdyn, _sds((), jnp.int32, repl),
        _sds(grid, jnp.int32, shd), _sds(grid, jnp.uint32, shd),
        _sds(grid + (KEY_BITS,), jnp.int8, shd),
        _sds(grid, jnp.int32, shd), _sds(grid, jnp.int32, shd),
        _sds(grid, jnp.bool_, shd)).compile()


# ---------------------------------------------------------------------------
# yi-9b at published widths (two layers: the scanned body is the same
# program at any depth).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def yi9b(one_chip):
    cfg = dataclasses.replace(configs.get_arch("yi-9b"), n_layers=2)
    init = functools.partial(transformer.init_params, cfg=cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), shapes)
    return cfg, params


def test_yi9b_resume_prefill_and_decode_compile(yi9b, one_chip):
    cfg, params = yi9b
    b, prefix, suffix, max_seq = 2, 48, 48, 104
    step = make_resume_prefill_step(cfg, max_seq)
    full = jax.eval_shape(step, params, {"tokens": jax.ShapeDtypeStruct(
        (b, prefix + suffix), jnp.int32)})
    prefix_kv = jax.tree.map(
        lambda a: _sds(a.shape[:-3] + (prefix,) + a.shape[-2:], a.dtype,
                       one_chip), full[2])
    prefill = jax.jit(step).lower(
        params, {"tokens": _sds((b, suffix), jnp.int32, one_chip)},
        prefix_kv).compile()
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    assert prefill.memory_analysis().argument_size_in_bytes >= weight_bytes
    cache = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: transformer.init_cache(cfg, b, max_seq)))
    jax.jit(make_decode_step(cfg)).lower(
        params, cache, _sds((b, 1), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()


def test_yi9b_slab_split_and_join_compile(yi9b, one_chip):
    """The resume engine's device slab programs: the split of a batch's
    new KV into chunk slabs, one row's join along the sequence axis and
    the join of the rows.  A slab's (..., 16, 4, 128) bf16 leaves take
    no more device bytes than their elements: the tiled layout pads
    nothing."""
    from repro.serve.resume import join_rows, join_run, split_slabs
    cfg, params = yi9b
    rows, chunks = 2, 4
    kv = jax.eval_shape(
        make_resume_prefill_step(cfg, chunks * kv_index.CHUNK_TOKENS),
        params, {"tokens": jax.ShapeDtypeStruct(
            (rows, chunks * kv_index.CHUNK_TOKENS), jnp.int32)})[2]
    kv = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), kv)
    split = jax.jit(split_slabs).lower(kv).compile()
    slabs = jax.eval_shape(split_slabs, kv)
    assert len(slabs) == rows and len(slabs[0]) == chunks
    slab_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(slabs[0][0]))
    assert split.memory_analysis().output_size_in_bytes >= \
        rows * chunks * slab_bytes
    run = [jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), s)
           for s in slabs[0]]
    joined = jax.jit(join_run).lower(run).compile()
    assert joined.memory_analysis().argument_size_in_bytes == \
        chunks * slab_bytes
    row = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                       jax.eval_shape(join_run, run))
    _compile(join_rows, [row] * rows)
