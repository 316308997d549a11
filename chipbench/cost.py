"""Operations and bytes the served work needs, computed from shapes.

These are the yardstick for the roofline and utilization shares: a
program's share is what these functions count, at the chip's peak, over
the device time the trace gives it.  They count what the algorithm
needs, never what a particular program happens to do beyond it (masked
key positions, bucket padding), so a share can only understate.
"""
from __future__ import annotations

import json
import pathlib

BF16 = 2
PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak row for a ``jax.Device.device_kind``; unknown kinds are
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    """Weights one decoder layer multiplies by (attention + MLP)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    mlp = (3 if cfg["hidden_act"] == "silu" else 2) * d * f
    return d * q + 2 * d * kv + q * d + mlp


def weight_bytes(cfg: dict) -> int:
    """Bytes of every served weight a decode step must read: all layers
    and the output head (the embedding table is gathered a row a token)."""
    return BF16 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                   + cfg["hidden_size"] * cfg["vocab_size"])


def kv_bytes_per_token(cfg: dict) -> int:
    return (BF16 * 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"])


def _attn_flops(cfg: dict, q_tokens: int, first_pos: int) -> float:
    """Causal attention of ``q_tokens`` queries at positions
    ``first_pos..first_pos+q_tokens-1``: QK^T and PV, keys up to and
    including each query's own position."""
    keys = q_tokens * first_pos + q_tokens * (q_tokens + 1) / 2
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * keys)


def prefill_flops(cfg: dict, rows: int, prefix: int, suffix: int) -> float:
    """Model FLOPs of a prefill that computes ``suffix`` tokens per row
    after a restored ``prefix``: the layers for every computed token,
    attention over prefix and suffix, the output head for the last."""
    lin = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * suffix
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return rows * (lin + _attn_flops(cfg, suffix, prefix) + head)


def decode_flops(cfg: dict, rows: int, pos: int) -> float:
    """One decode step writing position ``pos`` for ``rows`` rows."""
    lin = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return rows * (lin + _attn_flops(cfg, 1, pos) + head)


def decode_bytes(cfg: dict, rows: int, pos: int) -> float:
    """Bytes one decode step must move: the weights once, each row's
    embedding row and cache keys/values up to ``pos``, and the new
    key/value it writes."""
    kv = kv_bytes_per_token(cfg)
    return (weight_bytes(cfg)
            + rows * (BF16 * cfg["hidden_size"] + kv * (pos + 1) + kv))


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
