"""The benchmark's own arithmetic: parameters, operations and bytes from a
configuration's shapes, and the table of peaks.

A configuration file carries the published keys (``hidden_size``,
``num_hidden_layers`` as run, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size``, ``vocab_size``,
``tie_word_embeddings``).  A multiply-add counts as 2 operations.

What counts as work: the matrix multiplies of the layers and of the output
head, and attention's two (scores and values).  The embedding table is a
lookup, not a multiply, and is left out (the program's own
``llama.flops_per_token`` counts it: ``6 * num_params``).  Recomputation under
``remat`` is not counted: utilization is against the operations the forward
and backward passes require.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """``{"flops_per_s", "hbm_bytes_per_s"}`` of one chip of ``device_kind``.
    A kind the table does not hold is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (it has {sorted(table)})")
    return table[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix multiplies: q, k, v, o and the gated
    feed-forward's three.  The two norm vectors are not multiplies."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * hq + 2 * d * hkv + hq * d + 3 * d * f


def matmul_params(cfg: dict) -> int:
    """Weights every token is multiplied by: the layers and the output head
    (tied or not, the head is a multiply); the embedding lookup excluded."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    """Every stored weight: embedding, layers with their norms, final norm,
    and the head where it is not tied."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return (v * d + cfg["num_hidden_layers"] * (layer_matmul_params(cfg) + 2 * d)
            + d + head)


def attention_flops_per_token(cfg: dict, context: float) -> float:
    """Forward operations of attention's two multiplies for one query token
    that attends to ``context`` keys: scores and values, each
    ``2 * heads * head_dim`` per key, in every layer."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def prefill_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward operations per prompt token of a causal pass over ``seq_len``
    tokens: a token at position p attends to p + 1 keys, (seq_len + 1) / 2
    on average."""
    return (2.0 * matmul_params(cfg)
            + attention_flops_per_token(cfg, (seq_len + 1) / 2.0))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward: three times the forward pass."""
    return 3.0 * prefill_flops_per_token(cfg, seq_len)


def flash_attention_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Attention operations of one training step, forward and backward
    (backward: twice the forward's), causal, over every layer.  The
    backward kernel's recomputation of the scores is not counted."""
    per_token = attention_flops_per_token(cfg, (seq_len + 1) / 2.0)
    return 3.0 * per_token * batch * seq_len


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights one decode step reads: every multiply's weights once
    (the embedding is read a row a sequence, not counted)."""
    return matmul_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """Keys and values one token adds to the cache, all layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * bytes_per_value)


def decode_step_bytes(cfg: dict, live_context_tokens: int,
                      bytes_per_param: int = 2) -> int:
    """Bytes one decode token-step must read: the weights once and the live
    context's keys and values."""
    return (weight_bytes(cfg, bytes_per_param)
            + live_context_tokens * kv_bytes_per_token(cfg))
