"""The benchmark's arithmetic for a Laguna configuration as one chip's share
of an expert-parallel deployment (``model_type`` ``laguna``): parameters, the
bytes a decode token-step and its window layers' attention must move, and the
operations a token and a prompt chunk need.  Kept with the benchmark, whatever
the program implements them with.  ``cfg`` is a configuration file's dict
under the published key names (``layer_types`` and
``num_attention_heads_per_layer`` as long as ``num_hidden_layers``;
``num_experts`` the experts HELD, ``router_outputs`` the router's width).  A
multiply-add counts as 2 operations.

Per layer (two norm vectors each):

- attention of ``H`` query heads (the layer's entry in
  ``num_attention_heads_per_layer``) over ``num_key_value_heads`` of
  ``head_dim``: ``W_q`` ``d x H hd``, ``W_k`` and ``W_v`` ``d x kv hd``, the
  head gate ``W_g`` ``d x H``, ``W_o`` ``H hd x d``;
- a dense feed-forward ``3 d x intermediate_size`` in ``mlp_only_layers``,
  else an expert layer as held: the router ``d x router_outputs``, the shared
  expert ``3 d x shared_expert_intermediate_size`` and the held experts ``3 d
  x moe_intermediate_size`` each.

A cached position is a layer's ``kv hd`` keys and as many values, bf16.  A
full layer (``full_attention``) keeps and reads every position of a sequence;
a window layer (``sliding_attention``) the last ``sliding_window``.
"""

from __future__ import annotations

CACHE_BYTES = 2  # bf16


def layers(cfg: dict, kind: str) -> list:
    """The numbers of the layers of ``kind`` (``full`` or ``window``)."""
    name = {"full": "full_attention", "window": "sliding_attention"}[kind]
    return [i for i, t in enumerate(cfg["layer_types"]) if t == name]


def heads(cfg: dict, kind: str) -> int:
    """Query heads of a layer of ``kind`` (one count a kind)."""
    found = {cfg["num_attention_heads_per_layer"][i]
             for i in layers(cfg, kind)}
    if len(found) != 1:
        raise ValueError(f"{kind} layers with head counts {sorted(found)}")
    return found.pop()


def attention_params(cfg: dict, kind: str) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = heads(cfg, kind), cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + d * h + h * hd * d


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["router_outputs"]


def moe_ffn_params(cfg: dict) -> int:
    """An expert layer's feed-forward as held here."""
    return (router_params(cfg) + shared_expert_params(cfg)
            + cfg["num_experts"] * expert_params(cfg))


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def n_dense(cfg: dict) -> int:
    return len(cfg["mlp_only_layers"])


def n_moe(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - n_dense(cfg)


def matrix_params(cfg: dict) -> int:
    """Every stored matrix of this chip's share (untied: table and head)."""
    return (sum(len(layers(cfg, k)) * attention_params(cfg, k)
                for k in ("window", "full"))
            + n_dense(cfg) * dense_ffn_params(cfg)
            + n_moe(cfg) * moe_ffn_params(cfg)
            + 2 * embedding_params(cfg))


def total_params(cfg: dict) -> int:
    """The matrices and the norm vectors: two a layer and the last."""
    return matrix_params(cfg) + (2 * cfg["num_hidden_layers"] + 1) * cfg[
        "hidden_size"]


def routed_expert_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """The routed experts this chip holds, every expert layer."""
    return (n_moe(cfg) * cfg["num_experts"] * expert_params(cfg)
            * bytes_per_param)


def position_bytes(cfg: dict) -> int:
    """Bytes a cached position costs ONE layer's decode attention to read:
    its keys and its values (4 KiB at 8 KV heads of 128 in bf16)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * CACHE_BYTES


def ring_bytes(cfg: dict) -> int:
    """What one sequence holds that does not page: the last
    ``sliding_window`` positions of every window layer."""
    return (len(layers(cfg, "window")) * cfg["sliding_window"]
            * position_bytes(cfg))


def window_attention_bytes(cfg: dict, window_positions: float) -> float:
    """Bytes the window layers' decode attention must move in ONE token-step
    whose decoding rows read ``window_positions`` ring positions in ONE
    window layer (the counter ``decode_window_positions`` a token-step)."""
    return (float(window_positions) * len(layers(cfg, "window"))
            * position_bytes(cfg))


def full_attention_bytes(cfg: dict, full_positions: float) -> float:
    """The same for the full layers: ``full_positions`` pool positions read in
    ONE full layer (the counter ``decode_full_positions`` a token-step)."""
    return (float(full_positions) * len(layers(cfg, "full"))
            * position_bytes(cfg))


def decode_step_bytes(cfg: dict, window_positions: float,
                      full_positions: float,
                      experts_hit: float = 1.0) -> float:
    """Bytes ONE decode token-step must move: every weight a token-step needs
    once (all of them less the embedding table, of the held routed experts
    only the share ``experts_hit`` that some decoding row chose), and the keys
    and values of the positions its rows read: ``full_positions`` in every
    full layer, ``window_positions`` in every window layer (the counters
    ``decode_full_positions`` and ``decode_window_positions`` a token-step).
    The WORK, whatever reads it: an expert no row chose and a position
    outside a window layer's window are none, so no later skip can read over
    100."""
    return ((total_params(cfg) - embedding_params(cfg)) * 2
            - (1.0 - experts_hit) * routed_expert_bytes(cfg)
            + window_attention_bytes(cfg, window_positions)
            + full_attention_bytes(cfg, full_positions))


def token_matmul_params(cfg: dict) -> float:
    """Weights a token must be multiplied by on this chip, the head apart:
    every layer's attention matrices, the dense feed-forward, and in an
    expert layer the router, the shared expert and the held experts the token
    CHOSE (``num_experts_per_tok`` of ``router_outputs`` fall on
    ``num_experts`` held ones: 0.625 of an expert a token here).  The
    products with held experts a token did not choose are the program's cost,
    not work."""
    chosen_held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                   / cfg["router_outputs"])
    return (sum(len(layers(cfg, k)) * attention_params(cfg, k)
                for k in ("window", "full"))
            + n_dense(cfg) * dense_ffn_params(cfg)
            + n_moe(cfg) * (router_params(cfg) + shared_expert_params(cfg)
                            + chosen_held * expert_params(cfg)))


def token_flops(cfg: dict) -> float:
    """Operations a token needs whatever its position: its multiplies.
    Attention against the cached positions and the head are apart."""
    return 2.0 * token_matmul_params(cfg)


def head_flops(cfg: dict) -> float:
    return 2.0 * embedding_params(cfg)


def pair_flops(cfg: dict, kind: str) -> float:
    """Operations a (query, key) pair costs ONE layer of ``kind``: every
    query head a score and a weighted sum over ``head_dim`` values."""
    return 2.0 * heads(cfg, kind) * 2 * cfg["head_dim"]


def window_keys(p0: int, tokens: int, window: int) -> float:
    """(query, key) pairs of ``tokens`` queries from position ``p0`` in a
    window layer: a query at position ``p`` sees ``min(p + 1, window)``
    keys."""
    return float(sum(min(p + 1, window) for p in range(p0, p0 + tokens)))


def chunk_flops(cfg: dict, p0: int, tokens: int, is_last: bool) -> float:
    """Operations a prompt chunk of ``tokens`` real tokens from position
    ``p0`` needs: ``token_flops`` a token, the full layers' attention against
    the prompt so far (a token at position p sees p + 1 keys), the window
    layers' against the last ``sliding_window`` positions alone (WINDOWED
    work: ``min(p + 1, window)`` keys) and, where the chunk is the prompt's
    last, the head ONCE.  A chunk's padding, masked pairs, the products with
    held experts a token did not choose and the head's other rows are the
    program's cost, not work."""
    s, p = float(tokens), float(p0)
    full_keys = s * p + s * (s + 1) / 2.0
    return (token_flops(cfg) * s
            + len(layers(cfg, "full")) * pair_flops(cfg, "full") * full_keys
            + len(layers(cfg, "window")) * pair_flops(cfg, "window")
            * window_keys(p0, tokens, cfg["sliding_window"])
            + (head_flops(cfg) if is_last else 0.0))


def served_flops(cfg: dict, prompt_tokens: float,
                 emitted_tokens: float) -> float:
    """Operations of a span of serving from its two counters: every prompt
    and every emitted token through the layers, the head an emitted token.
    Attention's pairs are left out (they need positions no counter books
    for a prompt), so the share of the peak reads the lower for it."""
    return (token_flops(cfg) * (prompt_tokens + emitted_tokens)
            + head_flops(cfg) * emitted_tokens)
