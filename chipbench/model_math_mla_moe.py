"""The benchmark's arithmetic for a latent-attention (MLA) mixture-of-experts
configuration held as one chip's share: parameters, and the operations and
bytes of a decode token-step and of the latent decode kernel.

``cfg`` is a configuration file's dict: the published keys AS RUN
(``num_hidden_layers``, ``first_k_dense_replace``, ``n_routed_experts`` = the
experts HELD, ``vocab_size`` = the rows held) plus ``router_outputs`` (the
router's published width).  A multiply-add counts as 2 operations.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """One layer's attention matrices: W_dq, W_uq, W_dkv, W_ukv, W_o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def norm_params(cfg: dict) -> int:
    """One layer's norm vectors: four sandwich norms, the query latent's and
    the key-value latent's."""
    return 4 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def expert_params(cfg: dict) -> int:
    """One expert: the gated feed-forward's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg: dict) -> int:
    return (attention_params(cfg) + norm_params(cfg)
            + 3 * cfg["hidden_size"] * cfg["intermediate_size"])


def expert_layer_params(cfg: dict) -> int:
    """Attention, the router at its published width, the shared experts and
    the routed experts HELD."""
    return (attention_params(cfg) + norm_params(cfg)
            + cfg["hidden_size"] * cfg["router_outputs"]
            + (cfg["n_shared_experts"] + cfg["n_routed_experts"])
            * expert_params(cfg))


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    """Every weight this chip stores."""
    dense = cfg["first_k_dense_replace"]
    head = 0 if cfg.get("tie_word_embeddings") else embedding_params(cfg)
    return (dense * dense_layer_params(cfg)
            + (cfg["num_hidden_layers"] - dense) * expert_layer_params(cfg)
            + embedding_params(cfg) + head + cfg["hidden_size"])


def decode_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Weights one decode token-step reads where every held expert is
    wanted: all of them less the embedding table (read a row a sequence)."""
    return (params_held(cfg) - embedding_params(cfg)) * bytes_per_param


def latent_values(cfg: dict) -> int:
    """Values a position a layer keeps in the cache: [c_kv | k_rope]."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes a live position a layer costs a token-step's attention to read
    (1,152 in bf16; the stored row is padded to 1,280)."""
    return latent_values(cfg) * bytes_per_value


def routed_expert_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """The routed experts this chip holds, every expert layer."""
    return ((cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
            * cfg["n_routed_experts"] * expert_params(cfg) * bytes_per_param)


def decode_step_bytes(cfg: dict, live_positions: float,
                      experts_hit: float = 1.0) -> float:
    """Bytes one decode token-step must read: the weights once, of the held
    routed experts only the share ``experts_hit`` that some decoding row
    chose, and the latent of every live position in every layer.  An expert
    no row chose is no work, whatever reads it: the share then reads the same
    whether the program reads every held expert (it does) or skips the
    unwanted, and a decode that skips them cannot read over 100."""
    return (decode_weight_bytes(cfg)
            - (1.0 - experts_hit) * routed_expert_bytes(cfg)
            + live_positions * cfg["num_hidden_layers"] * latent_bytes(cfg))


def mla_kernel_flops(cfg: dict, live_positions: float) -> float:
    """Operations of the latent decode kernel for one token-step, all layers:
    a live position a layer costs every head a score over the latent row and
    a weighted sum of its value part."""
    per = 2.0 * cfg["num_attention_heads"] * (latent_values(cfg)
                                              + cfg["kv_lora_rank"])
    return per * live_positions * cfg["num_hidden_layers"]


def mla_kernel_bytes(cfg: dict, live_positions: float) -> float:
    """Bytes the latent decode kernel must read for one token-step."""
    return float(live_positions * cfg["num_hidden_layers"]
                 * latent_bytes(cfg))


def prefill_matmul_params(cfg: dict) -> float:
    """Weights a prompt token must be multiplied by on this chip: every
    layer's attention matrices, the dense layers' feed-forward, and in an
    expert layer the router, the shared experts and the held experts the
    token CHOSE: ``num_experts_per_tok`` of ``router_outputs`` fall on
    ``n_routed_experts`` held ones, half an expert a token here.  (The
    program multiplies every token by every held expert; that is its cost,
    not work.)  The head is counted a prompt, not a token."""
    dense = cfg["first_k_dense_replace"]
    chosen_held = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                   / cfg["router_outputs"])
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * 3 * cfg["hidden_size"] * cfg["intermediate_size"]
            + (cfg["num_hidden_layers"] - dense)
            * (cfg["hidden_size"] * cfg["router_outputs"]
               + (cfg["n_shared_experts"] + chosen_held) * expert_params(cfg)))


def prefill_flops(cfg: dict, new_tokens: int, prefix: int,
                  chunk: int) -> float:
    """Operations to prefill ``new_tokens`` tokens behind ``prefix`` cached
    positions in chunks of ``chunk``: the matrix multiplies of each token,
    the head's one row, and causal attention in the form that needs fewer
    operations here, the expanded one: a (query, key) pair costs every head
    a score over ``nope + rope`` values and a weighted sum over the value
    width, and every chunk expands each position it visits once from the
    latent to per-head keys and values."""
    h = cfg["num_attention_heads"]
    pair = 2.0 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                      + cfg["v_head_dim"])
    expand = 2.0 * cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                             + cfg["v_head_dim"])
    pairs = new_tokens * prefix + new_tokens * (new_tokens + 1) / 2.0
    visited = sum(prefix + min(new_tokens, p0 + chunk)
                  for p0 in range(0, new_tokens, chunk))
    return (2.0 * prefill_matmul_params(cfg) * new_tokens
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
            + cfg["num_hidden_layers"] * (pair * pairs + expand * visited))
