"""The benchmark's arithmetic for a Kimi-Linear configuration as one chip's
share of an expert-parallel deployment (``model_type`` ``kimi_linear``):
parameters, the bytes a decode token-step and its KDA kernel must move, and
the operations a token and a prompt chunk need.  Kept with the benchmark,
whatever the program implements them with.  ``cfg`` is a configuration
file's dict under the published key names (``linear_attn_config`` nested as
published; ``num_experts`` the experts HELD, ``router_outputs`` the router's
width).  A multiply-add counts as 2 operations.

Per layer (27: 20 KDA and 7 MLA mixers; a dense feed-forward after the first
``first_k_dense_replace``, an expert layer after the others; two norm vectors
each):

- a KDA mixer, ``I = heads x head_dim``: ``W_q``, ``W_k``, ``W_v`` ``d x I``
  each, ``W_o`` ``I x d``, the decay's and the output gate's low-rank pairs
  ``d x r`` and ``r x I`` (``r = head_dim``: ``assumed``), ``W_b`` ``d x
  heads``; three depthwise convolutions ``taps x I``, ``A_log`` a head,
  ``dt_bias`` a channel, the head norm's ``head_dim``;
- an MLA mixer without a query down-projection: ``W_q`` ``d x heads x (nope +
  pe)``, ``W_dkv`` ``d x (rank + pe)``, ``W_ukv`` ``rank x heads x (nope +
  v)``, ``W_o`` ``heads x v x d``, the latent norm's ``rank``;
- an expert layer as held: the router ``d x router_outputs``, the shared and
  the held experts ``3 d f`` each.

A sequence's KDA state is ``heads x head_dim x head_dim`` values a KDA layer,
float32 at rest, and its convolution windows ``(taps - 1) x 3 I`` bf16.  A
cached position is ``[c | k_pe]`` of the MLA layers only.
"""

from __future__ import annotations

STATE_BYTES = 4   # float32 at rest
WINDOW_BYTES = 2  # bf16
# operations a token costs a value of a KDA head's state, as the recurrence
# is defined: the decay (1), S^T k (2), the rank-one update (2), S^T q (2)
STATE_OPS = 7


def _la(cfg: dict) -> dict:
    return cfg["linear_attn_config"]


def n_kda(cfg: dict) -> int:
    return len(_la(cfg)["kda_layers"])


def n_mla(cfg: dict) -> int:
    return len(_la(cfg)["full_attn_layers"])


def kda_inner(cfg: dict) -> int:
    return _la(cfg)["num_heads"] * _la(cfg)["head_dim"]


def kda_matmul_params(cfg: dict) -> int:
    d, i, r = cfg["hidden_size"], kda_inner(cfg), _la(cfg)["head_dim"]
    return 4 * d * i + 2 * (d * r + r * i) + d * _la(cfg)["num_heads"]


def kda_mixer_params(cfg: dict) -> int:
    la = _la(cfg)
    return (kda_matmul_params(cfg)
            + 3 * la["short_conv_kernel_size"] * kda_inner(cfg)
            + la["num_heads"] + kda_inner(cfg) + la["head_dim"])


def mla_matmul_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, pe, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["kv_lora_rank"], cfg["v_head_dim"])
    return d * h * (nope + pe) + d * (r + pe) + r * h * (nope + v) + h * v * d


def mla_mixer_params(cfg: dict) -> int:
    return mla_matmul_params(cfg) + cfg["kv_lora_rank"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["router_outputs"]


def moe_ffn_params(cfg: dict) -> int:
    """An expert layer's feed-forward as held here."""
    return router_params(cfg) + (cfg["num_shared_experts"]
                                 + cfg["num_experts"]) * expert_params(cfg)


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def n_moe(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def total_params(cfg: dict) -> int:
    """Every stored weight of this chip's share (untied: table and head)."""
    d = cfg["hidden_size"]
    return (n_kda(cfg) * kda_mixer_params(cfg)
            + n_mla(cfg) * mla_mixer_params(cfg)
            + cfg["first_k_dense_replace"] * dense_ffn_params(cfg)
            + n_moe(cfg) * moe_ffn_params(cfg)
            + 2 * cfg["num_hidden_layers"] * d + d
            + 2 * embedding_params(cfg))


def routed_expert_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """The routed experts this chip holds, every expert layer."""
    return (n_moe(cfg) * cfg["num_experts"] * expert_params(cfg)
            * bytes_per_param)


def state_values(cfg: dict) -> int:
    """Values of one sequence's state in ONE KDA layer."""
    la = _la(cfg)
    return la["num_heads"] * la["head_dim"] * la["head_dim"]


def window_bytes(cfg: dict) -> int:
    """One sequence's convolution windows in ONE KDA layer."""
    return ((_la(cfg)["short_conv_kernel_size"] - 1) * 3 * kda_inner(cfg)
            * WINDOW_BYTES)


def slot_state_bytes(cfg: dict) -> int:
    """What one sequence holds that does not page: state and windows, every
    KDA layer."""
    return n_kda(cfg) * (state_values(cfg) * STATE_BYTES + window_bytes(cfg))


def latent_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes a live position costs ONE MLA layer's decode attention to read
    (1,152 in bf16; the stored row is padded to 1,280)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def kda_kernel_bytes(cfg: dict, live_rows: float) -> float:
    """Bytes the kernel ``kda_state_update`` must move in ONE token-step for
    ``live_rows`` decoding rows, every KDA layer: a row's state read and
    written, and what the call reads and writes a row beside it (``q``,
    ``k``, ``v`` in bf16, the log decay in float32, ``beta`` a head, the
    output in float32)."""
    i, heads = kda_inner(cfg), _la(cfg)["num_heads"]
    a_row = (2 * state_values(cfg) * STATE_BYTES
             + 3 * i * 2 + i * 4 + heads * 4 + i * 4)
    return float(live_rows) * n_kda(cfg) * a_row


def decode_step_bytes(cfg: dict, live_rows: float, live_positions: float,
                      experts_hit: float = 1.0) -> float:
    """Bytes ONE decode token-step must move: every weight a token-step
    needs once (all of them less the embedding table, of the held routed
    experts only the share ``experts_hit`` that some decoding row chose), the
    decoding rows' state and windows both ways, and the latent of every live
    position in every MLA layer.  The WORK, whatever reads it: an expert no
    row chose and a slot that does not decode are none, so no later skip can
    read over 100."""
    return ((total_params(cfg) - embedding_params(cfg)) * 2
            - (1.0 - experts_hit) * routed_expert_bytes(cfg)
            + float(live_rows) * 2 * slot_state_bytes(cfg)
            + float(live_positions) * n_mla(cfg) * latent_bytes(cfg))


def token_matmul_params(cfg: dict) -> float:
    """Weights a token must be multiplied by on this chip, the head apart:
    every mixer's matrices, the dense feed-forward, and in an expert layer
    the router, the shared expert and the held experts the token CHOSE
    (``num_experts_per_token`` of ``router_outputs`` fall on ``num_experts``
    held ones: half an expert a token here).  The products with held experts
    a token did not choose are the program's cost, not work."""
    chosen_held = (cfg["num_experts_per_token"] * cfg["num_experts"]
                   / cfg["router_outputs"])
    return (n_kda(cfg) * kda_matmul_params(cfg)
            + n_mla(cfg) * mla_matmul_params(cfg)
            + cfg["first_k_dense_replace"] * dense_ffn_params(cfg)
            + n_moe(cfg) * (router_params(cfg)
                            + (cfg["num_shared_experts"] + chosen_held)
                            * expert_params(cfg)))


def token_flops(cfg: dict) -> float:
    """Operations a token needs whatever its position: its multiplies and
    the recurrence as defined (``STATE_OPS`` a value of the state a KDA
    layer).  Attention against the cached positions and the head are apart."""
    return (2.0 * token_matmul_params(cfg)
            + STATE_OPS * n_kda(cfg) * state_values(cfg))


def head_flops(cfg: dict) -> float:
    return 2.0 * embedding_params(cfg)


def attention_pair_flops(cfg: dict) -> float:
    """Operations a (query, key) pair costs the MLA layers, expanded: every
    head a score over ``nope + pe`` values and a weighted sum over ``v``."""
    return 2.0 * n_mla(cfg) * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def chunk_flops(cfg: dict, p0: int, tokens: int, is_last: bool) -> float:
    """Operations a prompt chunk of ``tokens`` real tokens from position
    ``p0`` needs: ``token_flops`` a token, the 7 MLA layers' attention
    expanded against the prompt so far (a token at position p sees p + 1
    keys) and, where the chunk is the prompt's last, the head ONCE.  A
    chunk's padding, the chunked form's masked half, the products with held
    experts a token did not choose and the head's other rows are the
    program's cost, not work."""
    s, p = float(tokens), float(p0)
    keys = s * p + s * (s + 1) / 2.0
    return (token_flops(cfg) * s + attention_pair_flops(cfg) * keys
            + (head_flops(cfg) if is_last else 0.0))


def served_flops(cfg: dict, prompt_tokens: float,
                 emitted_tokens: float) -> float:
    """Operations of a span of serving from its two counters: every prompt
    and every emitted token through the layers, the head an emitted token.
    Attention's pairs are left out (they need positions no counter books:
    under a tenth of the rest at this cell's lengths), so the share of the
    peak reads the lower for it."""
    return (token_flops(cfg) * (prompt_tokens + emitted_tokens)
            + head_flops(cfg) * emitted_tokens)
