"""The benchmark's arithmetic for a hybrid state-space configuration
(``model_type`` ``granitemoehybrid`` with no routed experts): parameters, the
bytes a decode token-step and its state-update kernel must move, and the
operations a prompt needs.  Kept with the benchmark, whatever the program
implements them with.  ``cfg`` is a configuration file's dict under the
published key names.  A multiply-add counts as 2 operations.

Per layer kind (granite-4.0-h-micro: 36 ``mamba`` and 4 ``attention`` in
``layer_types``, a gated feed-forward after each):

- a Mamba-2 mixer: in-projection ``d x (2I + 2N + H)`` (``I = mamba_expand x
  d = H x P``), depthwise convolution ``(I + 2N) x K`` with bias, ``dt_bias``,
  ``A_log`` and ``D`` a head, the gated norm's ``I``, out-projection ``I x
  d``;
- an attention mixer: q and o ``d x d``, k and v ``d x (kv heads x head
  width)``;
- the feed-forward: ``d x 2F`` and ``F x d`` (``F =
  shared_intermediate_size``); two norm vectors a layer.

A sequence's recurrent state is ``H x P x N`` values a Mamba layer, float32
at rest (the configuration's ``assumed.state_precision``), and its
convolution window ``(K - 1) x (I + 2N)`` bf16.  A cached position is keys
and values of the attention layers only.
"""

from __future__ import annotations

STATE_BYTES = 4   # float32 at rest
WINDOW_BYTES = 2  # bf16


def _kinds(cfg: dict):
    lt = cfg["layer_types"]
    return lt.count("mamba"), lt.count("attention")


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def conv_width(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mamba_matmul_params(cfg: dict) -> int:
    d, i = cfg["hidden_size"], d_inner(cfg)
    return d * (i + conv_width(cfg) + cfg["mamba_n_heads"]) + i * d


def mamba_mixer_params(cfg: dict) -> int:
    conv = conv_width(cfg) * (cfg["mamba_d_conv"]
                              + (1 if cfg["mamba_conv_bias"] else 0))
    return (mamba_matmul_params(cfg) + conv + d_inner(cfg)
            + 3 * cfg["mamba_n_heads"])


def attention_mixer_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"] * head_dim(cfg)
    hkv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * d * hq + 2 * d * hkv


def ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """Every stored weight (tied embeddings: the table once)."""
    nm, na = _kinds(cfg)
    d = cfg["hidden_size"]
    head = 0 if cfg["tie_word_embeddings"] else embedding_params(cfg)
    return (nm * mamba_mixer_params(cfg) + na * attention_mixer_params(cfg)
            + (nm + na) * (ffn_params(cfg) + 2 * d)
            + embedding_params(cfg) + head + d)


def layer_matmul_params(cfg: dict) -> int:
    """Weights a token is multiplied by in the layers (the head apart)."""
    nm, na = _kinds(cfg)
    return (nm * mamba_matmul_params(cfg) + na * attention_mixer_params(cfg)
            + (nm + na) * ffn_params(cfg))


def state_values(cfg: dict) -> int:
    """Values of one sequence's recurrent state in ONE Mamba layer."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def slot_state_bytes(cfg: dict) -> int:
    """What one sequence holds that does not page: state and window, every
    Mamba layer."""
    nm, _ = _kinds(cfg)
    return nm * (state_values(cfg) * STATE_BYTES + window_bytes(cfg))


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    _, na = _kinds(cfg)
    return (2 * na * cfg["num_key_value_heads"] * head_dim(cfg)
            * bytes_per_value)


def window_bytes(cfg: dict) -> int:
    """One sequence's convolution window in ONE Mamba layer: its last
    ``K - 1`` inputs, the real channels (the program pads a tap's 4,352
    channels to 48 rows of 128; padding is its cost, not work)."""
    return (cfg["mamba_d_conv"] - 1) * conv_width(cfg) * WINDOW_BYTES


def ssm_kernel_bytes(cfg: dict, live_rows: float) -> float:
    """Bytes the state-update kernel must move in ONE token-step for
    ``live_rows`` decoding rows.  Since PR 44 the kernel is the whole
    layer-step between a Mamba layer's two projections, so a row's state AND
    its window are read and written once a layer inside it (before, the
    window was kept outside the kernel and was not counted: 1.2% of the
    state's bytes).  Its other operands, 25 KB a row a layer of projection in
    and output out, are left out: the share reads the lower for it."""
    nm, _ = _kinds(cfg)
    return float(live_rows) * nm * 2 * (state_values(cfg) * STATE_BYTES
                                        + window_bytes(cfg))


def decode_step_bytes(cfg: dict, live_rows: float,
                      live_positions: float) -> float:
    """Bytes ONE decode token-step must move: every weight once (the tied
    table is the head's weight), the decoding rows' state and window both
    ways, the live positions' keys and values."""
    return (total_params(cfg) * 2 + float(live_rows) * 2 * slot_state_bytes(
        cfg) + float(live_positions) * kv_bytes_per_position(cfg))


def chunk_flops(cfg: dict, p0: int, tokens: int, is_last: bool) -> float:
    """Operations a prompt chunk of ``tokens`` real tokens from position
    ``p0`` needs: the layers' multiplies a token, the recurrence as defined (a
    multiply-add an element of the state for the update and one for the
    output, a Mamba layer a token), the attention layers' scores and values
    against the prompt so far (a token at position p sees p + 1 keys), and,
    where the chunk is the prompt's last, the head ONCE (only the last
    position's logits are needed).  A chunk's padding, the chunked form's
    masked half and the head's other rows are the program's cost, not
    work."""
    nm, na = _kinds(cfg)
    s, p = float(tokens), float(p0)
    keys = s * p + s * (s + 1) / 2.0  # sum of p + 1 over the chunk
    attn = 4.0 * na * cfg["num_attention_heads"] * head_dim(cfg) * keys
    return (2.0 * layer_matmul_params(cfg) * s
            + 4.0 * nm * state_values(cfg) * s + attn
            + (2.0 * embedding_params(cfg) if is_last else 0.0))


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Operations a whole prompt needs: its chunks', however it is cut."""
    return chunk_flops(cfg, 0, prompt_len, True)
