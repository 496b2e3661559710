"""``full_attn_decode_roofline_pct``: the FULL layers' decode attention
calls' share of their roofline in a Laguna share's decode program, beside
``window_attn_decode_roofline_pct`` (whose arithmetic this is: the rows
counted over the traced dispatches, the traced token-steps, the calls' self
time).  Bytes: ``model_math_laguna.full_attention_bytes``: the keys and
values of every position a decoding row holds (``length + 1``: the counter
``decode_full_positions`` over ``decode_live_rows``), every full layer, a
token-step.  The kernel fetches whole blocks of ``block_size`` positions, so
the share counts the last block's unused rows as no work.  The calls are
found by their NAME, ``paged_attention`` (groups of 6 query heads a KV head,
tables up to 2,048 blocks wide); the window layers' carry another.  Nothing
is read on a program that books no ``decode_full_positions``: Mistral's cell
has the same kernel name and no such counter."""

from chipbench import model_math_laguna as math_
from chipbench import spec

KERNEL = r"^paged_attention"


def read(evidence):
    share_pct = spec.load_module(
        "layer_metrics", "window_attn_decode_roofline_pct").share_pct
    return share_pct(evidence, KERNEL, "decode_full_positions",
                     math_.full_attention_bytes)
