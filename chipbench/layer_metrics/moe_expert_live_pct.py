"""``moe_expert_live_pct``: of the held experts whose weights a decode
token-step read (``moe_experts_held``: experts held x expert layers, a
token-step in which any row decodes), the share that at least one decoding
row chose (``moe_experts_hit``), between the two ledger reads.  The decode
program books both itself.  What is left is weight traffic no token asked
for: with 16 of 256 experts here and 8 a token, a row lands on 0.5 held
experts, so at 16 decoding rows about two fifths of the experts are live."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "moe_experts_hit",
                                   "moe_experts_held")
