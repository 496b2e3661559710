"""``decode_table_live_pct``: of the block-table entries handed to the decode
program between the two ledger reads (``decode_table_pages``: per dispatch
``max_batch_size`` x the table's bucketed width), the share that decoding
slots really held (``decode_live_pages``).  What is left is padding: idle
rows, and the columns between a row's blocks and the longest row's bucket.
A paged-attention kernel whose time follows the live pages touches only this
share of the table, so it is also the most such a kernel can save."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "decode_live_pages",
                                   "decode_table_pages")
