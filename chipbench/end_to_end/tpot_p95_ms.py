"""``tpot_p95_ms``: 95th percentile over the window's requests of
(last token - first token) / (tokens - 1), client's clock: the stream's pace,
whatever the framing of ``decode_chunk``."""

from chipbench.spec import log, percentile


def read(evidence):
    rows = [r for r in evidence["rows"] if r["phase"] == "window"
            and r["ok"] and r["got"] >= 2]
    if not rows or evidence["traffic"]["loop"] != "open":
        return None
    vals = [(r["last"] - r["first"]) / (r["got"] - 1) * 1e3 for r in rows]
    log(f"tpot_ms over {len(vals)} requests: p50 {percentile(vals, 50):.2f} "
        f"p95 {percentile(vals, 95):.2f} max {max(vals):.2f}")
    return percentile(vals, 95)
