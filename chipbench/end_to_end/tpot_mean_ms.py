"""``tpot_mean_ms``: mean over the window's streams of
(last token - first token) / (tokens - 1), client's clock: the stream's pace,
whatever the framing of ``decode_chunk``.  Every stream of the window counts
once, so the decode step and what prompt chunks put between a stream's tokens
both show, and no single stream decides the reading (PERF.md section 2)."""

from chipbench.spec import tpot_ms


def read(evidence):
    vals = tpot_ms(evidence)
    return sum(vals) / len(vals) if vals else None
