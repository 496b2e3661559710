"""``loadgen_late_p95_ms``: how late the load generator ran, 95th percentile
over the window's requests of (sent - due), generator's own clock.  A starved
generator must not be read as a fast server."""

from chipbench.spec import percentile


def read(evidence):
    vals = [(r["sent"] - r["due"]) * 1e3 for r in evidence.get("rows", ())
            if r["phase"] == "window" and r.get("due") is not None
            and r["sent"] is not None]
    return percentile(vals, 95) if vals else None
