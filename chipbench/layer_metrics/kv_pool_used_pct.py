"""``kv_pool_used_pct``: mean over the window of KV blocks in use over blocks
in the pool, from the engine's exact bookkeeping
(``utilization()["kv_blocks"]``), sampled once a second."""


def read(evidence):
    samples = [s for s in evidence.get("util_samples", ())
               if 0 <= s["t"] <= evidence["seconds"] and s["kv_total"]]
    if not samples:
        return None
    return 100.0 * sum(s["kv_used"] / s["kv_total"] for s in samples) / len(samples)
