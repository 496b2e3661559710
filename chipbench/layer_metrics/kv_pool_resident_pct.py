"""``kv_pool_resident_pct``: blocks of the pool that hold something a request
can use, at the end of the run: those live requests hold (``used``) plus
free blocks that still hold a registered prefix (``cached``: a later match
revives them), over the pool (``utilization()["kv_blocks"]`` in the report
taken after the drain).  ``kv_pool_used_pct`` counts only the first kind:
in a cell that re-reads cached documents it reads the live rows' share and
this reads what the pool holds."""


def read(evidence):
    rep = evidence.get("report_after") or {}
    kv = (rep.get("utilization") or {}).get("kv_blocks") or {}
    if not kv.get("total") or "cached" not in kv:
        return None
    return 100.0 * (kv["used"] + kv["cached"]) / kv["total"]
