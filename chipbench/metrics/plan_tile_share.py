"""Percent of the dense route's K/V tiles that the decode plan fetched,
summed over the window's jobs (``serve()``'s ``decode_fetch`` counts:
``kv_fetch_tiles_plan / kv_fetch_tiles_dense``)."""


def read(run):
    f = [j.report.get("decode_fetch") for j in run.jobs]
    if not f or not all(f):
        return None
    dense = sum(x["kv_fetch_tiles_dense"] for x in f)
    return 100.0 * sum(x["kv_fetch_tiles_plan"] for x in f) / dense \
        if dense else None
