"""Router and wire: what a query costs for crossing to its shard, in
milliseconds.  For each of the router's ``rpc:query`` spans (``pid`` 0)
with the shard's ``query`` span parented to it (``pid`` i + 1 for shard
i), the rpc span's duration less the shard span's; the mean over them.
Both are durations, each on its own process's clock, so the clock offset
between router and shard cancels.  What it holds: framing and the socket
both ways, and the shard's wait between the request's frame and its
server starting the query.  None where no such pair exists, as in a
one-chip cell."""


def read(run):
    rpc = {sp["s"]: sp["d"] for sp in run.spans
           if sp["n"] == "rpc:query" and sp.get("pid") == 0}
    diffs = [rpc[sp["p"]] - sp["d"] for sp in run.spans
             if sp["n"] == "query" and sp["p"] in rpc]
    if not diffs:
        return None
    return 1e3 * sum(diffs) / len(diffs)
