"""Router and wire: bytes of the frames a query's exchange with its shard
sent and received (``wire_tx_bytes`` + ``wire_rx_bytes`` of the router's
``rpc:query`` spans, length prefixes included), per ``rpc:query`` span.
None where no such span carries them: a one-chip cell, or a program whose
wire counts nothing."""

from benchmarks.chip import hostlayers as hl


def read(run):
    tx = hl.attr_per_unit(run.spans, "rpc:query", "wire_tx_bytes")
    rx = hl.attr_per_unit(run.spans, "rpc:query", "wire_rx_bytes")
    if tx is None or rx is None:
        return None
    return tx + rx
