"""Share of the bytes the mesh exchange moved that carried no row: from the
window's deltas of the program's counters, 100 × (bytes_moved −
bytes_payload) / bytes_moved (`parallel.exchange.*`, counted on the host per
exchange by parallel/distributed.py). None when no exchange ran."""


def read(ctx):
    moved = ctx.counters.get("parallel.exchange.bytes_moved", 0)
    if moved <= 0:
        return None
    return 100.0 * (moved - ctx.counters.get("parallel.exchange.bytes_payload", 0)) / moved
