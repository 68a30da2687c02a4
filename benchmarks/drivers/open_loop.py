"""Driver for ``kind: open_loop``: requests are submitted when they are
due (``rate_per_s`` and ``arrivals`` of the traffic file), whether or not
earlier ones have finished; latencies count from the due time."""
import serve_loop

COMPARES = serve_loop.COMPARES


def run(r) -> None:
    serve_loop.run(r, open_loop=True)
