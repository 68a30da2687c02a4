"""Driver for ``kind: closed_loop``: the queue is kept topped up from the
seeded list (``queue_target`` waiting), so a free slot never waits."""
import serve_loop

COMPARES = serve_loop.COMPARES


def run(r) -> None:
    serve_loop.run(r, open_loop=False)
