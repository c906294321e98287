"""95th percentile, over the tickets answered inside the window, of the
time each waited in the server's ingress queue: the program's own
``Ticket.dequeued_at - Ticket.submitted_at`` stamps."""
from bench.traffic import percentile


def read(run):
    waits = [
        (r.dequeued - r.submitted) * 1e3
        for r in run.completed_in_window()
        if r.dequeued is not None
    ]
    return percentile(waits, 95) if waits else None
