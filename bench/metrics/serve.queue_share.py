"""Share of request latency spent queued in the serving engine
(``serving/assign.py``): the ``queue_seconds`` of the service's
``serve/request`` events over their ``total_seconds``, summed over the
window's requests (source: program spans)."""


def read(run):
    reqs = run.counters.get("serve_requests", ())
    total = sum(r["total_seconds"] for r in reqs)
    if not reqs or total <= 0:
        return None
    return 100.0 * sum(r["queue_seconds"] for r in reqs) / total
