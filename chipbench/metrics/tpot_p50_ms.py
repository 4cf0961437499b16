"""Time per output token, the median over requests (host clock).

For every request that finished inside the window: the seconds from its
submission to its last token, over the tokens it was served.  Its own
admission and prefill are in it.  A window holds some tens of requests,
too few for a tail: the 95th percentile of 30 is their second largest."""
import statistics


def read(m):
    per = [lat / n for lat, n in m.completed if n > 0]
    if not per:
        return None
    return statistics.median(per) * 1e3
