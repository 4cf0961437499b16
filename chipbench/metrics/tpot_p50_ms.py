"""Time per output token, the median over requests (host clock).

For every request that finished inside the window: the seconds from when
it was due to be sent (its submission, in a closed loop) to its last token,
over the tokens it was served.  Its own admission and prefill are in it,
and in an open loop its wait for a slot.  A window holds some tens of requests,
too few for a tail: the 95th percentile of 30 is their second largest."""
import statistics


def read(m):
    per = [lat / n for lat, n in m.completed if n > 0]
    if not per:
        return None
    return statistics.median(per) * 1e3
